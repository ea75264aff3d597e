"""What a cell is: its entry in ``BENCHMARK.json``, its configuration file
and its traffic file, found by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under this directory:

  configs/<config>.json   the configuration as it is run
  traffic/<traffic>.json  the traffic mix or training job
  metrics/<metric>.py     the reduction of one per-layer metric

so a later cell adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(ROOT, "src")

# ModelConfig field for each key of a configuration file's "config" (the
# published config.json's own names); keys not listed here are documentary
FIELDS = {
    "hidden_size": "d_model", "d_model": "d_model",
    "num_hidden_layers": "n_layers", "n_layer": "n_layers",
    "vocab_size": "vocab_size",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head", "intermediate_size": "d_ff",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "tie_embeddings": "tie_embeddings",
    "d_state": "ssm_state", "expand": "ssm_expand", "headdim": "ssm_head_dim",
    "d_conv": "ssm_conv", "chunk_size": "ssm_chunk",
    "torch_dtype": "param_dtype",
}


def padded_vocab(c: dict) -> int:
    """The embedding's rows: ``vocab_size`` rounded up to a multiple of
    ``pad_vocab_size_multiple`` where the configuration pads it."""
    m = int(c.get("pad_vocab_size_multiple", 1))
    return -(-int(c["vocab_size"]) // m) * m


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list

    def model_config(self):
        """The program's configuration for this cell: its registry entry
        (the smoke preset where the file says so) with the sizes the
        configuration file states applied over it."""
        if SRC not in sys.path:
            sys.path.insert(0, SRC)
        from repro.configs import full_config, smoke_config

        preset = smoke_config if self.config.get("preset") == "smoke" \
            else full_config
        want = {FIELDS[k]: v for k, v in self.config["config"].items()
                if k in FIELDS}
        want["vocab_size"] = padded_vocab(self.config["config"])
        return preset(self.config["arch"]).with_(**want)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])
