"""What a cell is: its entry in ``BENCHMARK.json``, its configuration file
and its traffic file, found by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under this directory:

  configs/<config>.json   the configuration as it is run
  references/<config>.py  its plain reference, where ``reference.py``
                          does not cover it
  work/<config>.py        its operations and bytes, where ``flops.py``
                          does not count them right
  tests/tiny/<config>.json  its form for the CPU tests
  traffic/<traffic>.json  the traffic mix or training job
  metrics/<metric>.py     the reduction of one per-layer metric

so a later cell, of a new architecture too, adds files and entries and
edits none.  A configuration file names its own pieces:

  "reference": "references/<config>.py"   (default ``reference.py``)
  "work": "work/<config>.py"              (default ``flops.py``)
  "fields": {"<config key>": "<ModelConfig field>", ...}

paths relative to this directory (or absolute).  ``fields`` is laid over
``FIELDS`` for that file alone: a file whose ``intermediate_size`` is the
expert width maps it with ``{"intermediate_size": "expert_d_ff"}``.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
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


_MODULES: dict = {}


def load_module(path: str):
    """The Python file at ``path`` (relative to this directory, or
    absolute) as a module, loaded once per path; a file of this directory
    itself is its module as imported."""
    path = os.path.normpath(os.path.join(HERE, path))
    if path not in _MODULES:
        stem = os.path.splitext(os.path.relpath(path, HERE))[0]
        if os.path.dirname(path) == HERE:
            _MODULES[path] = importlib.import_module(stem)
        else:
            mod_spec = importlib.util.spec_from_file_location(
                "bench_" + "".join(c if c.isalnum() else "_" for c in stem),
                path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            _MODULES[path] = mod
    return _MODULES[path]


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
    config_file: str = ""  # where ``config`` was read, for messages

    def model_config(self):
        """The program's configuration for this cell: its registry entry
        (the smoke preset where the file says so) with the sizes the
        configuration file states applied over it, each key through the
        file's ``fields`` or else ``FIELDS``."""
        if SRC not in sys.path:
            sys.path.insert(0, SRC)
        from repro.configs import full_config, smoke_config
        from repro.models.config import ModelConfig

        preset = smoke_config if self.config.get("preset") == "smoke" \
            else full_config
        fields = {**FIELDS, **self.config.get("fields", {})}
        known = {f.name for f in dataclasses.fields(ModelConfig)}
        want = {}
        for k, v in self.config["config"].items():
            if k not in fields:
                continue
            if fields[k] not in known:
                raise KeyError(
                    f"{self.config_file or self.config.get('name')}: key "
                    f"{k!r} maps to {fields[k]!r}, which ModelConfig lacks")
            # ModelConfig is frozen and hashed (the engine caches on it)
            want[fields[k]] = tuple(v) if isinstance(v, list) else v
        want["vocab_size"] = padded_vocab(self.config["config"])
        return preset(self.config["arch"]).with_(**want)

    def reference(self):
        """The plain reference module this configuration is compared with:
        the file its ``reference`` names, else ``reference.py``."""
        return load_module(self.config.get("reference", "reference.py"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config_file = os.path.join(ROOT, configs[w["config"]]["file"])
    traffic = _read_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=_read_json(config_file),
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        config_file=config_file)
