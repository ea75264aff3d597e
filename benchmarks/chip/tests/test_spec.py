"""A configuration is files found by name: its reference, its field map,
its work counts and its CPU test form.  One is added here as files only,
in a directory of its own, and runs a serve cell and a train cell on the
CPU; the two configurations the benchmark has read what they read before
these files could be named."""

import copy
import dataclasses
import json
import os
import time
import types

import pytest

import flops
import reference
import run
import spec

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
NAME = "dense-as-files"
SEED = 2 ** 31 + 54321
LIMITS = {"serve": "minicpm-2b.serve.chat",
          "train": "mamba2-370m.train.commit"}
TRAFFIC = {"serve": "serve.chat", "train": "train.commit"}

WRAPPED_REFERENCE = '''
"""reference.py, recording each call."""
import reference

CALLS = []


def _recorded(f):
    def call(*a, **kw):
        CALLS.append(f.__name__)
        return f(*a, **kw)
    return call


class Sizes:
    @staticmethod
    def from_file(config_file):
        CALLS.append("from_file")
        return reference.Sizes.from_file(
            {k: v for k, v in config_file.items() if k != "reference"})


init_weights = _recorded(reference.init_weights)
forward_logits = _recorded(reference.forward_logits)
train_readings = _recorded(reference.train_readings)
'''

DOUBLED_WORK = '''
"""Twice the matrix-product weights and twice the weight bytes."""
import flops


def matmul_params(cfg):
    return 2 * flops.matmul_params(cfg)


def weight_bytes(cfg):
    return 2 * flops.weight_bytes(cfg)
'''


def _tiny_minicpm():
    with open(os.path.join(TINY, "minicpm-2b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A configuration added as files: its file (with ``reference``,
    ``fields`` and ``work``), its reference, its work module and its tiny
    form, and the benchmark with its two cells."""
    d = tmp_path_factory.mktemp("added")
    (d / "tiny").mkdir()
    (d / "wrapped_reference.py").write_text(WRAPPED_REFERENCE)
    (d / "doubled_work.py").write_text(DOUBLED_WORK)
    config = _tiny_minicpm()
    config.update(name=NAME, reference=str(d / "wrapped_reference.py"),
                  work=str(d / "doubled_work.py"),
                  fields={"aux_loss_coef": "router_aux_coef",
                          "global_layer_ids": "global_layers"})
    config["config"].update(aux_loss_coef=0.0123, global_layer_ids=[0, 1])
    (d / f"{NAME}.json").write_text(json.dumps(config))
    (d / "tiny" / f"{NAME}.json").write_text(json.dumps(
        {"preset": "smoke", "config": config["config"]}))
    bench = copy.deepcopy(spec.benchmark())
    bench["configs"].append({"name": NAME, "file": str(d / f"{NAME}.json")})
    bench["workloads"] += [{"name": f"{NAME}.{t}", "config": NAME,
                            "traffic": t, "chips": 1}
                           for t in TRAFFIC.values()]
    return types.SimpleNamespace(dir=d, bench=bench)


def _limits(kind):
    return run.limits_of(LIMITS[kind])


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_added_configuration_runs_correct(added, tiny, kind):
    cell = tiny(f"{NAME}.{TRAFFIC[kind]}", added.bench,
                str(added.dir / "tiny"))
    wrapped = cell.reference()
    assert wrapped is not reference and wrapped.__file__ == str(
        added.dir / "wrapped_reference.py")
    cfg = cell.model_config()
    assert cfg.router_aux_coef == 0.0123 and cfg.global_layers == (0, 1)
    hash(cfg)
    del wrapped.CALLS[:]
    r = run.execute(cell, SEED, 2.0, False, require_tpu=False,
                    t_start=time.perf_counter(), limits=_limits(kind))
    checks = {c.name: c for c in r.pop("_checks")}
    assert r["correct"], checks
    called = "forward_logits" if kind == "serve" else "train_readings"
    assert "from_file" in wrapped.CALLS and called in wrapped.CALLS


def _event(seconds):
    return types.SimpleNamespace(start=0.0, end=seconds * 1e9)


def _record(cell, **counters):
    return types.SimpleNamespace(
        cell=cell, cfg=cell.model_config(), window=(0.0, 10.0),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        counters=counters, modules=lambda name: [_event(0.25)] * 3)


def test_mfu_readers_count_through_the_work_module(added):
    cell = spec.load_cell(f"{NAME}.serve.chat", added.bench)
    rec = _record(cell, batch=4, seq_len=64,
                  intervals=[(1.0, [9, 30]), (2.0, [10, 31, 5])])
    work = flops.of(rec)
    assert work is not flops and flops.of(rec) is work
    # a helper it overrides is the one this file's own functions call
    assert work.forward_flops(rec.cfg, 64) - flops.forward_flops(
        rec.cfg, 64) == 2 * flops.matmul_params(rec.cfg) * 64
    assert work.kv_bytes_per_position is not flops.kv_bytes_per_position

    train_mfu = run.load_metric("train_step_mfu")(rec)
    assert train_mfu == 100.0 * work.train_step_flops(rec.cfg, 4, 64) / (
        0.25 * 197e12)
    least = [flops.roofline_s(w["flops"], w["bytes"], rec.peaks)[0]
             for w in (work.decode_interval(rec.cfg, live)
                       for _, live in rec.counters["intervals"])]
    decode_mfu = run.load_metric("decode_step_mfu")(rec)
    assert decode_mfu == 100.0 * (sum(least) / 2) / 0.25

    plain = dataclasses.replace(cell, config={
        k: v for k, v in cell.config.items() if k != "work"})
    rec.cell = plain
    assert run.load_metric("train_step_mfu")(rec) < train_mfu
    assert run.load_metric("decode_step_mfu")(rec) < decode_mfu


def test_unknown_field_name_raises(added):
    cell = spec.load_cell(f"{NAME}.serve.chat", added.bench)
    cell.config["fields"]["aux_loss_coef"] = "no_such_field"
    with pytest.raises(KeyError, match=f"{NAME}.json.*aux_loss_coef"):
        cell.model_config()


def test_a_cell_with_no_tiny_form_names_the_missing_file(added, tiny,
                                                         tmp_path):
    with pytest.raises(FileNotFoundError, match=f"{NAME}.json"):
        tiny(f"{NAME}.serve.chat", added.bench, str(tmp_path))


@pytest.mark.parametrize("name", ["mamba2-370m.train.commit",
                                  "minicpm-2b.serve.chat"])
def test_existing_configurations_read_as_before(name):
    from repro.configs import full_config

    cell = spec.load_cell(name)
    assert cell.reference() is reference
    assert flops.of(types.SimpleNamespace(cell=cell)) is flops
    c = cell.config["config"]  # the keys through ``FIELDS`` alone
    want = {spec.FIELDS[k]: v for k, v in c.items() if k in spec.FIELDS}
    want["vocab_size"] = spec.padded_vocab(c)
    assert cell.model_config() == full_config(cell.config["arch"]).with_(
        **want)


def test_reference_reads_the_existing_files_as_before():
    def sizes(name):
        return reference.Sizes.from_file(spec.load_cell(name).config)

    assert sizes("mamba2-370m.train.commit") == reference.Sizes(
        kind="ssm", n_layers=48, d_model=1024, vocab=50288, d_state=128,
        expand=2, headdim=64, d_conv=4, dtype="bfloat16")
    assert sizes("minicpm-2b.serve.chat") == reference.Sizes(
        kind="dense", n_layers=40, d_model=2304, vocab=122753, n_heads=36,
        n_kv_heads=36, d_head=64, d_ff=5760, rope_theta=10000.0,
        norm_eps=1e-5, dtype="bfloat16")


@pytest.mark.parametrize("change, fault", [
    ({"reference": "references/other.py"}, "its reference is"),
    ({"drop": "head_dim"}, r"lacks \['head_dim'\]"),
    ({"drop": "intermediate_size"}, r"lacks \['intermediate_size'\]"),
    ({"fields": {"intermediate_size": "expert_d_ff"}},
     r"remap \['intermediate_size'\]"),
])
def test_reference_refuses_a_file_it_cannot_read(change, fault):
    config = copy.deepcopy(spec.load_cell("minicpm-2b.serve.chat").config)
    config["config"].pop(change.pop("drop", None), None)
    config.update(change)
    with pytest.raises(ValueError, match=fault):
        reference.Sizes.from_file(config)
