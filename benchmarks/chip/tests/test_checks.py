"""What decides ``correct``: a sound run passes, the control (the plain
reference at float8 in the program's place) and each planted fault fail.

The runs are the cells' own code path at the programs' smoke sizes on the
CPU, with the look for a chip skipped; the limits are the cells' own."""

import json
import os
import time

import jax
import numpy as np
import pytest

import digest
import reference
import run
import serve
import train

LIMITS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "limits")
TRAIN = "mamba2-370m.train.commit"
CHAT = "minicpm-2b.serve.chat"
SEED = 2 ** 31 + 12345


def limits(cell):
    with open(os.path.join(LIMITS, cell + ".json")) as f:
        return json.load(f)["limits"]


def execute(cell, seconds=2.0):
    r = run.execute(cell, SEED, seconds, False, require_tpu=False,
                    t_start=time.perf_counter(), limits=limits(cell.name))
    return r, {c.name: c for c in r.pop("_checks")}


def test_reference_weights_are_the_programs(tiny):
    from repro.models import init_params

    for name in (TRAIN, CHAT):
        cell = tiny(name)
        got = reference.init_weights(
            reference.Sizes.from_file(cell.config), 77)
        want = init_params(cell.model_config(), jax.random.PRNGKey(77))
        flat = {str(p[-1].key): np.asarray(x) for p, x in
                jax.tree_util.tree_leaves_with_path(want)}
        assert set(flat) == set(got)
        for k, v in got.items():
            assert np.array_equal(np.asarray(v), flat[k]), k


def test_benchmark_digest_is_the_programs():
    from repro.kernels.fingerprint.ops import tree_digest_hex

    rng = np.random.default_rng(0)
    tree = {"a": jax.numpy.asarray(rng.normal(size=(33, 7)),
                                   jax.numpy.bfloat16),
            "b": {"c": jax.numpy.asarray(rng.normal(size=(5,)),
                                         jax.numpy.float32),
                  "d": jax.numpy.asarray(rng.integers(0, 9, (3, 3)),
                                         jax.numpy.int32)}}
    host = jax.tree.map(np.asarray, tree)
    assert digest.tree_digest_hex(host) == tree_digest_hex(tree)


@pytest.mark.parametrize("name", [TRAIN, CHAT])
def test_sound_run_is_correct(tiny, name):
    r, checks = execute(tiny(name))
    assert r["correct"], checks


def test_control_fails_train(tiny):
    """The control of the train cells is the program with its own int8
    gradient compression switched on."""
    cell = tiny(TRAIN)
    cell.traffic["optimizer"] = dict(cell.traffic["optimizer"],
                                     compress_grads=True)
    r, checks = execute(cell)
    assert not r["correct"], checks


def test_control_fails_chat(shallow, monkeypatch):
    monkeypatch.setattr(serve, "EXTRA", ("fp8",))
    _, checks = execute(shallow(CHAT), seconds=6.0)
    assert not checks["served_gap.fp8"].ok, checks


def _broken_step(monkeypatch, broken):
    import repro.runtime.trainer as trainer_mod

    build = trainer_mod.build_train_step

    def build_broken(cfg, **kw):
        return broken(build(cfg, **kw))

    monkeypatch.setattr(trainer_mod, "build_train_step", build_broken)


def test_state_left_unchanged_fails(tiny, monkeypatch):
    def broken(step):
        def unchanged(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return unchanged

    _broken_step(monkeypatch, broken)
    r, checks = execute(tiny(TRAIN))
    assert not r["correct"] and not checks["change_gap"].ok, checks


def test_half_the_batch_left_out_fails(tiny, monkeypatch):
    def broken(step):
        def half(params, opt_state, batch):
            t = batch["tokens"]
            return step(params, opt_state, {"tokens": t[:t.shape[0] // 2]})
        return half

    _broken_step(monkeypatch, broken)
    r, checks = execute(tiny(TRAIN))
    assert not r["correct"] and not checks["change_gap"].ok, checks


def test_commit_left_out_of_the_window_fails(tiny, monkeypatch):
    import types

    import repro.runtime.trainer as trainer_mod

    real = trainer_mod.ckpt
    skipping = types.SimpleNamespace(
        **{k: getattr(real, k) for k in dir(real) if not k.startswith("__")})
    skipping.save = lambda *a, **kw: None
    monkeypatch.setattr(trainer_mod, "ckpt", skipping)
    r, checks = execute(tiny(TRAIN))
    assert not r["correct"] and not checks["commits_missing"].ok, checks


def test_committed_bytes_altered_fails(tiny, monkeypatch):
    import repro.checkpoint.saver as saver

    cols = saver.leaves_to_columns

    def altered(tree):
        out = cols(tree)
        if "embed" in out:
            out["embed"] = out["embed"].copy()
            out["embed"].reshape(-1)[0] += 1
        return out

    monkeypatch.setattr(saver, "leaves_to_columns", altered)
    r, checks = execute(tiny(TRAIN))
    assert not r["correct"] and not checks["commit_bytes_differ"].ok, checks


def test_served_token_altered_fails(shallow, monkeypatch):
    from repro.serving.engine import ServeEngine

    decode = ServeEngine.row_decode

    def altered(self):
        real = decode(self)
        vocab = self.cfg.vocab_size

        def step(params, tokens, cache):
            tok, cache = real(params, tokens, cache)
            return (tok + 1) % vocab, cache
        return step

    monkeypatch.setattr(ServeEngine, "row_decode", altered)
    r, checks = execute(shallow(CHAT), seconds=6.0)
    assert not r["correct"] and not checks["served_gap"].ok, checks
