"""Checkpoint-as-commit: save/restore roundtrip, async manager, digests,
elastic reshard, fault-tolerant trainer resume."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, columns_to_tree,
                              latest_checkpoint, leaves_to_columns, restore,
                              restore_into, save)
from repro.configs import smoke_config
from repro.models import init_params
from repro.optim import adamw

KEY = jax.random.PRNGKey(0)


def _params():
    return init_params(smoke_config("paper-demo"), KEY)


def test_leaves_columns_roundtrip():
    tree = {"a": np.arange(6).reshape(2, 3), "b": {"c": np.ones(4)}}
    cols = leaves_to_columns(tree)
    back = columns_to_tree(cols)
    np.testing.assert_array_equal(back["a"], tree["a"])
    np.testing.assert_array_equal(back["b"]["c"], tree["b"]["c"])


def _transposed_layout(x):
    """``x`` held on the device with its last two axes swapped in memory,
    as a TPU holds leaves whose last axis would pad its tiles most."""
    from jax.experimental.layout import Format, Layout

    order = (*range(x.ndim - 2), x.ndim - 1, x.ndim - 2)
    y = jax.device_put(x, Format(Layout(major_to_minor=order), x.sharding))
    assert not np.asarray(y).flags.c_contiguous
    return y


@pytest.mark.parametrize("shape", [(48, 32), (4, 16, 40)])
def test_commit_path_copies_no_column_on_the_host(lake, shape):
    """Leaves in a transposed device layout reach the tensorfile in row
    order: every ``table.encode`` of the commit reports ``copied`` 0."""
    from repro import tracing

    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    params = {"w": _transposed_layout(x), "b": jnp.ones(3, jnp.bfloat16)}
    opt = {"mu": _transposed_layout(x * 2)}
    lake.catalog.create_branch("t.run", "main", author="t")
    commit = save(lake, "t.run", step=1, params=params, opt_state=opt,
                  author="t")
    (root,) = [s for s in tracing.spans() if s.name == "ckpt.save"][-1:]
    encodes = [s for s in tracing.spans()
               if s.name == "table.encode" and s.t0 >= root.t0
               and s.t1 <= root.t1]
    assert [s.attrs["copied"] for s in encodes] == [0, 0]
    p2, opt2, _ = restore(lake, commit)
    np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(x))
    np.testing.assert_array_equal(opt2["mu"], np.asarray(x * 2))


def test_async_manager_host_copies_are_row_major(lake):
    lake.catalog.create_branch("t.run", "main", author="t")
    x = jnp.arange(48 * 32, dtype=jnp.float32).reshape(48, 32)
    mgr = CheckpointManager(lake, "t.run", author="t")
    mgr.submit(step=1, params={"w": _transposed_layout(x)})
    ((_, commit),) = mgr.wait()
    mgr.close()
    from repro import tracing

    encoded = [s for s in tracing.spans() if s.name == "table.encode"][-1]
    assert encoded.attrs["copied"] == 0
    p2, _, _ = restore(lake, commit)
    np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(x))


def test_save_restore_roundtrip(lake):
    params = _params()
    opt = adamw.init(params, adamw.AdamWConfig())
    commit = save(lake, "main", step=7, params=params, opt_state=opt,
                  _wap_token=True) if False else None
    # main is protected; use a user branch like the trainer does
    lake.catalog.create_branch("t.run", "main", author="t")
    commit = save(lake, "t.run", step=7, params=params, opt_state=opt,
                  author="t")
    p2, opt_cols, meta = restore(lake, commit)
    assert meta["step"] == 7
    flat1 = jax.tree.leaves(params)
    flat2 = jax.tree.leaves(p2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # typed opt-state restore
    template = adamw.init(p2, adamw.AdamWConfig())
    cols = lake.io.read(lake.catalog.tables(commit)["ckpt_opt"])
    opt2 = restore_into(template, cols)
    assert isinstance(opt2, adamw.AdamWState)
    assert int(opt2.step) == int(opt.step)


def test_restore_any_historical_commit(lake):
    params = _params()
    lake.catalog.create_branch("t.run", "main", author="t")
    c1 = save(lake, "t.run", step=1, params=params, author="t")
    p_later = jax.tree.map(lambda x: x + 1 if x.dtype != bool else x, params)
    save(lake, "t.run", step=2, params=p_later, author="t")
    p1, _, meta1 = restore(lake, c1)  # time travel to step 1
    assert meta1["step"] == 1
    np.testing.assert_array_equal(np.asarray(p1["embed"]),
                                  np.asarray(params["embed"]))


def test_digest_verification(lake):
    params = _params()
    lake.catalog.create_branch("t.run", "main", author="t")
    commit = save(lake, "t.run", step=1, params=params, author="t")
    p, _, _ = restore(lake, commit, verify=True)  # digest matches
    meta = lake.catalog.commit_info(commit).meta["checkpoint"]
    assert len(meta["params_digest"]) == 64  # 8 × uint32 hex


def test_async_manager(lake):
    params = _params()
    lake.catalog.create_branch("t.run", "main", author="t")
    mgr = CheckpointManager(lake, "t.run", author="t")
    for s in (1, 2, 3):
        mgr.submit(step=s, params=params)
    commits = mgr.wait()
    assert [s for s, _ in commits] == [1, 2, 3]
    assert latest_checkpoint(lake, "t.run") == commits[-1][1]
    mgr.close()


def test_unchanged_leaves_dedup(lake):
    """Content addressing: identical leaves across checkpoints are stored
    once (the CoW story for model state)."""
    params = _params()
    lake.catalog.create_branch("t.run", "main", author="t")
    save(lake, "t.run", step=1, params=params, author="t")
    n1 = len(list(lake.store.iter_objects()))
    save(lake, "t.run", step=2, params=params, author="t")  # same params
    n2 = len(list(lake.store.iter_objects()))
    assert n2 - n1 <= 2  # only the new snapshot + commit metadata objects


def test_trainer_fault_tolerant_resume_bitexact(lake):
    """Crash at step k, resume, and land on the same final loss as an
    uninterrupted run — proves checkpoint + stateless loader determinism."""
    from repro.data import build_data_pipeline, seed_corpus
    from repro.runtime import Trainer, TrainerConfig

    cfg = smoke_config("paper-demo")
    lake.catalog.create_branch("data.main", "main", author="data")
    seed_corpus(lake, "data.main", n_docs=64, seed=3,
                vocab_size=cfg.vocab_size, mean_len=80, author="data")
    lake.run(build_data_pipeline(32), branch="data.main", author="data")

    def make(run_name, failure_at=None):
        tcfg = TrainerConfig(arch=cfg.name, seq_len=32, global_batch=4,
                             n_steps=8, ckpt_every=4, author="t",
                             schedule="constant",
                             schedule_kw={"peak_lr": 1e-3})
        return Trainer(lake, cfg, tcfg, data_branch="data.main",
                       run_name=run_name, failure_at=failure_at)

    t_clean = make("clean")
    clean = t_clean.run()

    t_faulty = make("faulty", failure_at=6)
    with pytest.raises(RuntimeError):
        t_faulty.run()
    resumed = t_faulty.run(resume=True)
    # resume restarts from the step-4 checkpoint → same final state
    assert resumed["losses"][-1] == pytest.approx(clean["losses"][-1],
                                                  rel=1e-6)
