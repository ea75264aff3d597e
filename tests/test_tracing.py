"""Host spans (``repro.tracing``): nesting across threads, errors, the
bounded buffer, compile spans, the clock shared with a profiler trace, and
the spans the train loop, the checkpoint commit and the batcher record."""

import glob
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import smoke_config


def since(t0, name=None):
    return [s for s in tracing.spans() if s.t0 >= t0
            and (name is None or s.name == name)]


def subtree(root, spans):
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    out, todo = [], [root]
    while todo:
        s = todo.pop()
        out.append(s)
        todo.extend(kids.get(s.id, []))
    return out


def test_tracing_and_the_core_import_without_jax():
    code = (
        "import sys\n"
        "class NoJax:\n"
        "    def find_spec(self, name, *a):\n"
        "        if name.split('.')[0] in ('jax', 'jaxlib'):\n"
        "            raise ImportError(name)\n"
        "sys.meta_path.insert(0, NoJax())\n"
        "import repro.tracing, repro.core\n"
        "with repro.tracing.span('x'):\n"
        "    pass\n"
        "assert repro.tracing.spans()[-1].name == 'x'\n"
        "assert 'jax' not in sys.modules\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=src))
    assert p.returncode == 0, p.stderr


def test_nesting_and_parents_on_two_threads():
    t0 = time.perf_counter()
    barrier = threading.Barrier(2, timeout=10)

    def work(tag):
        with tracing.span("outer", tag=tag):
            barrier.wait()  # both outers open at once
            with tracing.span("inner", tag=tag):
                barrier.wait()
                with tracing.span("leaf", tag=tag):
                    pass

    threads = [threading.Thread(target=work, args=(k,)) for k in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    got = since(t0)
    for tag in "ab":
        mine = {s.name: s for s in got if s.attrs.get("tag") == tag}
        assert set(mine) == {"outer", "inner", "leaf"}
        assert mine["outer"].parent is None
        assert mine["inner"].parent == mine["outer"].id
        assert mine["leaf"].parent == mine["inner"].id
        assert len({s.thread for s in mine.values()}) == 1
        assert mine["outer"].t0 <= mine["inner"].t0 <= mine["leaf"].t0
        assert mine["leaf"].t1 <= mine["inner"].t1 <= mine["outer"].t1
    assert len({s.thread for s in got if s.name == "outer"}) == 2


def test_a_span_left_by_an_exception_is_kept_and_marked():
    t0 = time.perf_counter()
    with pytest.raises(KeyError):
        with tracing.span("fails", n=1) as s:
            s.set(n=2)
            raise KeyError("x")
    with tracing.span("after"):
        pass
    (bad,), (ok,) = since(t0, "fails"), since(t0, "after")
    assert bad.error == "KeyError" and bad.attrs == {"n": 2}
    assert ok.error is None and ok.parent is None  # the stack unwound


def test_the_buffer_keeps_the_newest_spans():
    n = tracing.CAPACITY + 7
    t = time.perf_counter()
    for i in range(n):
        tracing.record("overflow", t, t, i=i)
    got = tracing.spans()
    assert len(got) == tracing.CAPACITY
    assert got[0].attrs["i"] == 7 and got[-1].attrs["i"] == n - 1


def test_record_and_decorator():
    t0 = time.perf_counter()

    @tracing.span("decorated", kind="fn")
    def f(x):
        with tracing.span("inside"):
            return x + 1

    assert f(1) == 2 and f(2) == 3
    tracing.record("queued", t0 - 1.0, t0 - 0.5, rid=7)
    outer = since(t0, "decorated")
    assert len(outer) == 2 and len({s.id for s in outer}) == 2
    assert all(s.attrs == {"kind": "fn"} for s in outer)
    assert {s.parent for s in since(t0, "inside")} == {s.id for s in outer}
    (q,) = [s for s in tracing.spans() if s.name == "queued"
            and s.attrs == {"rid": 7} and s.t1 == t0 - 0.5]
    assert q.t0 == t0 - 1.0 and q.parent is None


def test_a_fresh_jit_compile_is_a_span_under_the_open_one():
    def tracing_fresh_fn(x):
        return jnp.sin(x) * 3.25 + 0.125

    t0 = time.perf_counter()
    with tracing.span("compiles_here") as outer:
        jax.jit(tracing_fresh_fn)(jnp.arange(5.0)).block_until_ready()
    mine = [s for s in since(t0, tracing.COMPILE)
            if "tracing_fresh_fn" in s.attrs["fun_name"]]
    assert len(mine) == 1
    assert mine[0].parent == outer.id
    assert outer.t0 <= mine[0].t1 <= time.perf_counter()


def test_spans_agree_with_their_annotations_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    names = ("clock.a", "clock.b", "clock.c")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("clock.anchor"):
            anchor = time.perf_counter()
            for name in names:
                with tracing.span(name):
                    f(x).block_until_ready()
                    time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    mem = {s.name: s for s in since(anchor) if s.name in names}
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in (*names, "clock.anchor"):
                    events[e.name] = (int(e.start_ns),
                                      int(e.start_ns) + int(e.duration_ns))
    lo = events["clock.anchor"][0]
    for name in names:
        s = mem[name]
        want = (lo + (s.t0 - anchor) * 1e9, lo + (s.t1 - anchor) * 1e9)
        got = events[name]
        assert abs(got[0] - want[0]) < 1e6 and abs(got[1] - want[1]) < 1e6


def test_trainer_run_records_steps_and_the_commit_tree(lake):
    from repro.data import build_data_pipeline, seed_corpus
    from repro.runtime import Trainer, TrainerConfig

    cfg = smoke_config("paper-demo")
    lake.catalog.create_branch("data.main", "main", author="data")
    seed_corpus(lake, "data.main", n_docs=48, seed=5,
                vocab_size=cfg.vocab_size, mean_len=64, author="data")
    lake.run(build_data_pipeline(32), branch="data.main", author="data")
    tcfg = TrainerConfig(arch=cfg.name, seq_len=32, global_batch=4,
                         n_steps=3, ckpt_every=3, author="t",
                         schedule="constant", schedule_kw={"peak_lr": 1e-3})
    trainer = Trainer(lake, cfg, tcfg, data_branch="data.main",
                      run_name="traced")
    before = set(lake.store.iter_objects())
    t0 = time.perf_counter()
    out = trainer.run()
    got = since(t0)

    steps = [s for s in got if s.name == "train.step"]
    assert [s.attrs["step"] for s in steps] == [0, 1, 2]
    for s, r in zip(steps, trainer.records):
        kids = [k.name for k in got if k.parent == s.id]
        assert kids == ["train.feed", "train.dispatch", "train.sync"]
        assert 0 < r.wall_ms <= (s.t1 - s.t0) * 1e3
    feed = [s for s in got if s.name == "train.feed"]
    assert all(s.attrs["bytes"] == 4 * 32 * 4 for s in feed)

    (save,) = [s for s in got if s.name == "ckpt.save"]
    assert save.parent is None and save.attrs == {"step": 3}
    tree = subtree(save, got)
    names = [s.name for s in tree]
    for name in ("ckpt.copy", "table.encode", "table.stats", "table.pack",
                 "store.hash", "store.compress", "store.write",
                 "ckpt.digest", "catalog.commit"):
        assert name in names, name
    assert names.count("ckpt.copy") == 2
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(out["params"])) + sum(
        x.nbytes for x in jax.tree.leaves(out["opt_state"]))
    copies = [s for s in tree if s.name == "ckpt.copy"]
    assert sum(s.attrs["bytes"] for s in copies) == n_bytes
    assert (sum(s.attrs["leaves"] for s in copies)
            == len(jax.tree.leaves(out["params"]))
            + len(jax.tree.leaves(out["opt_state"])))

    # every object the commit wrote: its bytes in and out of the store
    written = [s for s in tree if s.name == "store.write"]
    hashed = [s for s in tree if s.name == "store.hash"
              and not s.attrs["dedup"]]
    compressed = [s for s in tree if s.name == "store.compress"]
    metrics_objects = set(lake.store.iter_objects())
    new = metrics_objects - before
    from repro.checkpoint import latest_checkpoint

    commit = latest_checkpoint(lake, trainer.run_branch)
    mine = {d for d in new if d in _closure(lake, commit)}
    assert len(written) == len(hashed) == len(compressed) == len(mine)
    assert (sum(s.attrs["bytes"] for s in written)
            == sum(lake.store.size(d) for d in mine))
    assert (sum(s.attrs["bytes"] for s in hashed)
            == sum(len(lake.store.get(d)) for d in mine))
    assert ([s.attrs["bytes_out"] for s in compressed]
            == [s.attrs["bytes"] for s in written])
    encoded = sorted(s.attrs["bytes"] for s in tree
                     if s.name == "table.encode")
    assert len(encoded) == 2  # params and AdamW state, one file each
    assert set(encoded) <= {s.attrs["bytes"] for s in hashed}


def _closure(lake, commit):
    """Objects a checkpoint commit reaches: itself, its snapshots, their
    manifests and files."""
    from repro.core.gc import _mark_commit

    live = set()
    _mark_commit(lake.store, commit, live)
    return live


def test_batcher_records_queue_and_admit_per_request():
    from repro.models import init_params
    from repro.serving import BatchedServer, ServeEngine

    cfg = smoke_config("paper-demo")
    engine = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                         max_len=48, batch_size=2)
    server = BatchedServer(engine)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    rids = [11, 12, 13, 14]
    for rid, n in zip(rids, (3, 5, 1, 4)):
        server.submit(rid, rng.integers(3, cfg.vocab_size, 8), n)
    while server.pending:
        server.step()
    assert sorted(server.completed) == rids
    got = since(t0)
    queued = {s.attrs["rid"]: s for s in got if s.name == "serve.queue"}
    admits = {s.attrs["rid"]: s for s in got if s.name == "serve.admit"}
    assert sorted(queued) == sorted(admits) == rids
    assert len([s for s in got if s.name == "serve.admit"]) == len(rids)
    steps = {s.id: s for s in got if s.name == "serve.step"}
    for rid in rids:
        assert queued[rid].t1 == admits[rid].t0
        assert admits[rid].parent in steps
        assert admits[rid].attrs["prompt_len"] == 8
    assert queued[13].t1 - queued[13].t0 > queued[11].t1 - queued[11].t0
    decodes = [s for s in got if s.name == "serve.decode"]
    assert len(decodes) == server.intervals
    assert all(d.parent in steps for d in decodes)
    fetches = [s for s in got if s.name == "serve.fetch"]
    assert fetches and all(f.attrs["intervals"] >= 1 for f in fetches)
