"""The readers of the program's spans, on hand-built spans and traces:
each new metric against its value worked out by hand, and nothing where
the program kept no spans of the window."""

import sys

import pytest

import devtrace as tr
import harness
import run

import repro
from repro import tracing

W0, W1 = 10.0, 210.0  # the window, perf_counter seconds


def span(id, name, t0, t1, parent=None, error=None, **attrs):
    return tracing.Span(id, parent, name, t0, t1, 1, attrs, error)


def record(trace=None, trace_window=None):
    return harness.Record(cell=None, cfg=None, peaks=None, window=(W0, W1),
                          spans=None, counters={}, trace=trace,
                          trace_window=trace_window, device="d")


@pytest.fixture
def kept(monkeypatch):
    """Make ``tracing.spans()`` return the given spans (after one that
    closed in the set-up, before the window)."""
    def use(spans):
        monkeypatch.setattr(tracing, "spans",
                            lambda: [span(0, "setup", 1.0, 2.0), *spans])
    return use


COMMIT = [
    span(2, "ckpt.copy", 20, 30, 1, bytes=10),
    span(4, "table.pack", 30, 40, 3),
    span(5, "table.stats", 40, 45, 3),
    span(3, "table.encode", 30, 45, 1),
    span(6, "store.hash", 45, 47, 1),
    span(7, "store.compress", 47, 57, 1),
    span(8, "store.write", 57, 60, 1),
    span(9, "ckpt.copy", 60, 70, 1),
    span(11, "jax.compile", 70, 80, 10),
    span(10, "ckpt.digest", 70, 100, 1),
    span(13, "store.hash", 101, 102, 12),
    span(14, "store.compress", 102, 103, 12),
    span(15, "store.write", 103, 104, 12),
    span(12, "catalog.commit", 100, 118, 1),
    span(1, "ckpt.save", 20, 120),
    # a commit that began before the window, and one after it
    span(20, "ckpt.copy", 6, 8, 21),
    span(21, "ckpt.save", 5, 9),
    span(22, "ckpt.save", 215, 230),
]
WANT = {"copy": 20, "encode": 15, "hash": 3, "compress": 11, "write": 4,
        "digest": 30, "other": 17}


def test_commit_stages_split_the_save(kept):
    kept(COMMIT)
    got = {p: run.load_metric(f"commit_{p}_s")(record()) for p in WANT}
    assert got == pytest.approx(WANT)
    assert sum(got.values()) == pytest.approx(100.0)  # the save's time


def test_commit_stages_are_a_mean_over_commits(kept):
    second = [s._replace(id=s.id + 100, t0=s.t0 + 100, t1=s.t1 + 100,
                         parent=None if s.parent is None else s.parent + 100)
              for s in COMMIT[:15]]
    second[5] = second[5]._replace(t1=second[5].t1 + 4)  # compress 14, not 10
    second[-1] = second[-1]._replace(t1=second[-1].t1 + 4)
    kept(COMMIT + second)
    assert run.load_metric("commit_compress_s")(record()) == \
        pytest.approx(13.0)
    assert run.load_metric("commit_other_s")(record()) == pytest.approx(17.0)


def test_loop_host_leaves_out_the_wait_and_the_partial_step(kept):
    kept([
        span(2, "train.feed", 20, 21, 1),
        span(3, "train.sync", 22, 29, 1),
        span(1, "train.step", 20, 30),
        span(5, "train.sync", 31, 39.5, 4),
        span(4, "train.step", 30, 40),
        span(6, "train.step", 5, 12),           # began before the window
        span(7, "train.step", 205, 212, error="Deadline"),
    ])
    assert run.load_metric("loop_host_ms.train")(record()) == \
        pytest.approx((3 + 1.5) / 2 * 1e3)


def test_serve_loop_host_leaves_out_fetches_at_any_depth(kept):
    kept([
        span(2, "serve.fetch", 21, 23, 3),      # a retirement at admit
        span(3, "serve.admit", 20, 24, 1),
        span(4, "serve.decode", 24, 25, 1),
        span(5, "serve.fetch", 25, 28, 1),
        span(1, "serve.step", 20, 30),
        span(6, "serve.step", 30, 31),
    ])
    assert run.load_metric("loop_host_ms.serve")(record()) == \
        pytest.approx((10 - 2 - 3 + 1) / 2 * 1e3)


def test_idle_untraced_counts_what_neither_device_nor_span_covers(kept):
    # one trace ns is one nanosecond of the window: window 10.0 s -> 5000
    kept([span(1, "serve.step", W0 + 300e-9, W0 + 350e-9),
          span(2, "train.step", W0 - 1.0, W0 + 10e-9)])
    trace = tr.Trace(ops={"d": [tr.Event("a", 5000, 5200),
                                tr.Event("b", 5500, 5600)]},
                     modules={}, host={"sync": [(5200, 5300)]})
    rec = record(trace, (5000, 6000))
    # covered: 5000-5300 (op, then sync), 5300-5350 (span), 5500-5600
    want = 100.0 * (1000 - 300 - 50 - 100) / 1000
    for name in ("idle_untraced.train", "idle_untraced.serve"):
        assert run.load_metric(name)(rec) == pytest.approx(want)


NAMES = [f"commit_{p}_s" for p in WANT] + [
    "loop_host_ms.train", "loop_host_ms.serve", "idle_untraced.train",
    "idle_untraced.serve"]


def test_an_overflowed_buffer_gives_nothing(monkeypatch):
    # the oldest span kept closed after the window opened
    monkeypatch.setattr(tracing, "spans", lambda: COMMIT[:15])
    trace = tr.Trace(ops={"d": []}, modules={}, host={})
    for name in NAMES:
        assert run.load_metric(name)(record(trace, (0, 100))) is None


def test_a_program_without_tracing_gives_nothing(monkeypatch):
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    trace = tr.Trace(ops={"d": []}, modules={}, host={})
    for name in NAMES:
        assert run.load_metric(name)(record(trace, (0, 100))) is None
