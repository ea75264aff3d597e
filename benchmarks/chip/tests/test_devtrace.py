"""The trace reduction: interval arithmetic, and host spans read back from a
trace recorded from a tiny CPU program."""

import jax
import jax.numpy as jnp

import devtrace as tr


def test_union_clip_total_gaps():
    ivs = [(5, 9), (0, 2), (1, 3), (8, 12), (20, 20)]
    assert tr.union(ivs) == [(0, 3), (5, 12)]
    assert tr.clip(ivs, 2, 10) == [(5, 9), (2, 3), (8, 10)]
    assert tr.total(tr.union(ivs)) == 10
    assert tr.gaps(ivs, 0, 15) == [(3, 5), (12, 15)]
    assert tr.gaps([], 4, 7) == [(4, 7)]


def test_idle_breakdown_names_gaps_by_the_overlapping_span():
    t = tr.Trace(
        ops={"d": [tr.Event("a", 0, 10), tr.Event("b", 30, 40),
                   tr.Event("c", 45, 50)]},
        modules={}, host={"commit": [(10, 29)], "feed": [(40, 44)]})
    assert t.busy("d") == [(0, 10), (30, 40), (45, 50)]
    assert tr.idle_breakdown(t, "d", 0, 60) == [
        ("commit", 20 / 1e9), ("other", 10 / 1e9), ("feed", 5 / 1e9)]
    assert tr.top_ops(t, "d", top=2) == [("a", 10 / 1e9), ("b", 10 / 1e9)]


def test_host_spans_read_back_from_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("feed"):
            f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("commit"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)), ["feed", "commit", "absent"])
    assert len(t.host["feed"]) == 3 and len(t.host["commit"]) == 1
    assert t.host["absent"] == []
    (c0, c1), = t.host["commit"]
    assert all(e <= c0 for _, e in t.host["feed"]) and c1 > c0
    assert t.devices == []  # a CPU trace has no TPU plane
