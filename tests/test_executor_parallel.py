"""Parallel wave executor: independent DAG nodes overlap under ``--jobs N``
and results are bit-identical to sequential execution (same output snapshot
digests — content addressing makes this checkable exactly)."""

import threading
import time

import numpy as np
import pytest

from repro.core import (Model, NodeExecutionError, Pipeline, ReproError,
                        execute, model)


class Tracker:
    """Records per-node (start, end) wall intervals + peak concurrency."""

    def __init__(self):
        self.lock = threading.Lock()
        self.intervals = {}
        self.active = 0
        self.peak = 0

    def enter(self, name):
        with self.lock:
            self.active += 1
            self.peak = max(self.peak, self.active)
            self.intervals[name] = [time.perf_counter(), None]

    def exit(self, name):
        with self.lock:
            self.active -= 1
            self.intervals[name][1] = time.perf_counter()

    def overlap(self, a, b) -> bool:
        (s1, e1), (s2, e2) = self.intervals[a], self.intervals[b]
        return max(s1, s2) < min(e1, e2)


def diamond(tracker, sleep_s=0.15):
    """src -> (left, right) -> merged: left/right are independent."""

    @model()
    def left(data=Model("source_table")):
        tracker.enter("left")
        time.sleep(sleep_s)
        out = {"v": data["c1"] * 2.0}
        tracker.exit("left")
        return out

    @model()
    def right(data=Model("source_table")):
        tracker.enter("right")
        time.sleep(sleep_s)
        out = {"v": data["c1"] + 5.0}
        tracker.exit("right")
        return out

    @model()
    def merged(a=Model("left"), b=Model("right")):
        tracker.enter("merged")
        out = {"v": a["v"] + b["v"]}
        tracker.exit("merged")
        return out

    return Pipeline([left, right, merged])


def test_independent_nodes_overlap_under_jobs_4(seeded_lake):
    tracker = Tracker()
    pipe = diamond(tracker)
    seeded_lake.catalog.create_branch("r.par", "main", author="r")
    report = execute(pipe, seeded_lake.catalog, seeded_lake.io,
                     branch="r.par", author="r", use_cache=False, jobs=4)
    assert report.jobs == 4
    assert tracker.peak >= 2  # left and right ran concurrently
    assert tracker.overlap("left", "right")
    # merged strictly after both parents
    assert tracker.intervals["merged"][0] >= tracker.intervals["left"][1]
    assert tracker.intervals["merged"][0] >= tracker.intervals["right"][1]


def test_sequential_never_overlaps(seeded_lake):
    tracker = Tracker()
    pipe = diamond(tracker)
    seeded_lake.catalog.create_branch("r.seq", "main", author="r")
    execute(pipe, seeded_lake.catalog, seeded_lake.io,
            branch="r.seq", author="r", use_cache=False, jobs=1)
    assert tracker.peak == 1
    assert not tracker.overlap("left", "right")


def test_parallel_results_bit_identical_to_sequential(seeded_lake):
    seeded_lake.catalog.create_branch("r.a", "main", author="r")
    seeded_lake.catalog.create_branch("r.b", "main", author="r")
    seq = execute(diamond(Tracker(), 0), seeded_lake.catalog, seeded_lake.io,
                  branch="r.a", author="r", use_cache=False, jobs=1)
    par = execute(diamond(Tracker(), 0), seeded_lake.catalog, seeded_lake.io,
                  branch="r.b", author="r", use_cache=False, jobs=4)
    assert seq.outputs == par.outputs  # same snapshot digests, node for node
    # and through the catalog: both branches converge to identical tables
    assert (seeded_lake.catalog.tables("r.a")
            == seeded_lake.catalog.tables("r.b"))


def test_parallel_run_through_lake_records_jobs(seeded_lake):
    tracker = Tracker()
    pipe = diamond(tracker, 0.05)
    seeded_lake.catalog.create_branch("r.lake", "main", author="r")
    res = seeded_lake.run(pipe, branch="r.lake", author="r", jobs=4)
    m = seeded_lake.ledger.get(res.run_id)
    assert m["executor"]["jobs"] == 4
    assert set(m["nodes"]) == {"left", "right", "merged"}


def test_node_failure_propagates_from_worker_thread(seeded_lake):
    @model()
    def boom(data=Model("source_table")):
        raise RuntimeError("node exploded")

    @model()
    def ok(data=Model("source_table")):
        return {"v": data["c1"]}

    seeded_lake.catalog.create_branch("r.err", "main", author="r")
    with pytest.raises(NodeExecutionError, match="node exploded") as ei:
        execute(Pipeline([boom, ok]), seeded_lake.catalog, seeded_lake.io,
                branch="r.err", author="r", jobs=4)
    assert isinstance(ei.value.__cause__, RuntimeError)
    # the failed run must not have committed anything
    assert "ok" not in seeded_lake.catalog.tables("r.err")


def test_failure_carries_node_identity_and_sibling_stats(seeded_lake):
    """Regression: the executor used to re-raise the bare worker exception,
    losing WHICH node failed and throwing away the NodeStats of every node
    that had already finished."""
    done = threading.Event()

    @model()
    def first(data=Model("source_table")):
        return {"v": data["c1"]}

    @model()
    def boom(data=Model("first")):
        done.set()
        raise ValueError("bad partition")

    seeded_lake.catalog.create_branch("r.id", "main", author="r")
    with pytest.raises(NodeExecutionError) as ei:
        execute(Pipeline([first, boom]), seeded_lake.catalog,
                seeded_lake.io, branch="r.id", author="r", jobs=4)
    err = ei.value
    assert err.node == "boom"
    assert err.attempts == 1
    assert "boom" in str(err) and "bad partition" in str(err)
    # the sibling that completed before the failure kept its stats
    assert set(err.node_stats) == {"first"}
    assert err.node_stats["first"].snapshot is not None
    assert done.is_set()


def test_sibling_failure_drains_in_flight_without_publishing(seeded_lake):
    """Regression: the old ``except BaseException: fut.cancel()`` path could
    not stop in-flight nodes — they kept running after the raise and WROTE
    their snapshot + cache entry into a failed run.  Now the coordinator
    drains them: the slow sibling finishes (threads can't be killed) but
    publishes nothing once the failure was observed."""
    slow_ran = threading.Event()

    @model()
    def fail_fast(data=Model("source_table")):
        # fail only once the sibling is in flight: on a loaded host the
        # failure could otherwise be observed before `slow` ever started
        slow_ran.wait(timeout=10)
        raise RuntimeError("fast failure")

    @model()
    def slow(data=Model("source_table")):
        slow_ran.set()
        time.sleep(0.4)  # still in flight when fail_fast is observed
        return {"v": data["c1"] * 3.0}

    lake = seeded_lake
    lake.catalog.create_branch("r.drain", "main", author="r")
    with pytest.raises(NodeExecutionError, match="fail_fast"):
        execute(Pipeline([fail_fast, slow]), lake.catalog, lake.io,
                branch="r.drain", author="r", jobs=4)
    assert slow_ran.is_set()  # it really was in flight
    # drained: no cache entry (and thus no published snapshot) for `slow`
    cached_nodes = {e["node"] for e in
                    (lake.run_cache.get(k) for k in lake.run_cache.keys())
                    if e}
    assert "slow" not in cached_nodes
    assert "slow" not in lake.catalog.tables("r.drain")
    # and a rerun on a healthy DAG re-executes slow (no stale hit)
    @model(name="slow")
    def slow_ok(data=Model("source_table")):
        slow_ran.set()
        time.sleep(0.4)
        return {"v": data["c1"] * 3.0}

    rep = execute(Pipeline([slow_ok]), lake.catalog, lake.io,
                  branch="r.drain", author="r")
    assert not rep.node_stats["slow"].cache_hit


def test_wide_fanout_all_waves_complete(seeded_lake):
    """32 independent nodes + a fan-in: more nodes than workers."""
    nodes = []
    for i in range(32):
        def make(i=i):
            @model(name=f"n{i:02d}")
            def n(data=Model("source_table")):
                return {"v": data["c1"] + float(i)}
            return n
        nodes.append(make())

    def fan_in_fn(**inputs):
        return {"v": sum(v["v"] for v in inputs.values())}

    from repro.core.pipeline import Node, code_hash_of
    fan_in = Node(
        name="total", fn=fan_in_fn, deps=[n.name for n in nodes],
        dep_params={f"i{k}": Model(n.name) for k, n in enumerate(nodes)},
        code_hash=code_hash_of(fan_in_fn))
    pipe = Pipeline(nodes + [fan_in])
    seeded_lake.catalog.create_branch("r.wide", "main", author="r")
    report = execute(pipe, seeded_lake.catalog, seeded_lake.io,
                     branch="r.wide", author="r", jobs=4)
    assert len(report.outputs) == 33
    src = seeded_lake.read_table("main", "source_table")
    expect = src["c1"] * 32 + sum(range(32))
    np.testing.assert_allclose(
        seeded_lake.read_table("r.wide", "total")["v"], expect, rtol=1e-5)
