"""Immutable runs + replay (paper §4–5): every run gets a ``run_id`` that
uniquely pins the combination of code, config, runtime and input-data commit —
``bauplan run --id=1441804`` becomes ``ledger.replay(run_id, ...)``.

The run manifest covers all four rows of the paper's Table 1:

    input data -> data_commit (catalog commit digest at read time)
    code       -> per-node code hashes + pipeline hash
    runtime    -> python/jax versions + node runtime pins (pip={...})
    hardware   -> mesh fingerprint (device kind, axis names, shape)

Replay = checkout a debug branch at ``data_commit``, re-execute the same code,
and (optionally) verify output digests are bit-identical to the original run.
"""

from __future__ import annotations

import hashlib
import platform
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import msgpack

from .catalog import Catalog
from .errors import CodeDrift, RefNotFound, RunNotFound
from .pipeline import ExecutionReport, Pipeline, RunResult, execute
from .runcache import RunCache
from .store import ObjectStore
from .table import TableIO

_RUNS_HEAD = "runs-head"


def _pack(obj) -> bytes:
    return msgpack.packb(obj, use_bin_type=True)


def _unpack(blob: bytes):
    return msgpack.unpackb(blob, raw=False)


def runtime_fingerprint() -> Dict[str, str]:
    fp = {
        "python": sys.version.split()[0],
        "platform": platform.platform(),
    }
    try:
        import jax

        fp["jax"] = jax.__version__
    except ImportError:  # pragma: no cover
        pass
    return fp


def mesh_fingerprint(mesh=None) -> Dict[str, Any]:
    """Hardware row of Table 1, TPU edition."""
    if mesh is None:
        return {"kind": "unspecified"}
    return {
        "kind": str(mesh.devices.flat[0].device_kind),
        "shape": dict(mesh.shape),
        "axes": list(mesh.axis_names),
        "n_devices": int(mesh.devices.size),
    }


@dataclass
class ReplayReport:
    run_id: str
    replay_run_id: str
    branch: str
    bit_exact: bool
    diffs: Dict[str, tuple] = field(default_factory=dict)


class RunLedger:
    """Append-only chain of run manifests in the object store."""

    def __init__(self, store: ObjectStore, clock=time.time):
        self.store = store
        self.clock = clock

    # ---------------------------------------------------------------- record
    def record(
        self,
        *,
        pipeline: Pipeline,
        data_commit: str,
        result_commit: str,
        branch: str,
        outputs: Dict[str, str],
        config: Optional[Dict[str, Any]] = None,
        seed: Optional[int] = None,
        mesh=None,
        parent_run: Optional[str] = None,
        kind: str = "pipeline",
        report: Optional[ExecutionReport] = None,
    ) -> str:
        executor = {}
        nodes = {}
        if report is not None:
            executor = {
                "kind": report.executor,
                "exec_id": report.exec_id,
                "jobs": report.jobs,
                "cache": report.cache_enabled,
                "cache_hits": report.cache_hits,
                "cache_misses": report.cache_misses,
            }
            nodes = {name: stat.to_obj()
                     for name, stat in sorted(report.node_stats.items())}
        manifest = {
            "kind": kind,
            "code": pipeline.code_manifest(),
            "pipeline_hash": pipeline.code_hash(),
            "node_runtime": {n.name: n.runtime
                             for n in pipeline.nodes.values() if n.runtime},
            "data_commit": data_commit,
            "result_commit": result_commit,
            "branch": branch,
            "outputs": dict(sorted(outputs.items())),
            "config": config or {},
            "seed": seed,
            "runtime": runtime_fingerprint(),
            "hardware": mesh_fingerprint(mesh),
            "parent_run": parent_run,
            "executor": executor,  # per-run cache/parallelism settings
            "nodes": nodes,  # per-node cache hit/miss + wall time
            "ts": self.clock(),
        }
        blob = _pack(manifest)
        run_id = hashlib.sha256(blob).hexdigest()[:16]
        payload_digest = self.store.put(blob)
        # append to the run chain (enumerable history of all runs)
        try:
            prev = self.store.get_ref(_RUNS_HEAD)
        except RefNotFound:
            prev = None
        link = self.store.put(_pack({"run_id": run_id,
                                     "manifest": payload_digest,
                                     "prev": prev}))
        self.store.set_ref(_RUNS_HEAD, link)
        return run_id

    # ------------------------------------------------------------------ read
    def _iter_links(self):
        try:
            cur: Optional[str] = self.store.get_ref(_RUNS_HEAD)
        except RefNotFound:
            return
        while cur is not None:
            link = _unpack(self.store.get(cur))
            yield link
            cur = link["prev"]

    def runs(self) -> List[str]:
        return [link["run_id"] for link in self._iter_links()]

    def links(self):
        """Chain links newest-first: ``{run_id, manifest, prev}`` dicts.
        Exposed for push/pull, which graft missing manifests onto the
        destination's own chain instead of copying link blobs (the chains
        on two hosts interleave differently but reference identical,
        content-addressed manifests)."""
        return self._iter_links()

    def graft(self, run_id: str, manifest_digest: str) -> None:
        """Append an existing (transferred) manifest to this store's chain.
        The manifest blob must already be in the store; the run keeps its
        original id so replay-by-id works across hosts."""
        try:
            prev = self.store.get_ref(_RUNS_HEAD)
        except RefNotFound:
            prev = None
        link = self.store.put(_pack({"run_id": run_id,
                                     "manifest": manifest_digest,
                                     "prev": prev}))
        self.store.set_ref(_RUNS_HEAD, link)

    def get(self, run_id: str) -> Dict[str, Any]:
        for link in self._iter_links():
            if link["run_id"] == run_id or link["run_id"].startswith(run_id):
                return _unpack(self.store.get(link["manifest"]))
        raise RunNotFound(run_id)

    # ---------------------------------------------------------------- replay
    def replay(
        self,
        run_id: str,
        pipeline: Pipeline,
        catalog: Catalog,
        io: TableIO,
        *,
        branch: str,
        author: str = "system",
        allow_code_drift: bool = False,
        verify: bool = True,
        cache: Optional[RunCache] = None,
        use_cache: bool = True,
        jobs: Optional[int] = None,
        executor: str = "thread",
        **exec_opts,
    ) -> ReplayReport:
        """Re-execute a past run into a (new) debug branch — use case #2.

        1) time-travel: the debug branch is created at the run's data commit;
        2) code check: the supplied pipeline must hash-match the manifest
           (the paper pins code via its API; we verify and refuse on drift);
        3) re-run + record, and compare output digests to the original.
        """
        manifest = self.get(run_id)
        recorded = manifest["code"]
        current = pipeline.code_manifest()
        if recorded != current and not allow_code_drift:
            drifted = sorted(k for k in set(recorded) | set(current)
                             if recorded.get(k) != current.get(k))
            raise CodeDrift(f"nodes changed since run {run_id}: {drifted}")
        if branch not in catalog.branches():
            catalog.create_branch(branch, manifest["data_commit"],
                                  author=author)
        report = execute(pipeline, catalog, io, branch=branch, author=author,
                         params=manifest["config"].get("params"),
                         read_ref=manifest["data_commit"],
                         cache=cache, use_cache=use_cache, jobs=jobs,
                         executor=executor, **exec_opts)
        outputs = report.outputs
        replay_id = self.record(
            pipeline=pipeline,
            data_commit=manifest["data_commit"],
            result_commit=catalog.head(branch),
            branch=branch,
            outputs=outputs,
            config=manifest["config"],
            seed=manifest["seed"],
            parent_run=run_id,
            kind="replay",
            report=report,
        )
        if report.exec_id:
            from .exec import bind_ledger_run

            bind_ledger_run(self.store, report.exec_id, replay_id)
        diffs = {}
        if verify:
            for name, digest in manifest["outputs"].items():
                new = outputs.get(name)
                if new != digest:
                    diffs[name] = (digest, new)
        return ReplayReport(run_id=run_id, replay_run_id=replay_id,
                            branch=branch, bit_exact=not diffs, diffs=diffs)


def run_pipeline(
    pipeline: Pipeline,
    catalog: Catalog,
    io: TableIO,
    ledger: RunLedger,
    *,
    branch: str,
    author: str = "system",
    config: Optional[Dict[str, Any]] = None,
    seed: Optional[int] = None,
    mesh=None,
    cache: Optional[RunCache] = None,
    use_cache: bool = True,
    jobs: Optional[int] = None,
    executor: str = "thread",
    **exec_opts,
) -> RunResult:
    """``bauplan run``: execute + record, returning the run id.

    ``executor`` / ``exec_opts`` (lease_ttl, max_attempts, poll,
    wait_timeout) pass straight through to :func:`~.pipeline.execute`; the
    run manifest records which backend ran the DAG, and the ledger run id
    is bound back into the execution's refs-keyspace record so
    ``repro status <run-id>`` resolves either identifier."""
    data_commit = catalog.head(branch)
    report = execute(pipeline, catalog, io, branch=branch, author=author,
                     params=(config or {}).get("params"),
                     cache=cache, use_cache=use_cache, jobs=jobs,
                     executor=executor, **exec_opts)
    result_commit = catalog.head(branch)
    run_id = ledger.record(
        pipeline=pipeline, data_commit=data_commit,
        result_commit=result_commit, branch=branch, outputs=report.outputs,
        config=config, seed=seed, mesh=mesh, report=report,
    )
    if report.exec_id:
        from .exec import bind_ledger_run

        bind_ledger_run(catalog.store, report.exec_id, run_id)
    return RunResult(run_id=run_id, commit=result_commit, branch=branch,
                     outputs=report.outputs, node_stats=report.node_stats)
