"""Checkpoint save/restore through the catalog + elastic resharding."""

from __future__ import annotations

import queue
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.experimental.layout import Format, Layout

from .. import tracing
from ..core import Lake
from ..kernels.fingerprint.ops import tree_digest_hex

_SEP = "/"


def _path_str(path) -> str:
    return jax.tree_util.keystr(path, simple=True, separator=_SEP)


def host_copy(leaf) -> np.ndarray:
    """A leaf on the host, in row-major order.

    A TPU may hold an array with its axes in another order (the one that
    pads its tiles least), and the host copy keeps that order: the
    tensorfile would then have to transpose it on the host, which is
    slow.  Such a leaf is laid out row-major on the device first."""
    layout = getattr(getattr(leaf, "format", None), "layout", None)
    order = tuple(range(np.ndim(leaf)))
    if layout is not None and layout.major_to_minor != order:
        leaf = jax.device_put(leaf, Format(Layout(major_to_minor=order),
                                           leaf.sharding))
    return np.asarray(leaf)


def leaves_to_columns(tree) -> Dict[str, np.ndarray]:
    """Pytree → single-row columns: leaf path → (1, *shape) array."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out[_path_str(path)] = host_copy(leaf)[None, ...]
    return out


def columns_to_tree(cols: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """Inverse of ``leaves_to_columns`` for dict-of-dict trees."""
    root: Dict[str, Any] = {}
    for name, arr in cols.items():
        parts = name.split(_SEP)
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr[0]
    return root


def restore_into(template, cols: Dict[str, np.ndarray]):
    """Rebuild a TYPED pytree (NamedTuples etc.) from saved columns using the
    template's structure: each template leaf is replaced by the column at the
    same keypath.  Template leaf values are never read — only structure."""
    paths = [_path_str(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(template)]
    missing = [p for p in paths if p not in cols]
    if missing:
        raise KeyError(f"checkpoint missing leaves: {missing[:5]}...")
    leaves = [cols[p][0] for p in paths]
    treedef = jax.tree_util.tree_structure(template)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save(lake: Lake, branch: str, *, step: int, params, opt_state=None,
         author: str = "system", extra_meta: Optional[dict] = None,
         digest: bool = True) -> str:
    """Commit a checkpoint (one multi-table transaction). Returns commit."""
    with tracing.span("ckpt.save", step=int(step)):
        updates = {"ckpt_params": lake.io.write_snapshot(
            _host_columns(params))}
        if opt_state is not None:
            updates["ckpt_opt"] = lake.io.write_snapshot(
                _host_columns(opt_state))
        meta = {"step": int(step), **(extra_meta or {})}
        if digest:
            # device-side content digest (fingerprint kernel): integrity check
            with tracing.span("ckpt.digest",
                              leaves=len(jax.tree_util.tree_leaves(params))):
                meta["params_digest"] = tree_digest_hex(params)
        return lake.catalog.commit(branch, updates,
                                   f"checkpoint step={step}", author=author,
                                   meta={"checkpoint": meta})


def _host_columns(tree) -> Dict[str, np.ndarray]:
    """``leaves_to_columns`` as a span: the device→host copy of a tree."""
    with tracing.span("ckpt.copy") as s:
        cols = leaves_to_columns(tree)
        s.set(leaves=len(cols), bytes=sum(a.nbytes for a in cols.values()))
    return cols


def restore(lake: Lake, ref: str, *, mesh=None, param_specs=None,
            opt_specs=None, verify: bool = False
            ) -> Tuple[dict, Optional[Any], dict]:
    """Load (params, opt_state, meta) from a commit.

    Elastic resharding: arrays are stored layout-free; passing
    ``mesh``+``param_specs`` lays them onto WHATEVER mesh is alive now
    (restore after scaling from 512 → 256 chips is the same code path).
    """
    commit = lake.catalog.commit_info(ref)
    meta = commit.meta.get("checkpoint", {})
    tables = lake.catalog.tables(ref)
    params = columns_to_tree(lake.io.read(tables["ckpt_params"]))
    opt_state = None
    if "ckpt_opt" in tables:
        opt_state = columns_to_tree(lake.io.read(tables["ckpt_opt"]))
    if mesh is not None and param_specs is not None:
        from ..distributed.sharding import named

        params = jax.device_put(params, named(mesh, param_specs))
        if opt_state is not None and opt_specs is not None:
            opt_state = jax.device_put(opt_state, named(mesh, opt_specs))
    else:
        # placed once at load: a jitted step handed host arrays would copy
        # them to the device again on every call
        params = jax.device_put(params)
    if verify and "params_digest" in meta:
        actual = tree_digest_hex(params)
        if actual != meta["params_digest"]:
            raise ValueError(
                f"checkpoint digest mismatch: {actual} != "
                f"{meta['params_digest']}")
    return params, opt_state, meta


def latest_checkpoint(lake: Lake, branch: str) -> Optional[str]:
    """Newest commit on the branch that carries checkpoint metadata."""
    for digest in lake.catalog.log(branch):
        if "checkpoint" in lake.catalog.commit_info(digest).meta:
            return digest
    return None


class CheckpointManager:
    """Async checkpointing: the device→host copy happens on the caller
    thread (cheap, one HBM read), serialization + commit on a worker thread
    — the distributed-training "don't stall the step loop" optimization."""

    def __init__(self, lake: Lake, branch: str, *, author: str = "system",
                 keep_last: int = 0):
        self.lake = lake
        self.branch = branch
        self.author = author
        self._queue: "queue.Queue" = queue.Queue()
        self._errors: list = []
        self._commits: list = []
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    def _run(self):
        while True:
            item = self._queue.get()
            if item is None:
                self._queue.task_done()
                return
            step, params_host, opt_host, extra = item
            try:
                c = save(self.lake, self.branch, step=step,
                         params=params_host, opt_state=opt_host,
                         author=self.author, extra_meta=extra)
                self._commits.append((step, c))
            except Exception as e:  # surfaced on wait()
                self._errors.append(e)
            finally:
                self._queue.task_done()

    def submit(self, *, step: int, params, opt_state=None,
               extra_meta: Optional[dict] = None):
        # synchronous part: pull to host memory (jax arrays → np)
        params_host = jax.tree.map(host_copy, params)
        opt_host = (jax.tree.map(host_copy, opt_state)
                    if opt_state is not None else None)
        self._queue.put((step, params_host, opt_host, extra_meta or {}))

    def wait(self):
        """Block until every submitted checkpoint is committed."""
        self._queue.join()
        if self._errors:
            raise self._errors[0]
        return list(self._commits)

    def close(self):
        self._queue.put(None)
        self._worker.join()
        if self._errors:
            raise self._errors[0]
