"""Training cells: the program's own ``Trainer.run`` over a lake table,
committing checkpoints every ``ckpt_every`` steps.

The run drives the loop the program has and copies none of it.  It wraps
the ``Trainer`` instance's ``train_step`` (the window's clock and its
close), its loader's ``batch`` (the feed's host span) and
``repro.checkpoint.save`` (the commit's host span).

  set-up   lake, corpus, packing pipeline, Trainer; steps 0 .. setup-1
           through the window's own call and feed (step 0 compiles or
           loads the compiled step); the commit's digest is warmed once
           where the set-up steps' pace puts a commit within reach of
           the window
  window   opens at call ``setup_steps`` + 1 of ``train_step`` and closes
           at the first call after ``--seconds``: a commit that began in
           the window counts in full
  check    the first steps against the plain reference; the window holds
           every commit that ``ckpt_every`` puts in its steps, and the
           first of them against the params it was given
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from typing import Dict, List

import numpy as np

import generators
import harness
import reference
from harness import Check, log

TRACE_SPANS = ("feed", "commit")
#: readings besides the program's, for the control and the planted faults
#: (``check.py`` sets them; the benchmark's own runs leave this empty):
#: "fp8" is the reference at float8, "half" the reference on half of each
#: batch with the mean taken over the rest, "frozen" the reference with a
#: step that returns its state unchanged
EXTRA: tuple = ()


class Deadline(Exception):
    """Raised out of ``Trainer.run`` at the first step after the window."""


def _leaf_names(tree) -> List[str]:
    import jax

    return [str(p[-1].key) for p, _ in
            jax.tree_util.tree_leaves_with_path(tree)]


def _leaf_norms(tree, scale=1.0) -> Dict[str, float]:
    """Per-leaf L2 norms keyed by the leaf's last path name."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda t: [jnp.linalg.norm(x.astype(jnp.float32))
                           for x in jax.tree.leaves(t)])
    return {n: float(v) * scale for n, v in zip(_leaf_names(tree), f(tree))}


def commits_in(first: int, n: int, every: int) -> int:
    """How many commits ``Trainer.run`` makes after steps first ..
    first + n - 1 (it commits after step s when s + 1 is a multiple of
    ``every``)."""
    return (first + n) // every - first // every


def _change_norms(master, params0) -> Dict[str, float]:
    """Per-leaf L2 norms of master - params0."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda m, p: [jnp.linalg.norm(a - b.astype(jnp.float32))
                              for a, b in zip(jax.tree.leaves(m),
                                              jax.tree.leaves(p))])
    return {n: float(v) for n, v in zip(_leaf_names(params0),
                                        f(master, params0))}


@dataclasses.dataclass
class Watch:
    """State the wrappers collect while the program runs."""
    setup_steps: int
    seconds: float
    b1: float
    calls: int = 0
    t0: float = None
    t1: float = None
    window_calls: int = 0
    params0: object = None
    first_grad: Dict[str, float] = None
    change: Dict[str, float] = None
    fed: List[np.ndarray] = dataclasses.field(default_factory=list)
    commits: List[dict] = dataclasses.field(default_factory=list)


def run(cell, seed: int, seconds: float, profiler, spans, compiles,
        devices, t_start: float, limits: dict) -> harness.Outcome:
    import jax

    import repro.checkpoint as ckpt_mod
    from repro.core import Lake
    from repro.data import build_data_pipeline
    from repro.kernels.fingerprint.ops import tree_digest_hex
    from repro.optim import adamw
    from repro.runtime import Trainer, TrainerConfig

    cfg = cell.model_config()
    job = cell.traffic
    S, B = job["seq_len"], job["global_batch"]
    init_seed = generators.sub_seed(seed, "init")
    root = tempfile.mkdtemp(prefix="chipbench_lake_")
    try:
        lake = Lake(root)
        docs = generators.corpus(job, cfg.vocab_size, seed)
        lake.catalog.create_branch("data.main", "main", author="data")
        lake.write_table("data.main", "raw_docs", docs, author="data")
        lake.run(build_data_pipeline(S), branch="data.main", author="data")
        tcfg = TrainerConfig(
            arch=cfg.name, seq_len=S, global_batch=B, n_steps=10 ** 9,
            ckpt_every=job["ckpt_every"], seed=init_seed,
            schedule=job["schedule"], schedule_kw=dict(job["schedule_kw"]),
            opt=adamw.AdamWConfig(**job["optimizer"]), author="trainer")
        trainer = Trainer(lake, cfg, tcfg, data_branch="data.main",
                          run_name="bench")
        w = Watch(job["setup_steps"], seconds, job["optimizer"]["b1"])
        step_fn, batch_fn, save_fn = (trainer.train_step,
                                      trainer.loader.batch, ckpt_mod.save)

        def train_step(params, opt_state, batch):
            w.calls += 1
            if w.calls == 1:
                w.params0 = params
            elif w.calls == 2:  # mu after one step = (1 - b1) * clipped grad
                w.first_grad = _leaf_norms(opt_state.mu,
                                           1.0 / (1.0 - w.b1))
            if w.calls == w.setup_steps + 1:
                # a commit within twice the window's reach at the set-up
                # steps' pace: its digest programs are loaded now
                walls = [r.wall_ms for r in trainer.records[1:]] or \
                    [r.wall_ms for r in trainer.records]
                reach = int(2 * w.seconds / (min(walls) / 1e3)) + 1
                if commits_in(w.setup_steps, reach, job["ckpt_every"]):
                    tree_digest_hex(params)
                master = (opt_state.master if opt_state.master is not None
                          else params)
                w.change = _change_norms(master, w.params0)
                w.params0 = None
                profiler.start()
                w.t0 = profiler.t0
            elif w.t0 is not None and time.perf_counter() >= w.t0 + w.seconds:
                w.t1 = time.perf_counter()
                profiler.stop()
                raise Deadline
            if w.t0 is not None:
                w.window_calls += 1
            return step_fn(params, opt_state, batch)

        def batch(step):
            with spans.span("feed"):
                out = batch_fn(step)
            if len(w.fed) < w.setup_steps:
                w.fed.append(np.array(out["tokens"]))
            return out

        def save(lake_, branch, *, step, params, opt_state=None, **kw):
            with spans.span("commit"):
                commit = save_fn(lake_, branch, step=step, params=params,
                                 opt_state=opt_state, **kw)
            if w.t0 is not None and w.t1 is None:
                entry = {"step": step, "commit": commit}
                if not w.commits:  # the first commit of the window is checked
                    entry["params"] = params
                w.commits.append(entry)
            return commit

        trainer.train_step = train_step
        trainer.loader.batch = batch
        ckpt_mod.save = save
        try:
            trainer.run()
        except Deadline:
            pass
        finally:
            ckpt_mod.save = save_fn
            profiler.stop()
        steps = w.window_calls  # the call that raised ran no step
        window = (w.t0, w.t1)
        e2e = {"train_tokens_per_s": steps * B * S / (w.t1 - w.t0),
               "setup_s": w.t0 - t_start}
        peak = harness.memory_peak(devices)
        log(f"setup {e2e['setup_s']:.2f} s; window: {steps} steps, "
            f"{len(w.commits)} commits, "
            f"{w.t1 - w.t0:.3f} s; compiles in the window: "
            f"{compiles.between(w.t0, w.t1)}; losses "
            f"{[r.loss for r in trainer.records[:w.setup_steps]]}")

        committed = _read_commit(lake, w.commits[0]) if w.commits else None
        trained = (jax.tree.map(np.asarray, w.commits[0].pop("params"))
                   if w.commits else None)
        losses = [r.loss for r in trainer.records[:w.setup_steps]]
        record = harness.Record(
            cell=cell, cfg=cfg, peaks=None, window=window, spans=spans,
            counters={"steps": steps, "batch": B, "seq_len": S,
                      "commits": len(w.commits),
                      "param_leaves": (len(jax.tree.leaves(trained))
                                       if trained else None),
                      "param_bytes": (sum(x.nbytes for x in
                                          jax.tree.leaves(trained))
                                      if trained else None)})
        # free the program's device state before the reference runs
        del trainer, step_fn, batch_fn, train_step, batch
        gc.collect()

        checks = compare(cell, init_seed, docs, w, losses, limits)
        due = commits_in(w.setup_steps, steps, job["ckpt_every"])
        checks.append(Check("commits_missing",
                            float(due - len(w.commits)), 0.0))
        if w.commits:
            checks += commit_checks(committed, trained, limits)
        return harness.Outcome(e2e=e2e, record=record, checks=checks,
                               attempted=steps, failed=0,
                               memory_peak_bytes=peak)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _read_commit(lake, entry) -> dict:
    """What the commit holds: its params as saved, and its digest."""
    from repro.checkpoint import columns_to_tree

    tables = lake.catalog.tables(entry["commit"])
    meta = lake.catalog.commit_info(entry["commit"]).meta["checkpoint"]
    cols = lake.io.read(tables["ckpt_params"])
    return {"params": columns_to_tree(cols), "digest": meta["params_digest"],
            "step": meta["step"]}


# ------------------------------------------------------------- the check
def fed_rows(docs, fed: List[np.ndarray], seq_len: int):
    """The reference's own rows for the batches that were fed, or an error
    message: every fed row must be a row of the corpus packed by the
    reference, and no row may come twice."""
    rows = generators.pack(docs, seq_len)
    index = {r.tobytes(): i for i, r in enumerate(rows)}
    seen, batches = set(), []
    for b in fed:
        ids = []
        for r in b:
            i = index.get(np.asarray(r, np.int32).tobytes())
            if i is None:
                return None, "a fed row is no row of the corpus"
            if i in seen:
                return None, "a row was fed twice in the first steps"
            seen.add(i)
            ids.append(i)
        batches.append(rows[ids])
    return batches, None


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              keep=None) -> Dict[str, float]:
    """Per leaf |got - want| / max(want, the median leaf's want)."""
    names = [n for n in want if keep is None or n in keep]
    med = float(np.median([want[n] for n in names]))
    return {n: abs(got[n] - want[n]) / max(want[n], med) for n in names}


def gap_checks(name: str, got, want, limits, keep=None) -> List[Check]:
    """The worst leaf's gap and the median leaf's gap of one reading."""
    gaps = leaf_gaps(got, want, keep)
    worst = max(gaps, key=gaps.get)
    log(f"{name}: worst leaf {worst} ({got[worst]!r} vs {want[worst]!r})")
    return [Check(name, gaps[worst], limits[name]),
            Check(name + "_median", float(np.median(list(gaps.values()))),
                  limits[name + "_median"])]


def compare(cell, init_seed, docs, w: Watch, losses, limits) -> List[Check]:
    job = cell.traffic
    batches, err = fed_rows(docs, w.fed, job["seq_len"])
    if err:
        log(f"check: {err}")
        return [Check("fed_rows", 1.0, 0.0)]
    sz = reference.Sizes.from_file(cell.config)
    t0 = time.perf_counter()
    ref = reference.train_readings(sz, init_seed, batches, job)
    log(f"reference: {time.perf_counter() - t0:.1f} s; losses "
        f"{ref['losses']}")
    checks = readings_checks(losses, w.first_grad, w.change, ref, limits)
    for extra in EXTRA:
        if extra == "half":
            half = [b[:len(b) // 2] for b in batches]
            low = reference.train_readings(sz, init_seed, half, job)
        elif extra == "frozen":
            low = reference.train_readings(sz, init_seed, batches, job,
                                           frozen=True)
        else:
            low = reference.train_readings(sz, init_seed, batches, job, extra)
        for c in readings_checks(low["losses"], low["first_grad"],
                                 low["change"], ref, limits):
            checks.append(Check(f"{c.name}.{extra}", c.value, c.limit))
    return checks


def readings_checks(losses, first_grad, change, ref, limits) -> List[Check]:
    """The numbers compared: each step's loss (the worst step's relative
    gap), the first clipped gradient's norm per leaf, and the change of
    the weights after the steps per leaf, leaving out leaves whose
    reference gradient is under a thousandth of the median leaf's."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"]))
    raw = ref["raw_grad"]
    med = float(np.median(list(raw.values())))
    moving = {n for n, g in raw.items() if g >= 1e-3 * med}
    return ([Check("loss_gap", loss_gap, limits["loss_gap"])]
            + gap_checks("grad_gap", first_grad, ref["first_grad"], limits)
            + gap_checks("change_gap", change, ref["change"], limits, moving))


def commit_checks(committed, trained, limits) -> List[Check]:
    """The window's first commit: its bytes equal the params it was given,
    and its digest is theirs (the benchmark's own digest)."""
    import digest

    if committed is None:
        return [Check("commit_bytes_differ", 1.0, 0.0)]
    import jax

    flat_c = dict(jax.tree_util.tree_leaves_with_path(committed["params"]))
    flat_t = dict(jax.tree_util.tree_leaves_with_path(trained))
    differ = sum(1 for k in flat_t
                 if k not in flat_c or flat_t[k].dtype != flat_c[k].dtype
                 or flat_t[k].tobytes() != np.asarray(flat_c[k]).tobytes())
    ok_digest = digest.tree_digest_hex(trained) == committed["digest"]
    return [Check("commit_bytes_differ", float(differ), 0.0),
            Check("commit_digest_differs", 0.0 if ok_digest else 1.0, 0.0)]
