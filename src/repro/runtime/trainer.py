"""Fault-tolerant trainer: the paper's replayable-pipeline semantics wrapped
around a JAX training loop.

Run anatomy (Fig. 4 of the paper, applied to training):
  - every run gets its own branch ``<user>.run-<name>`` forked from the data
    branch (copy-on-write — the corpus is never copied);
  - checkpoints are commits on that branch (step + iterator state + digest);
  - a crash (or injected failure) resumes by checking out the latest
    checkpoint commit — bit-exact thanks to the stateless loader;
  - at the end, metrics tables go through write-audit-publish before the
    run branch is merged into the target branch;
  - the run manifest (code/config/data/hardware) lands in the ledger so
    ``replay(run_id)`` can reproduce the whole run later.

Straggler mitigation: a host-side watchdog tracks step wall-times; steps
slower than ``straggler_factor ×`` the running median are counted and logged
to the metrics table (on a real pod the same hook triggers re-dispatch /
slice exclusion; in simulation it is observability + a tested interface).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from .. import checkpoint as ckpt
from .. import tracing
from ..core import Lake, Pipeline, not_empty, no_nans, publish
from ..core.wap import Expectation
from ..data.loader import DeterministicLoader
from ..models.config import ModelConfig
from ..optim import adamw
from .steps import build_train_step


@dataclass
class TrainerConfig:
    arch: str
    seq_len: int
    global_batch: int
    n_steps: int
    ckpt_every: int = 50
    seed: int = 0
    schedule: str = "cosine"
    schedule_kw: Optional[dict] = None
    opt: adamw.AdamWConfig = field(default_factory=adamw.AdamWConfig)
    straggler_factor: float = 3.0
    author: str = "trainer"


@dataclass
class StepRecord:
    step: int
    loss: float
    wall_ms: float
    straggler: bool


class Trainer:
    def __init__(self, lake: Lake, cfg: ModelConfig, tcfg: TrainerConfig,
                 *, data_branch: str, run_name: str,
                 mesh=None, ac=None, failure_at: Optional[int] = None):
        self.lake = lake
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.run_branch = f"{tcfg.author}.run-{run_name}"
        self.data_branch = data_branch
        self.failure_at = failure_at  # fault-injection hook (tests)
        self.records: List[StepRecord] = []
        self.straggler_events = 0

        if self.run_branch not in lake.catalog.branches():
            lake.catalog.create_branch(self.run_branch, data_branch,
                                       author=tcfg.author)
        packed = lake.read_table(self.run_branch, "packed")
        self.loader = DeterministicLoader(
            packed["tokens"], global_batch=tcfg.global_batch, seed=tcfg.seed)
        self.train_step = jax.jit(build_train_step(
            cfg, opt_config=tcfg.opt, schedule=tcfg.schedule,
            schedule_kw=tcfg.schedule_kw,
            ac=ac if ac is not None else (lambda x, name=None: x)))

    # ---------------------------------------------------------------- state
    def init_state(self):
        from ..models import init_params

        key = jax.random.PRNGKey(self.tcfg.seed)
        params = init_params(self.cfg, key)
        opt_state = adamw.init(params, self.tcfg.opt)
        return params, opt_state, 0

    def restore_state(self):
        """Resume from the newest checkpoint commit on the run branch."""
        commit = ckpt.latest_checkpoint(self.lake, self.run_branch)
        if commit is None:
            return self.init_state()
        params, opt_tree, meta = ckpt.restore(self.lake, commit)
        if opt_tree is None:
            opt_state = adamw.init(params, self.tcfg.opt)
        else:  # the saved leaves, typed by the optimizer's own structure
            template = jax.eval_shape(
                lambda p: adamw.init(p, self.tcfg.opt), params)
            opt_state = jax.device_put(ckpt.restore_into(
                template, ckpt.leaves_to_columns(opt_tree)))
        return params, opt_state, int(meta["step"])

    # ----------------------------------------------------------------- loop
    def run(self, *, resume: bool = False) -> Dict[str, Any]:
        params, opt_state, start_step = (self.restore_state() if resume
                                         else self.init_state())
        median_tracker: List[float] = []
        for step in range(start_step, self.tcfg.n_steps):
            if self.failure_at is not None and step == self.failure_at:
                self.failure_at = None  # next attempt survives
                raise RuntimeError(f"injected node failure at step {step}")
            with tracing.span("train.step", step=step) as span:
                with tracing.span("train.feed") as feed:
                    tokens = self.loader.batch(step)["tokens"]
                    batch = {"tokens": jax.numpy.asarray(tokens)}
                    feed.set(bytes=int(tokens.nbytes))
                with tracing.span("train.dispatch"):
                    params, opt_state, metrics = self.train_step(
                        params, opt_state, batch)
                with tracing.span("train.sync"):
                    loss = float(metrics["loss"])
                wall = span.elapsed() * 1e3
                median_tracker.append(wall)
                med = float(np.median(median_tracker[-32:]))
                straggler = len(median_tracker) > 4 and \
                    wall > self.tcfg.straggler_factor * med
                if straggler:
                    self.straggler_events += 1
                self.records.append(StepRecord(step, loss, wall, straggler))

            if (step + 1) % self.tcfg.ckpt_every == 0 \
                    or step + 1 == self.tcfg.n_steps:
                ckpt.save(self.lake, self.run_branch, step=step + 1,
                          params=params, opt_state=opt_state,
                          author=self.tcfg.author,
                          extra_meta={"loader_seed": self.tcfg.seed,
                                      "straggler_events":
                                          self.straggler_events})
        self._write_metrics()
        return {"params": params, "opt_state": opt_state,
                "final_step": self.tcfg.n_steps,
                "losses": [r.loss for r in self.records]}

    def _write_metrics(self):
        recs = self.records
        if not recs:
            return
        self.lake.write_table(
            self.run_branch, "train_metrics",
            {
                "step": np.array([r.step for r in recs], np.int64),
                "loss": np.array([r.loss for r in recs], np.float64),
                "wall_ms": np.array([r.wall_ms for r in recs], np.float64),
                "straggler": np.array([r.straggler for r in recs], np.bool_),
            },
            author=self.tcfg.author, message="training metrics")

    # ------------------------------------------------------------------ WAP
    def default_expectations(self) -> List[Expectation]:
        from ..core import expectation

        @expectation("train_metrics", name="loss_finite")
        def loss_finite(f):
            return bool(np.isfinite(f["loss"]).all())

        @expectation("train_metrics", name="loss_decreased")
        def loss_decreased(f):
            loss = f["loss"]
            k = max(len(loss) // 5, 1)
            return float(loss[-k:].mean()) < float(loss[:k].mean())

        return [not_empty("train_metrics"), loss_finite, loss_decreased]

    def publish(self, dst_branch: str = "main",
                expectations: Optional[List[Expectation]] = None) -> str:
        """Write-Audit-Publish the run branch (checkpoints + metrics)."""
        return publish(self.lake.catalog, self.lake.io, self.run_branch,
                       expectations or self.default_expectations(),
                       dst_branch=dst_branch, author=self.tcfg.author)
