#!/usr/bin/env python3
"""Chip smoke test: the lake → train → checkpoint-commit → resume → serve
path of the ``paper-demo`` model at its full config (12 layers, d_model 768,
~100M parameters, random weights from a seed), on one TPU chip, in one
process, through the entry points a user calls.

  python3 chip_smoke.py            # one chip: every phase
  python3 chip_smoke.py --chips 4  # four chips: sharded train steps and an
                                   # elastic restore, each against one chip

Phases (each prints one line, and any failed check ends the run non-zero):
  device  the first device must be a TPU; otherwise exit 2, no result line
  data    seed a lake with a corpus and run the packing pipeline
  train   Trainer, 20 steps of 8 x 1024 tokens, a checkpoint commit every 10
  digest  the compiled fingerprint kernel equals the reference on every leaf
  resume  injected failure → run(resume=True): losses equal the uninterrupted
          run's bit for bit; restore(verify=True) of the last commit
  serve   ServeEngine.from_catalog + BatchedServer (4 slots), 8 requests:
          tokens equal generating each request alone; prefill logits agree
          with a float32 forward at "highest" matmul precision

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# Prefill logits vs the float32 reference.  Bound set from the worst case
# that a float32 matmul at default precision on a TPU is one bf16 pass
# (inputs keep 8 significant bits, relative rounding 2^-9 ≈ 2e-3), with about
# seven chained matmuls per layer over 12 layers plus the unembedding adding
# such errors roughly in quadrature: near 2e-2, times 2.5.  A v5e measured
# 0.9e-3 to 1.5e-3.
PREFILL_REL_L2 = 5e-2
# Four chips against one: the same arithmetic, summed in another order
# across shards (float32 accumulation, ~1e-6 relative per reduction); three
# Adam steps can amplify that in near-zero-gradient coordinates by no more
# than the learning rate allows.
SHARDED_REL = 1e-3


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The run's sizes; the defaults are what the chip runs."""
    seq_len: int = 1024
    batch: int = 8
    steps: int = 20
    ckpt_every: int = 10
    fail_at: int = 15
    n_docs: int = 512
    mean_len: int = 512
    slots: int = 4
    max_len: int = 512
    # (prompt length, tokens to generate): 8 requests over 4 prompt lengths
    requests: tuple = ((37, 24), (200, 8), (37, 5), (455, 16),
                       (120, 31), (200, 1), (120, 12), (455, 40))
    sharded_steps: int = 3


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class Phases:
    """Prints one line per phase with its compiles, read from the program's
    ``jax.compile`` spans (``fun_name`` says which jitted function
    compiled).  Each phase is a span, so its compiles are recorded."""

    @staticmethod
    def compiles(since: float, fun_name=None) -> list:
        from repro import tracing

        return [s for s in tracing.spans() if s.name == tracing.COMPILE
                and s.t0 >= since
                and fun_name in (None, s.attrs["fun_name"])]

    @contextlib.contextmanager
    def phase(self, name: str):
        from repro import tracing

        with tracing.span("smoke." + name) as span:
            try:
                yield span.t0
            except BaseException as e:
                print(f"[{name}] FAILED: {type(e).__name__}: {e}",
                      flush=True)
                raise
        done = self.compiles(span.t0)
        secs = sum(s.t1 - s.t0 for s in done)
        print(f"[{name}] ok: {len(done)} compiles, {secs:.1f} s compiling, "
              f"{span.elapsed():.1f} s in the phase", flush=True)


# ----------------------------------------------------------------- phases
def seed_lake(lake, cfg, sz: Sizes):
    from repro.data import build_data_pipeline, seed_corpus

    lake.catalog.create_branch("data.main", "main", author="data")
    seed_corpus(lake, "data.main", n_docs=sz.n_docs, seed=SEED,
                vocab_size=cfg.vocab_size, mean_len=sz.mean_len,
                author="data")
    res = lake.run(build_data_pipeline(sz.seq_len), branch="data.main",
                   author="data")
    tokens = lake.read_table("data.main", "packed")["tokens"]
    check(tokens.shape[1] == sz.seq_len and tokens.shape[0] >= sz.batch,
          f"packed table has shape {tokens.shape}")
    print(f"  run {res.run_id}: packed {tokens.shape[0]} rows x "
          f"{tokens.shape[1]} tokens")
    return tokens


def trainer_config(cfg, sz: Sizes):
    from repro.runtime import TrainerConfig

    return TrainerConfig(
        arch=cfg.name, seq_len=sz.seq_len, global_batch=sz.batch,
        n_steps=sz.steps, ckpt_every=sz.ckpt_every, seed=SEED,
        schedule="cosine",
        schedule_kw={"peak_lr": 3e-4, "warmup_steps": 2,
                     "total_steps": sz.steps},
        author="trainer")


def checkpoint_steps(lake, branch: str) -> list:
    steps = []
    for commit in lake.catalog.log(branch):
        meta = lake.catalog.commit_info(commit).meta
        if "checkpoint" in meta:
            steps.append(meta["checkpoint"]["step"])
    return sorted(steps)


def train(lake, cfg, sz: Sizes):
    """The uninterrupted run: the reference for the resumed one."""
    from repro.kernels import interpret_mode
    from repro.kernels.fingerprint import ops as fp_ops
    from repro.runtime import Trainer

    trainer = Trainer(lake, cfg, trainer_config(cfg, sz),
                      data_branch="data.main", run_name="uninterrupted")
    out = trainer.run()
    losses = [r.loss for r in trainer.records]
    check(len(losses) == sz.steps and np.isfinite(losses).all(),
          f"losses {losses}")
    want = list(range(sz.ckpt_every, sz.steps + 1, sz.ckpt_every))
    got = checkpoint_steps(lake, trainer.run_branch)
    check(got == want, f"checkpoint commits at steps {got}, want {want}")
    check(not interpret_mode(), "Pallas kernels would run interpreted")
    leaf = out["params"]["embed"]
    hlo = fp_ops._digest.lower(leaf).compile().as_text()
    check("tpu_custom_call" in hlo, "fingerprint did not compile to Mosaic")
    print(f"  loss {losses[0]:.4f} -> {losses[-1]:.4f}; commits at steps "
          f"{got}; fingerprint compiled (tpu_custom_call)")
    return trainer, out


def digest(lake, trainer, out):
    import jax
    from repro import checkpoint as ckpt
    from repro.kernels.fingerprint.ops import tensor_digest, tree_digest_hex
    from repro.kernels.fingerprint.ref import fingerprint_ref

    ref = jax.jit(fingerprint_ref)
    leaves = jax.tree_util.tree_leaves_with_path(out["params"])
    for path, leaf in leaves:
        got, want = np.asarray(tensor_digest(leaf)), np.asarray(ref(leaf))
        check(np.array_equal(got, want),
              f"digest of {jax.tree_util.keystr(path)}: {got} != {want}")
    last = ckpt.latest_checkpoint(lake, trainer.run_branch)
    stored = lake.catalog.commit_info(last).meta["checkpoint"]
    check(stored["params_digest"] == tree_digest_hex(out["params"]),
          "the committed params_digest is not the params' digest")
    print(f"  {len(leaves)} leaves: kernel == fingerprint_ref bit for bit; "
          f"commit digest {stored['params_digest'][:16]}")


def resume(lake, cfg, sz: Sizes, reference):
    from repro import checkpoint as ckpt
    from repro.runtime import Trainer

    trainer = Trainer(lake, cfg, trainer_config(cfg, sz),
                      data_branch="data.main", run_name="resumed",
                      failure_at=sz.fail_at)
    try:
        trainer.run()
    except RuntimeError as e:
        check("injected" in str(e), f"unexpected failure: {e}")
    else:
        raise SmokeFailure("the injected failure did not fire")
    trainer.run(resume=True)
    after = trainer.records[sz.fail_at:]
    restart = sz.fail_at - sz.fail_at % sz.ckpt_every
    check([r.step for r in after] == list(range(restart, sz.steps)),
          f"resumed steps {[r.step for r in after]}")
    want = {r.step: r.loss for r in reference.records}
    bad = [(r.step, r.loss, want[r.step]) for r in after
           if r.loss != want[r.step]]
    check(not bad, f"resumed losses differ (step, got, want): {bad}")
    last = ckpt.latest_checkpoint(lake, trainer.run_branch)
    _, _, meta = ckpt.restore(lake, last, verify=True)
    ref_last = ckpt.latest_checkpoint(lake, reference.run_branch)
    ref_meta = lake.catalog.commit_info(ref_last).meta["checkpoint"]
    check(meta["step"] == sz.steps, f"last commit at step {meta['step']}")
    check(meta["params_digest"] == ref_meta["params_digest"],
          "final params differ from the uninterrupted run's")
    print(f"  restarted at step {restart} after a failure at {sz.fail_at}: "
          f"{len(after)} losses bit-equal to the uninterrupted run; final "
          f"params digest equal; restore(verify=True) ok")
    return last


def serve(lake, cfg, sz: Sizes, commit, phases: Phases, mark: int):
    import jax
    import jax.numpy as jnp
    from repro.models import forward, init_cache
    from repro.runtime import build_prefill_step
    from repro.serving import BatchedServer, ServeEngine

    engine = ServeEngine.from_catalog(lake, commit, cfg, max_len=sz.max_len,
                                      batch_size=sz.slots)
    check(all(isinstance(x, jax.Array) and x.devices() == {jax.devices()[0]}
              for x in jax.tree.leaves(engine.params)),
          "serving weights are not on the chip")
    server = BatchedServer(engine)
    rng = np.random.default_rng(SEED)
    prompts = {}
    for rid, (plen, n) in enumerate(sz.requests):
        prompts[rid] = rng.integers(3, cfg.vocab_size,
                                    size=plen).astype(np.int32)
        server.submit(rid, prompts[rid], n)
    while server.pending:
        server.step()
    check(sorted(server.completed) == list(range(len(sz.requests))),
          f"completed {sorted(server.completed)}")
    lengths = sorted({p for p, _ in sz.requests})
    n_prefill = len(phases.compiles(mark, "jit(prefill_tok)"))
    print(f"  prefill compiles: {n_prefill} for {len(lengths)} distinct "
          f"prompt lengths {lengths}")

    solo = ServeEngine(cfg, engine.params, max_len=sz.max_len, batch_size=1,
                       model_commit=commit)
    for rid, (_, n) in enumerate(sz.requests):
        alone = solo.generate(prompts[rid][None], n_tokens=n).tokens
        got = server.completed[rid].tokens
        check(np.array_equal(got, alone),
              f"request {rid}: batched {got.tolist()} != alone "
              f"{alone.tolist()}")

    prefill = jax.jit(build_prefill_step(cfg, max_len=sz.max_len))
    f32 = cfg.with_(param_dtype="float32", compute_dtype="float32")
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), engine.params)
    reference = jax.jit(lambda p, t: forward(f32, p, t, remat=False)[0][:, -1])
    errs = []
    for plen in lengths:
        rid = next(r for r, (p, _) in enumerate(sz.requests) if p == plen)
        tokens = jnp.asarray(prompts[rid][None])
        cache = init_cache(cfg, 1, sz.max_len, dtype=cfg.compute_dtype)
        got, _ = prefill(engine.params, tokens, cache)
        with jax.default_matmul_precision("highest"):
            want = reference(params32, tokens)
        errs.append(rel_l2(got, want))
    check(max(errs) <= PREFILL_REL_L2,
          f"prefill logits rel L2 {errs} > {PREFILL_REL_L2}")
    print(f"  {len(sz.requests)} requests: batched tokens == each alone; "
          f"prefill logits rel L2 vs float32 'highest' "
          f"{[f'{e:.2e}' for e in errs]} (bound {PREFILL_REL_L2})")


def four_chips(lake, cfg, sz: Sizes, tokens):
    """Sharded train steps on a 2 x 2 (data x model) mesh against one chip,
    then a checkpoint saved from the sharded params restored onto a 4 x 1
    mesh and onto one chip (elastic restore)."""
    import jax
    import jax.numpy as jnp
    from repro import checkpoint as ckpt
    from repro.data import DeterministicLoader
    from repro.distributed import (batch_specs, make_activation_constraint,
                                   named, opt_state_specs, param_specs)
    from repro.launch.mesh import auto_mesh, make_local_mesh
    from repro.models import forward, init_params
    from repro.optim import adamw
    from repro.runtime import build_train_step

    mesh = make_local_mesh(model=2)
    check(mesh.devices.shape == (2, 2), f"mesh {mesh.devices.shape}")
    pspecs = param_specs(cfg, mesh)
    ospecs = opt_state_specs(pspecs, has_master=False, compress=False)
    bshard = named(mesh, batch_specs(cfg, mesh, global_batch=sz.batch))
    opt_cfg = adamw.AdamWConfig()
    tcfg = trainer_config(cfg, sz)
    kw = dict(opt_config=opt_cfg, schedule=tcfg.schedule,
              schedule_kw=tcfg.schedule_kw)
    sharded_step = jax.jit(
        build_train_step(cfg, ac=make_activation_constraint(cfg, mesh), **kw),
        in_shardings=(named(mesh, pspecs), named(mesh, ospecs), bshard),
        out_shardings=(named(mesh, pspecs), named(mesh, ospecs), None))
    one_step = jax.jit(build_train_step(cfg, **kw))

    p1 = init_params(cfg, jax.random.PRNGKey(SEED))
    o1 = adamw.init(p1, opt_cfg)
    p4 = jax.device_put(p1, named(mesh, pspecs))
    o4 = jax.device_put(o1, named(mesh, ospecs))
    loader = DeterministicLoader(tokens, global_batch=sz.batch, seed=SEED)
    errs = []
    for step in range(sz.sharded_steps):
        batch = loader.batch(step)["tokens"]
        p1, o1, m1 = one_step(p1, o1, {"tokens": jnp.asarray(batch)})
        p4, o4, m4 = sharded_step(
            p4, o4, {"tokens": jax.device_put(batch, bshard["tokens"])})
        l1, l4 = float(m1["loss"]), float(m4["loss"])
        errs.append(abs(l4 - l1) / abs(l1))
        check(np.isfinite(l4) and errs[-1] <= SHARDED_REL,
              f"step {step}: sharded loss {l4} vs one chip {l1}")
    print(f"  {sz.sharded_steps} steps on a 2x2 mesh: loss rel diff vs one "
          f"chip {[f'{e:.2e}' for e in errs]} (bound {SHARDED_REL})")

    lake.catalog.create_branch("trainer.run-sharded", "data.main",
                               author="trainer")
    commit = ckpt.save(lake, "trainer.run-sharded", step=sz.sharded_steps,
                       params=p4, author="trainer")
    mesh41 = auto_mesh((4, 1), ("data", "model"))
    pa, _, _ = ckpt.restore(lake, commit, mesh=mesh41,
                            param_specs=param_specs(cfg, mesh41), verify=True)
    pb, _, _ = ckpt.restore(lake, commit, verify=True)
    check(len(pa["embed"].sharding.device_set) == 4,
          "the 4x1 restore is not spread over four chips")
    tok = jnp.asarray(loader.batch(sz.sharded_steps)["tokens"][:2, :256])
    errs = {}
    for precision in ("default", "highest"):
        with jax.default_matmul_precision(precision):
            fwd = jax.jit(lambda p, t: forward(cfg, p, t, remat=False)[0])
            errs[precision] = rel_l2(fwd(pa, tok), fwd(pb, tok))
    # the bits are equal (both digests verified), so compare the layouts at
    # float32 precision: at the default one the two programs round
    # different intermediates to bf16 (measured 2e-3 apart on a v5e)
    check(errs["highest"] <= SHARDED_REL,
          f"elastic restore logits rel L2 {errs}")
    print(f"  checkpoint from the 2x2 mesh restored onto 4x1 and onto one "
          f"chip (digest verified): forward logits rel L2 "
          f"{errs['highest']:.2e} at 'highest' (bound {SHARDED_REL}), "
          f"{errs['default']:.2e} at default precision")


# ------------------------------------------------------------------- main
def require_tpu(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chip_smoke: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        sys.exit(2)
    return devs


def run(chips: int, cfg=None, sz: Sizes = Sizes()) -> dict:
    import jax
    from repro.configs import full_config
    from repro.core import Lake
    from repro.launch.compile_cache import enable_compile_cache

    cfg = cfg or full_config("paper-demo")
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    print(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}; model "
          f"{cfg.name} ({cfg.param_count() / 1e6:.1f}M params)", flush=True)
    phases = Phases()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        lake = Lake(os.path.join(tmp, "lake"))
        with phases.phase("data"):
            tokens = seed_lake(lake, cfg, sz)
        if chips == 4:
            with phases.phase("four-chips"):
                four_chips(lake, cfg, sz, tokens)
        else:
            with phases.phase("train"):
                reference, out = train(lake, cfg, sz)
            with phases.phase("digest"):
                digest(lake, reference, out)
            del out
            with phases.phase("resume"):
                last = resume(lake, cfg, sz, reference)
            with phases.phase("serve") as mark:
                serve(lake, cfg, sz, last, phases, mark)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-train and elastic-restore "
                         "phase, across four chips")
    args = ap.parse_args(argv)

    import jax

    require_tpu(jax, args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    device = run(args.chips)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
