#!/usr/bin/env python3
"""Compile each cell's programs at its shapes for a described TPU v5e, with
no chip attached, and print what the compiler says of their memory.

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/chip/rehearse.py \
      --workload mamba2-370m.train.commit [--batch 8]

Nothing runs: a compile that passes says the program is accepted and fits
one chip's memory, not how fast it is.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def _mem(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"args {m.argument_size_in_bytes / 1e9:.3f} GB, out "
            f"{m.output_size_in_bytes / 1e9:.3f} GB, temp "
            f"{m.temp_size_in_bytes / 1e9:.3f} GB, alias "
            f"{m.alias_size_in_bytes / 1e9:.3f} GB")


def train(cfg, job, sharding, batch):
    import jax
    import jax.numpy as jnp
    from repro.models import init_params
    from repro.optim import adamw
    from repro.runtime import build_train_step

    opt = adamw.AdamWConfig(**job["optimizer"])
    step = build_train_step(cfg, opt_config=opt, schedule=job["schedule"],
                            schedule_kw=job["schedule_kw"])
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), t)
    params = put(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    state = put(jax.eval_shape(lambda p: adamw.init(p, opt), params))
    tokens = jax.ShapeDtypeStruct((batch, job["seq_len"]), jnp.int32,
                                  sharding=sharding)
    c = jax.jit(step).lower(params, state, {"tokens": tokens}).compile()
    print(f"train_step {batch}x{job['seq_len']}: {_mem(c)}", flush=True)


def serve(cfg, mix, sharding):
    import jax
    import jax.numpy as jnp
    from repro.models import init_cache, init_params
    from repro.serving.engine import _shared_steps

    put = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), t)
    slots, max_len = mix["slots"], mix["max_len"]
    _, prefill_tok, _, row_decode, write_slot = _shared_steps(cfg, max_len)
    params = put(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache1 = put(jax.eval_shape(lambda: init_cache(cfg, 1, max_len,
                                                   dtype=cfg.compute_dtype)))
    pool = jax.eval_shape(lambda: init_cache(cfg, slots, max_len,
                                             dtype=cfg.compute_dtype))
    pool["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    pool = put(pool)
    toks = put(jax.ShapeDtypeStruct((slots, 1), jnp.int32))
    c = row_decode.lower(params, toks, pool).compile()
    print(f"row_decode {slots} slots x {max_len}: {_mem(c)}", flush=True)
    first = put(jax.ShapeDtypeStruct((), jnp.int32))
    c = write_slot.lower(pool, toks, cache1, first, 0).compile()
    print(f"write_slot: {_mem(c)}", flush=True)
    for plen in sorted(int(k) for k in mix["prompt_len"]):
        t = put(jax.ShapeDtypeStruct((1, plen), jnp.int32))
        c = prefill_tok.lower(params, t, cache1).compile()
        print(f"prefill_tok 1x{plen}: {_mem(c)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None,
                    help="train cells: compile at this batch instead")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import spec

    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = cell.model_config()
    if cell.traffic["kind"] == "train":
        train(cfg, cell.traffic, one_chip,
              args.batch or cell.traffic["global_batch"])
    else:
        serve(cfg, cell.traffic, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
