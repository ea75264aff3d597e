"""Mixture-of-Experts layer: top-k routing with capacity, scatter dispatch.

TPU-native design notes (DESIGN.md §2): instead of the quadratic GShard
one-hot dispatch einsum, tokens are placed into per-expert capacity buffers
with a scatter (memory-bound, not FLOP-bound) and combined back with a
gather.  Expert positions come from a cumsum over one-hot assignments — no
sort — which partitions cleanly under SPMD (per-shard cumsum + offset
all-reduce).  Experts are sharded over the ``model`` ("expert-parallel")
axis; the scatter/gather across data→expert shards lowers to all-to-alls.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class MoEMetrics(NamedTuple):
    aux_loss: jnp.ndarray       # load-balancing loss (scalar fp32)
    dropped_frac: jnp.ndarray   # fraction of (token,k) slots over capacity


def capacity_of(n_tokens: int, n_experts: int, top_k: int,
                capacity_factor: float) -> int:
    if capacity_factor <= 0:  # no-drop mode (serving): worst case = T
        cap = n_tokens
    else:
        cap = int(math.ceil(n_tokens * top_k / n_experts * capacity_factor))
    return max(8, ((cap + 7) // 8) * 8)  # pad to lane multiple


def moe_layer(
    x: jnp.ndarray,          # (B, S, D)
    router_w: jnp.ndarray,   # (D, E)
    w_gate: jnp.ndarray,     # (E, D, F)
    w_up: jnp.ndarray,       # (E, D, F)
    w_down: jnp.ndarray,     # (E, F, D)
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
    normalize_gates: bool = True,
    ac=lambda x, name=None: x,   # activation-sharding hook (EP layouts)
    combine_dtype: str | None = None,  # bf16 combine payloads (§Perf)
) -> tuple[jnp.ndarray, MoEMetrics]:
    B, S, D = x.shape
    E, _, F = w_gate.shape
    T = B * S
    C = capacity_of(T, E, top_k, capacity_factor)
    xf = x.reshape(T, D)

    # ---- routing (fp32 for numerical stability of the softmax) ----
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)      # (T, K)
    if normalize_gates:
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # ---- load-balancing aux loss (Switch-style) ----
    me = jnp.mean(probs, axis=0)                             # (E,)
    onehot_top1 = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(onehot_top1, axis=0)
    aux = jnp.sum(me * ce) * E

    # ---- position of each (t, k) inside its expert's capacity buffer ----
    flat_ids = expert_ids.reshape(-1)                        # (T*K,) k-major? t-major
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)    # (T*K, E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot      # exclusive
    pos = jnp.take_along_axis(pos_in_expert, flat_ids[:, None],
                              axis=1)[:, 0]                  # (T*K,)
    within_cap = pos < C
    slot = jnp.where(within_cap, flat_ids * C + pos, E * C)  # OOB → dropped

    # ---- dispatch: scatter tokens into (E*C, D) expert buffers ----
    xk = jnp.repeat(xf, top_k, axis=0) if top_k > 1 else xf  # (T*K, D)
    xk = ac(xk, "moe_tokens")
    # NB: jnp.repeat(t-major) matches expert_ids.reshape(-1) (t-major, k minor)
    buf = jnp.zeros((E * C, D), x.dtype)
    buf = buf.at[slot].set(xk.astype(x.dtype), mode="drop")
    buf = ac(buf.reshape(E, C, D), "moe_buf")  # EP layout: experts sharded

    # ---- expert computation: batched matmuls over the expert axis ----
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
         if act == "silu"
         else jax.nn.gelu(g.astype(jnp.float32)).astype(x.dtype) * u)
    out = jnp.einsum("ecf,efd->ecd", h, w_down)
    out = ac(out, "moe_buf").reshape(E * C, D)

    # ---- combine: gather back, weight by gates, sum over k ----
    # The gather moves (T*K, D) across the EP boundary; keeping the payload
    # in the model dtype (bf16) instead of letting the fp32 gate multiply
    # upcast it halves the all-to-all bytes (§Perf iteration).
    cdt = jnp.dtype(combine_dtype) if combine_dtype else jnp.float32
    out_padded = jnp.concatenate([out, jnp.zeros((1, D), out.dtype)], axis=0)
    safe_slot = jnp.where(within_cap, slot, E * C)
    yk = out_padded[safe_slot].astype(cdt)                   # (T*K, D)
    yk = ac(yk, "moe_tokens")
    yk = yk * gate_vals.reshape(-1)[:, None].astype(cdt)
    y = jnp.sum(yk.reshape(T, top_k, D), axis=1)

    dropped = 1.0 - jnp.mean(within_cap.astype(jnp.float32))
    return y.reshape(B, S, D), MoEMetrics(aux.astype(jnp.float32), dropped)


# ======================================================================
# Hand-scheduled expert parallelism (shard_map) — §Perf beyond-paper.
#
# Observation: in this framework's layout, activations are sharded over the
# data axes and REPLICATED over "model" — every model-rank already holds all
# tokens of its data shard.  So EP needs NO token all-to-all at all:
#   1. each rank routes its (replicated) tokens locally,
#   2. keeps only the (token, k) pairs destined for ITS expert slice,
#   3. runs its experts locally,
#   4. one bf16 psum over "model" combines the per-rank partial outputs.
# The pjit baseline instead lowers the same computation to
# scatter-by-all-reduce + f32 all-to-alls (~20 GB/layer measured for
# qwen3-moe prefill); this path moves ~1 GB/layer.
# ======================================================================
def moe_layer_ep(
    x: jnp.ndarray,          # (B, S, D) — sharded (dp, None, None)
    router_w: jnp.ndarray,   # (D, E)    — replicated
    w_gate: jnp.ndarray,     # (E, D, F) — E sharded over "model"
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,     # (E, F, D)
    *,
    mesh,
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
    normalize_gates: bool = True,
    dp_axes: tuple = ("data",),
    ep_axis: str = "model",
) -> tuple[jnp.ndarray, MoEMetrics]:
    from jax.sharding import PartitionSpec as P

    E = w_gate.shape[0]
    ep_n = int(mesh.shape[ep_axis])
    assert E % ep_n == 0, (E, ep_n)
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)

    def local(x_loc, rw, wg, wu, wd):
        B_loc, S, D = x_loc.shape
        E_loc, _, F = wg.shape
        T = B_loc * S
        xf = x_loc.reshape(T, D)
        my_rank = jax.lax.axis_index(ep_axis)
        my_lo = my_rank * E_loc

        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            rw.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
        if normalize_gates:
            gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E,
                                     dtype=jnp.float32), axis=0)
        aux = jnp.sum(me * ce) * E

        # (token, k) pairs destined for MY expert slice
        flat_ids = expert_ids.reshape(-1)
        mine = (flat_ids >= my_lo) & (flat_ids < my_lo + E_loc)
        local_ids = jnp.where(mine, flat_ids - my_lo, E_loc)  # E_loc = drop
        C = capacity_of(T, E, top_k, capacity_factor)
        onehot = jax.nn.one_hot(local_ids, E_loc, dtype=jnp.int32)
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - onehot,
            jnp.minimum(local_ids, E_loc - 1)[:, None], axis=1)[:, 0]
        within = mine & (pos < C)
        slot = jnp.where(within, local_ids * C + pos, E_loc * C)

        xk = jnp.repeat(xf, top_k, axis=0) if top_k > 1 else xf
        buf = jnp.zeros((E_loc * C, D), x_loc.dtype)
        buf = buf.at[slot].set(xk.astype(x_loc.dtype), mode="drop")
        buf = buf.reshape(E_loc, C, D)

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = (jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * u
             if act == "silu"
             else jax.nn.gelu(g.astype(jnp.float32)).astype(x_loc.dtype) * u)
        out = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_loc * C, D)

        out_padded = jnp.concatenate(
            [out, jnp.zeros((1, D), out.dtype)], axis=0)
        yk = out_padded[jnp.where(within, slot, E_loc * C)]
        yk = yk * gate_vals.reshape(-1)[:, None].astype(yk.dtype)
        y_partial = jnp.sum(yk.reshape(T, top_k, D), axis=1)

        # THE one collective: combine partial expert outputs across ranks
        y = jax.lax.psum(y_partial, ep_axis)
        dropped = 1.0 - jnp.mean(
            jax.lax.psum(within.astype(jnp.float32), ep_axis))
        return (y.reshape(B_loc, S, D), aux.reshape(1),
                dropped.reshape(1))

    y, aux, dropped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None)),
        out_specs=(P(dp_spec, None, None), P(dp_spec), P(dp_spec)),
        check_vma=False,
    )(x, router_w, w_gate, w_up, w_down)
    # per-dp-shard scalars (each shard routed different tokens) → average
    return y, MoEMetrics(jnp.mean(aux), jnp.mean(dropped))

