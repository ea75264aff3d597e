"""Hierarchical multi-pod collectives (shard_map level).

Cross-pod ICI/DCN links are the scarce resource at 2+ pods.  The standard
trick: reduce-scatter INSIDE the pod (fast links), all-reduce the shards
ACROSS pods (slow links carry 1/|pod-size| of the bytes), all-gather back
inside the pod.  Optionally the cross-pod hop runs int8 with error feedback
(``repro.optim.compress_int8``), cutting slow-link bytes another 4×.

These run under ``jax.shard_map`` with explicit axis names, so the collective
schedule is deterministic rather than left to SPMD propagation — the
building block for the multi-pod gradient path (EXPERIMENTS.md §Perf,
"beyond-paper").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def hierarchical_psum(x: jnp.ndarray, *, intra_axis: str = "data",
                      inter_axis: str = "pod") -> jnp.ndarray:
    """psum(x) over (inter × intra) via RS(intra) → AR(inter) → AG(intra).

    Byte-equivalent result to a flat psum, but the ``inter_axis`` (cross-pod)
    hop moves only 1/|intra| of the tensor per device.
    Call INSIDE shard_map with both axes bound.
    """
    n_intra = jax.lax.axis_size(intra_axis)
    pad = (-x.shape[0]) % n_intra
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x
    # reduce-scatter within the pod
    shard = jax.lax.psum_scatter(xp, intra_axis, scatter_dimension=0,
                                 tiled=True)
    # all-reduce the 1/n shard across pods (the slow hop)
    shard = jax.lax.psum(shard, inter_axis)
    # all-gather within the pod
    full = jax.lax.all_gather(shard, intra_axis, axis=0, tiled=True)
    return full[: x.shape[0]] if pad else full


def hierarchical_psum_int8(x: jnp.ndarray, residual: jnp.ndarray, *,
                           intra_axis: str = "data",
                           inter_axis: str = "pod"):
    """Like ``hierarchical_psum`` but the cross-pod hop is int8 with error
    feedback: returns (psum_approx, new_residual).

    The intra-pod reduce-scatter stays full precision (fast links); only the
    scattered shard is quantized for the inter-pod all-reduce.  The
    quantization error is fed back into ``residual`` so it is re-applied on
    the next step (convergence-preserving — standard EF-SGD argument).
    """
    n_intra = jax.lax.axis_size(intra_axis)
    n_inter = jax.lax.axis_size(inter_axis)
    pad = (-x.shape[0]) % n_intra
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x
    shard = jax.lax.psum_scatter(xp, intra_axis, scatter_dimension=0,
                                 tiled=True)

    # residual is stored per-device over the SCATTERED shard
    g = shard.astype(jnp.float32) + residual
    # pods must agree on ONE scale BEFORE quantizing — otherwise the summed
    # int8 values have no common dequantization (a scalar pmax across pods
    # is the only extra traffic)
    scale = jnp.maximum(jnp.max(jnp.abs(g)) / 127.0, 1e-12)
    scale = jax.lax.pmax(scale, inter_axis)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    n_inter = jax.lax.axis_size(inter_axis)
    if n_inter == 2:
        # pairwise exchange: the wire carries TRUE int8 payloads (psum
        # would upcast before transfer); sum locally after the swap
        other = jax.lax.ppermute(q, inter_axis, perm=[(0, 1), (1, 0)])
        q32 = q.astype(jnp.int32) + other.astype(jnp.int32)
    else:
        # ≥3 pods: int16 wire (sum of P int8 fits while P ≤ 128) — still
        # 2× under f32; a byte-packed ring AR would need per-hop requant
        q32 = jax.lax.psum(q.astype(jnp.int16), inter_axis).astype(jnp.int32)
    deq = q32.astype(jnp.float32) * scale
    new_residual = g - (q.astype(jnp.float32) * scale)

    full = jax.lax.all_gather(deq.astype(x.dtype), intra_axis, axis=0,
                              tiled=True)
    return (full[: x.shape[0]] if pad else full), new_residual


def make_hierarchical_grad_reducer(mesh: Mesh, *, compress: bool = False):
    """shard_map-wrapped reducer for a gradient pytree laid out with batch
    over ("pod","data").  Used by the multi-pod training path when SPMD's
    flat all-reduce schedule is the bottleneck."""
    if "pod" not in mesh.axis_names:
        raise ValueError("hierarchical reduction needs a 'pod' axis")

    def reduce_tree(grads):
        def one(g):
            flat = g.reshape(-1)
            out = hierarchical_psum(flat, intra_axis="data",
                                    inter_axis="pod")
            return out.reshape(g.shape)

        return jax.tree.map(one, grads)

    in_specs = P(("pod", "data"))
    return jax.shard_map(reduce_tree, mesh=mesh,
                         in_specs=in_specs, out_specs=in_specs,
                         check_vma=False)
