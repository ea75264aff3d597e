"""Blockwise online-softmax (flash) attention — Pallas TPU kernel.

TPU adaptation of the flash-attention idea (DESIGN.md §6): instead of CUDA
shared-memory tiles and warp shuffles, blocks are BlockSpec-mapped VMEM tiles
sized for the MXU (128-multiples); the kv loop is a ``fori_loop`` whose trip
count is bounded per q-block so causal/windowed kernels skip fully-masked kv
blocks (the same work-skipping the CUDA kernel gets from early exit).

Grid: (batch, q_head, S // block_q).  GQA is handled in the index map — the
kv BlockSpec maps q-head h to kv-head h // group_size, so grouped K/V tiles
are fetched without materializing repeated heads in HBM.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.3819763e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                 block_q: int, block_k: int, kv_len: int, q_len: int,
                 causal: bool, window: Optional[int],
                 softcap: Optional[float]):
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale           # (bq, d)
    d = q.shape[-1]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q) + (kv_len - q_len)

    n_kv_blocks = pl.cdiv(kv_len, block_k)
    if causal:
        # highest kv block a query in this q-block can see
        hi = jax.lax.div(qi * block_q + block_q - 1 + (kv_len - q_len),
                         block_k) + 1
        hi = jnp.minimum(hi, n_kv_blocks)
    else:
        hi = n_kv_blocks
    if window is not None:
        lo = jax.lax.max(
            0, jax.lax.div(qi * block_q + (kv_len - q_len) - (window - 1),
                           block_k))
    else:
        lo = 0

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        kv_pos = j * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = kv_pos[None, :] < kv_len  # tail padding
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= (q_pos[:, None] - kv_pos[None, :]) < window
        logits = jnp.where(mask, logits, NEG_INF)

        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out.astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, Hq, S, d)
    k: jnp.ndarray,  # (B, Hkv, T, d)
    v: jnp.ndarray,  # (B, Hkv, T, d)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, S, d = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    groups = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, T)

    orig_S = S
    if S % block_q:
        pad = block_q - S % block_q
        q = jnp.pad(q, ((0, 0), (0, 0), (pad, 0), (0, 0)))  # left-pad queries
        S = S + pad
    # kv tail padding handled by the in-kernel kv_pos < kv_len mask
    if T % block_k:
        padk = block_k - T % block_k
        k = jnp.pad(k, ((0, 0), (0, 0), (0, padk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, padk), (0, 0)))

    grid = (B, Hq, S // block_q)
    # NB: q_len is the PADDED length — with left-padded queries, row r maps to
    # absolute position r + (T - S_padded), which keeps the last real query
    # aligned to the last kv position; padded rows land at negative positions
    # and are fully masked (their l==0 is guarded in the kernel).
    kernel = functools.partial(
        _attn_kernel, scale=d ** -0.5, block_q=block_q, block_k=block_k,
        kv_len=T, q_len=S, causal=causal, window=window,
        softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, k.shape[2], d),
                         lambda b, h, i, g=groups: (b, h // g, 0, 0)),
            pl.BlockSpec((None, None, v.shape[2], d),
                         lambda b, h, i, g=groups: (b, h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, S - orig_S:, :]
