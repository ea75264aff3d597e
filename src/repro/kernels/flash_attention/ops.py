"""Jitted public wrapper for the flash attention kernel.

``flash_gqa`` takes model-layout tensors (B, S, H, d) and handles GQA +
layout transposition; gradient support comes from a recompute-based
``jax.custom_vjp`` (forward kernel + reference backward), the standard
memory-saving pattern for attention backward on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import interpret_mode
from . import ref
from .kernel import flash_attention


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_gqa(q, k, v, causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None):
    """q: (B, S, Hq, d); k/v: (B, T, Hkv, d) → (B, S, Hq, d).

    The kernel compiles on a TPU and runs interpreted elsewhere."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          softcap=softcap, interpret=interpret_mode())
    return jnp.swapaxes(out, 1, 2)


def _fwd(q, k, v, causal, window, softcap):
    out = flash_gqa(q, k, v, causal, window, softcap)
    return out, (q, k, v)


def _bwd(causal, window, softcap, res, g):
    """Recompute-based backward via the reference implementation — the
    canonical flash-bwd trade (no O(S·T) tensor is saved from the fwd)."""
    q, k, v = res

    def f(q_, k_, v_):
        qt = jnp.swapaxes(q_, 1, 2)
        kt = jnp.swapaxes(k_, 1, 2)
        vt = jnp.swapaxes(v_, 1, 2)
        groups = qt.shape[1] // kt.shape[1]
        kr = jnp.repeat(kt, groups, axis=1)
        vr = jnp.repeat(vt, groups, axis=1)
        out = ref.mha_reference(qt, kr, vr, causal=causal, window=window,
                                softcap=softcap)
        return jnp.swapaxes(out, 1, 2)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_gqa.defvjp(_fwd, _bwd)
