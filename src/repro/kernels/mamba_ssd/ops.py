"""Jitted wrapper for the chunked SSD kernel (pads ragged sequence tails)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import ssd_chunked


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A, Bmat, Cmat, *, chunk: int = 128, h0=None):
    """Chunk-parallel SSD with identity-step padding for ragged tails.
    x: (B,S,nh,hd); dt: (B,S,nh); A: (nh,); B/C: (B,S,ns)."""
    S = x.shape[1]
    S_pad = ((S + chunk - 1) // chunk) * chunk
    if S_pad != S:
        pad = S_pad - S
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt=0 ⇒ identity step
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
    y, h = ssd_chunked(x, dt, A, Bmat, Cmat, chunk=chunk, h0=h0,
                       interpret=interpret_mode())
    return y[:, :S], h
