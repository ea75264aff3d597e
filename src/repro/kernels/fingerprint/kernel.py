"""Tensor fingerprint — Pallas TPU kernel.

Computes the (8,) uint32 content digest of an array.  The words are laid out
as ``(rows, 128)`` int32 tiles (one vreg lane width per row), so word ``p``
sits in column ``p % 128`` and feeds digest lane ``p % 8``.  Each grid cell
mixes one block of rows and folds it into an ``(8, 128)`` accumulator that
stays resident across the sequential grid; the 128 → 8 lane fold and the
length mixing run outside the kernel.

All arithmetic is int32 with wrapping multiplies and logical shifts: the low
32 bits are those of the uint32 reference (``ref.fingerprint_ref``) bit for
bit, and the TPU compiler supports int32 reductions where it refuses
unsigned ones.  Wrapping addition is commutative and associative, so the
block size never changes the digest.

This makes catalog commits of device-resident tensors (params, activations)
possible without copying bytes to the host: the digest IS the content
address (see ``repro.checkpoint``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .ref import GOLDEN, LANES, MULT1, MULT2, _to_words, mix_words

ROW = 128     # words per tile row (TPU lane width)
SUBLANES = 8  # rows per int32 vreg tile


def _i32(c: np.uint32) -> np.int32:
    return np.uint32(c).view(np.int32)


def _fp_kernel(w_ref, o_ref, *, block_rows: int, n_words: int):
    i = pl.program_id(0)
    words = w_ref[...]                                   # (block_rows, 128)
    row = jax.lax.broadcasted_iota(jnp.int32, words.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
    pos = (i * block_rows + row) * ROW + col             # global word index
    h = words ^ (_i32(GOLDEN) * (pos + 1))
    h = h * _i32(MULT1)
    h = h ^ jax.lax.shift_right_logical(h, jnp.int32(13))
    h = h * _i32(MULT2)
    h = h ^ jax.lax.shift_right_logical(h, jnp.int32(16))
    h = jnp.where(pos < n_words, h, 0)                   # block tail padding
    part = jnp.sum(h.reshape(block_rows // SUBLANES, SUBLANES, ROW), axis=0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = part

    @pl.when(i != 0)
    def _acc():
        o_ref[...] = o_ref[...] + part


def fingerprint(arr: jnp.ndarray, *, block_rows: int = 1024,
                interpret: bool = False) -> jnp.ndarray:
    """(8,) uint32 digest — bit-identical to ``ref.fingerprint_ref``."""
    words = _to_words(arr)
    n = words.shape[0]
    # the reference pads to a LANES multiple with zero words, which it mixes
    # in; those words are zero in the padded tiles too, so mixing up to this
    # length (and masking the rest of the last block) gives the same sum
    n_mixed = n + (-n) % LANES
    if n_mixed >= 2 ** 31:
        raise ValueError(f"fingerprint: {n} words exceed int32 positions")
    rows = -(-n // ROW)
    block_rows = min(block_rows, -(-rows // SUBLANES) * SUBLANES)
    total_rows = -(-rows // block_rows) * block_rows
    tiles = jax.lax.bitcast_convert_type(
        jnp.pad(words, (0, total_rows * ROW - n)), jnp.int32
    ).reshape(total_rows, ROW)
    acc = pl.pallas_call(
        functools.partial(_fp_kernel, block_rows=block_rows,
                          n_words=n_mixed),
        grid=(total_rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, ROW), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, ROW), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, ROW), jnp.int32),
        interpret=interpret,
    )(tiles)
    # column c holds lane c % LANES: fold 128 columns onto 8 lanes
    lanes = jnp.sum(acc, axis=0).reshape(ROW // LANES, LANES).sum(axis=0)
    lanes = jax.lax.bitcast_convert_type(lanes, jnp.uint32)
    n_mix = mix_words(jnp.full((LANES,), np.uint32(n)),
                      jnp.arange(LANES, dtype=jnp.uint32))
    return lanes + n_mix
