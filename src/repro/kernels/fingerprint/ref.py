"""Pure-jnp oracle for the tensor fingerprint.

The digest must be *bit-exactly* reproducible across the kernel and this
reference (uint32 wraparound arithmetic only — no floats), because it is
used by the catalog as a content address for device-resident tensors
(DESIGN.md §6: the TPU-native replacement for hashing Parquet files on S3).

Digest: 8 uint32 lanes.  Each 32-bit word w at global position p contributes
    mix(w XOR rot(GOLDEN * (p+1)))
to lane p % 8, where mix is an xxhash-style avalanche; contributions combine
by wrapping addition (commutative ⇒ chunk-parallel kernel is exact).
Finally the total word count is mixed into every lane.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

LANES = 8
GOLDEN = np.uint32(0x9E3779B1)
MULT1 = np.uint32(0x85EBCA6B)
MULT2 = np.uint32(0xC2B2AE35)


def _to_words(arr: jnp.ndarray) -> jnp.ndarray:
    """Flatten any-dtype array to uint32 words (little-endian packing)."""
    flat = arr.reshape(-1)
    nbits = flat.dtype.itemsize * 8
    if flat.dtype == jnp.bool_:
        flat = flat.astype(jnp.uint8)
        nbits = 8
    uint = jnp.dtype(f"uint{nbits}")
    if flat.dtype.kind != "u":
        flat = jax.lax.bitcast_convert_type(flat, uint)
    if nbits < 32:
        # strided slices, not a (-1, per) reshape: a trailing dim of 2 or 4
        # pads to a full 128-lane tile on TPU (64x the bytes for bf16)
        per = 32 // nbits
        pad = (-flat.shape[0]) % per
        flat = jnp.pad(flat, (0, pad))
        w = flat[0::per].astype(jnp.uint32)
        for j in range(1, per):
            w = w | (flat[j::per].astype(jnp.uint32) << np.uint32(j * nbits))
        return w
    if nbits == 64:
        lo = flat.astype(jnp.uint32)
        hi = (flat >> np.uint64(32)).astype(jnp.uint32)
        return jnp.stack([lo, hi], axis=1).reshape(-1)
    return flat.astype(jnp.uint32)


def mix_words(words: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Per-word avalanche used by both ref and kernel. uint32 in/out."""
    h = words ^ (GOLDEN * (positions + np.uint32(1)))
    h = h * MULT1
    h = h ^ (h >> np.uint32(13))
    h = h * MULT2
    h = h ^ (h >> np.uint32(16))
    return h


def fingerprint_ref(arr: jnp.ndarray) -> jnp.ndarray:
    """(8,) uint32 digest of an arbitrary array."""
    words = _to_words(arr)
    n = words.shape[0]
    pad = (-n) % LANES
    words = jnp.pad(words, (0, pad))
    pos = jnp.arange(words.shape[0], dtype=jnp.uint32)
    # padded words contribute mix(0, p) — deterministic, length-mixed below
    contrib = mix_words(words, pos)
    lanes = jnp.sum(contrib.reshape(-1, LANES), axis=0, dtype=jnp.uint32)
    n_mix = mix_words(jnp.full((LANES,), np.uint32(n)),
                      jnp.arange(LANES, dtype=jnp.uint32))
    return (lanes + n_mix).astype(jnp.uint32)


def digest_hex(digest: jnp.ndarray) -> str:
    return "".join(f"{int(x):08x}" for x in np.asarray(digest))
