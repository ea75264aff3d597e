"""The checkpoint digest, computed apart from the program, in numpy.

A leaf's words are its bytes read as little-endian uint32 (two bfloat16 to
a word, the first in the low half).  Word ``p`` adds
``mix(w ^ GOLDEN * (p + 1))`` to lane ``p % 8`` with wrapping addition,
after padding to a multiple of 8 with zero words; the word count, mixed at
positions 0..7, is added to the lanes.  A tree's digest is that of its
leaves' digests, concatenated in the order of their key paths.
"""

from __future__ import annotations

import numpy as np

LANES = 8
GOLDEN = np.uint32(0x9E3779B1)
MULT1 = np.uint32(0x85EBCA6B)
MULT2 = np.uint32(0xC2B2AE35)
CHUNK = 1 << 24  # words per pass


def words(arr: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(arr).reshape(-1)
    if a.dtype == np.bool_:
        a = a.astype(np.uint8)
    b = a.view(np.uint8)
    size = a.dtype.itemsize
    if size >= 4:
        return b.view("<u4")
    per = 4 // size
    pad = (-a.shape[0]) % per
    b = np.concatenate([b, np.zeros(pad * size, np.uint8)])
    return b.view("<u4")


def mix(w: np.ndarray, pos: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = w ^ (GOLDEN * (pos + np.uint32(1)))
        h = h * MULT1
        h ^= h >> np.uint32(13)
        h = h * MULT2
        h ^= h >> np.uint32(16)
    return h


def fingerprint(arr: np.ndarray) -> np.ndarray:
    """(8,) uint32 digest of one array."""
    w = words(arr)
    n = w.shape[0]
    total = n + (-n) % LANES
    lanes = np.zeros(LANES, np.uint64)
    for lo in range(0, total, CHUNK):
        hi = min(lo + CHUNK, total)
        chunk = np.zeros(hi - lo, np.uint32)
        real = w[lo:min(hi, n)]
        chunk[:real.shape[0]] = real
        h = mix(chunk, np.arange(lo, hi, dtype=np.uint32))
        lanes += h.reshape(-1, LANES).sum(axis=0, dtype=np.uint64)
    lanes = (lanes & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    n_mix = mix(np.full(LANES, np.uint32(n & 0xFFFFFFFF)),
                np.arange(LANES, dtype=np.uint32))
    with np.errstate(over="ignore"):
        return lanes + n_mix


def hex_of(d: np.ndarray) -> str:
    return "".join(f"{int(x):08x}" for x in d)


def tree_digest_hex(tree) -> str:
    import jax

    leaves = jax.tree_util.tree_leaves_with_path(tree)
    parts = [fingerprint(np.asarray(leaf)) for _, leaf in
             sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0]))]
    return hex_of(fingerprint(np.concatenate(parts).astype(np.uint32)))
