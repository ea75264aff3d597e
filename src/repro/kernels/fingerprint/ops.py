"""Public fingerprint API: digests for arrays and whole pytrees.

Used by the checkpoint layer to content-address device-resident tensors
(params, optimizer state) when committing to the catalog — the paper's
"immutable reference to data" without a device→host copy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import interpret_mode
from . import ref
from .kernel import fingerprint


@jax.jit
def _digest(arr: jnp.ndarray) -> jnp.ndarray:
    return fingerprint(arr, interpret=interpret_mode())


def tensor_digest(arr) -> jnp.ndarray:
    """(8,) uint32 digest of one array (kernel path).  The compiler cannot
    partition a Pallas kernel, so an array sharded over several devices is
    first gathered onto one of them."""
    arr = jnp.asarray(arr)
    devices = arr.sharding.device_set
    if len(devices) > 1:
        arr = jax.device_put(arr, min(devices, key=lambda d: d.id))
    return _digest(arr)


def tensor_digest_hex(arr) -> str:
    return ref.digest_hex(tensor_digest(arr))


def tree_digest_hex(tree) -> str:
    """Order-stable digest of a whole pytree: digest of leaf digests."""
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    parts = []
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        parts.append(np.asarray(tensor_digest(leaf)))
    stacked = jnp.asarray(np.concatenate(parts).astype(np.uint32))
    return ref.digest_hex(ref.fingerprint_ref(stacked))
