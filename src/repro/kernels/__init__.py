"""repro.kernels — Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships as <name>/kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), <name>/ops.py (jitted public wrapper) and <name>/ref.py (pure-jnp
oracle); tests sweep shapes/dtypes in interpret mode against the oracle.
The public wrappers take the mode from the platform (``interpret_mode``):
compiled on a TPU, interpreted anywhere else.
"""

import jax


def interpret_mode() -> bool:
    """True where Pallas kernels cannot compile: any backend but a TPU."""
    return jax.default_backend() != "tpu"
