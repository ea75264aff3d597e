"""Production mesh construction.

A FUNCTION (never a module-level constant) so importing this module touches
no jax device state — the dry-run sets XLA_FLAGS before first jax init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes: the sharding policy pins layouts
    with ``with_sharding_constraint``, which only Auto axes accept."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds the 'pod' axis (2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(*, model: int = 1):
    """Whatever devices exist now (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return auto_mesh((n // model, model), ("data", "model"))


def mesh_cells(mesh) -> int:
    return int(mesh.devices.size)
