"""Production mesh construction.

A function (never a module-level constant) so importing this module never
touches jax device state.  Shapes: 16x16 = one v5e pod (256 chips);
2x16x16 = two pods (512 chips) with a leading "pod" axis mapped to the
DCN-connected dimension.

Every axis is `AxisType.Auto`: the sharding rules in `runtime/sharding.py`
give parameters and batches `NamedSharding`s and anchor activations with
`with_sharding_constraint`, leaving the rest to the compiler's propagation.
Enter a mesh with `jax.set_mesh(mesh)`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over locally-visible devices (tests / examples)."""
    return _auto_mesh((data, model), ("data", "model"))
