"""Production mesh builders.

v5e pod = 16x16 (256 chips); multi-pod = 2 pods = 512 chips with a leading
``pod`` axis (cross-pod collectives traverse DCN).  Functions, not module
constants: importing this module never touches jax device state.

Every axis is ``Auto``: the models place activations with
``with_sharding_constraint`` (``sharding/rules.py``), which accepts only
Auto axes, and ``jax.make_mesh`` makes Explicit axes by default.
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


def make_host_mesh():
    """Single-host debug mesh (1x1) with the same axis names."""
    return _auto_mesh((1, 1), ("data", "model"))
