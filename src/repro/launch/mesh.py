"""Production mesh construction.

NOTE: this module must never touch jax device state at import time — the
mesh is built inside a function so tests/benches keep their 1-device world
and only dryrun.py (which sets XLA_FLAGS first) sees 512 host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic-scaling entry point: any (shape, axes) the launcher asks for.

    Axes are ``Auto``: the model stack places activations with
    ``with_sharding_constraint``, which refers only to Auto axes
    (``jax.make_mesh`` defaults to Explicit).
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
