"""Production mesh construction.

A function (not a module-level constant) so importing this module never touches
jax device state.  The dry-run sets XLA_FLAGS host-device-count *before* any jax
import; everything else sees the real device count.

Every mesh axis is ``Auto``: the models place activations with
``with_sharding_constraint`` (``models/layers.py::shard_hint``), which only
names Auto axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a (data, model) mesh with model=1."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
