"""Mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
real launches get the mesh from the actual TPU topology.

Every mesh here has ``Auto`` axes: the sharding rules in
``distributed/sharding.py`` place arrays with ``NamedSharding`` and
``with_sharding_constraint``, which ``Explicit`` axes (what ``jax.make_mesh``
makes by default) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(data: int, model: int, pods: int = 1, devices=None):
    """Arbitrary mesh for tests / small runs (e.g. (2, 4) on 8 host devices)."""
    if pods > 1:
        return auto_mesh((pods, data, model), ("pod", "data", "model"),
                         devices)
    return auto_mesh((data, model), ("data", "model"), devices)


def single_device_mesh():
    return make_mesh(1, 1)
