"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (required so tests/benches see 1 CPU device while only
dryrun.py forces 512 host devices).
"""
from __future__ import annotations

import jax

from repro.models.transformer import ShardCtx


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many devices exist (tests / single host)."""
    return _make_mesh(shape, axes)


def make_ctx(mesh) -> ShardCtx:
    axes = mesh.axis_names
    data_axes = tuple(a for a in axes if a in ("pod", "data"))
    return ShardCtx(mesh=mesh, data_axes=data_axes, model_axis="model")
