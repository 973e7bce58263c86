"""Production meshes.

``make_production_mesh`` is the mandated (pod,) data x model mesh.  BaPipe's
pipeline lives on the *model* axis, so ``make_pipeline_mesh`` reshapes the
same device set into (pod,) data x stage x tensor with
``stages * tensor == 16`` (per-arch factorisation from the config).

Functions, not module constants: importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes (the shard_map runtime's mesh
    type; jax defaults to Explicit).  ``devices`` defaults to the first
    ``prod(shape)`` of ``jax.devices()``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_pipeline_mesh(*, multi_pod: bool = False, stages: int = 16,
                       tensor: int = 1):
    """Same devices as the production mesh with the model axis split into
    (stage, tensor)."""
    assert stages * tensor == 16, (stages, tensor)
    if multi_pod:
        shape = (2, 16, stages, tensor)
        axes = ("pod", "data", "stage", "tensor")
    else:
        shape = (16, stages, tensor)
        axes = ("data", "stage", "tensor")
    return make_mesh(shape, axes)
