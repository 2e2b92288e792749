"""Production meshes.

Single pod: (16, 16) = ("data", "model") — 256 chips (TPU v5e pod).
Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips.

The 'model' axis carries the paper's layer-parallel (MGRIT chunk) dimension
during training and Megatron TP during serving; 'data'(+'pod') carry batch,
FSDP storage sharding and expert parallelism (DESIGN.md §4).

Functions, not module constants: importing this module must never touch
jax device state. Every mesh in the repository is built by :func:`make_mesh`.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` over the first ``prod(shape)`` devices, with every
    axis ``AxisType.Auto``.

    The model code shards by ``with_sharding_constraint`` and lets GSPMD
    propagate the rest (``parallel/sharding.py``). ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which plain ops such as the
    embedding gather refuse sharded operands, so no mesh is built
    without this helper."""
    n = int(np.prod(shape))
    return jax.make_mesh(tuple(shape), tuple(axes),
                         devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(jax.devices())}. "
            "The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax (launch/dryrun.py).")
    return make_mesh(shape, axes)
