"""Device meshes: the one place a ``jax.sharding.Mesh`` is built.

Every axis is an ``Auto`` axis.  Since jax 0.7 ``jax.make_mesh`` makes
``Explicit`` axes by default, and ``with_sharding_constraint`` (which
``ShardCtx.shard`` lowers to) refuses those, so a mesh built any other way
breaks the model code's logical-axis annotations.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is pure
data parallelism over DCN.

Defined as functions so importing this module never touches jax device
state (device count locks on first backend init).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.sharding.specs import ShardCtx


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of ``shape`` over ``axes`` with Auto axis types.

    ``devices=None`` lets JAX pick (and order) the visible devices;
    otherwise ``devices`` is laid out row-major over ``shape`` as given —
    repeats are allowed, which the sharding-rule tests use to describe a
    production mesh on one CPU device."""
    shape, axes = tuple(shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    return Mesh(np.asarray(devices, dtype=object).reshape(shape), axes,
                axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_ctx(mesh, *, seq_shard: bool = False) -> ShardCtx:
    names = mesh.axis_names
    batch = tuple(n for n in names if n in ("pod", "data"))
    model = "model" if "model" in names else None
    return ShardCtx(
        mesh=mesh, batch_axes=batch, model_axis=model, seq_shard=seq_shard
    )


def make_debug_mesh(data: int = 1, model: int = 1,
                    devices: Optional[Sequence] = None) -> Mesh:
    """A small ``(data, model)`` mesh over the local devices, or over
    ``devices`` (one replica's share of a host)."""
    return make_mesh((data, model), ("data", "model"), devices)
