"""Production mesh construction.

Functions, not module-level constants, so importing this module never
touches jax device state.  Device discovery is shared with
``repro.core.shard``: set ``REPRO_MESH_DEVICES=N`` (the one supported
env-var path, see docs/perf.md) and import repro before first jax use —
on CPU hosts the host platform is force-split into N devices
automatically; callers never craft ``XLA_FLAGS`` by hand.  (The old
dry-run path that exported ``--xla_force_host_platform_device_count``
manually still works but is subsumed by the env var.)
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes, devices=None):
    types = (AxisType.Auto,) * len(axes)
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(shape), axes,
                    axis_types=types)
    return jax.make_mesh(shape, axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (TPU v5e-256); 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_spec(data: int, model: int, pod: int = 1):
    """Arbitrary mesh for DSE / hillclimbing (device count permitting)."""
    if pod > 1:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def make_host_mesh(ndevices: int | None = None):
    """Whatever the current host offers (tests: 1 CPU device).

    Reuses :class:`repro.core.shard.EvalMesh` device discovery, so the
    resolution order is: explicit ``ndevices``, then
    ``REPRO_MESH_DEVICES``, then every visible device (requests beyond
    the visible count clamp)."""
    from ..core.shard import EvalMesh
    em = EvalMesh(ndevices=ndevices)
    return _mesh((em.ndevices, 1), ("data", "model"), devices=em.devices)
