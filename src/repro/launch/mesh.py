"""Production mesh topology.

Single pod: (data=16, model=16) = 256 v5e chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
data-parallel across the DCN/ICI boundary (gradient all-reduce crosses it
once per step; everything latency-sensitive stays intra-pod).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization, while tests/benches must keep seeing the real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, **kwargs):
    """``jax.make_mesh`` with Auto axes: shardings inside the model are
    constraints the compiler propagates (``with_sharding_constraint``),
    not the Explicit-axis types ``make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes), **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int = None, model: int = 2):
    """Small CPU mesh for tests: (data = n/model, model)."""
    n = n_devices or len(jax.devices())
    return auto_mesh((n // model, model), ("data", "model"))
