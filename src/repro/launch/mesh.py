"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax use,
while smoke tests and benchmarks must keep seeing 1 device.

Topology: a TPU v5e pod is modelled as a 16x16 = 256-chip 2D slice with
(data, model) axes; the multi-pod mesh adds a leading 'pod' axis over DCN.
``pods`` generalises beyond 2 — nothing is hard-coded to the dry-run size.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules hand
    specs to ``with_sharding_constraint``, which ``Explicit`` axes (the
    ``make_mesh`` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pods: int = 2):
    shape = (pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pods: int = 0):
    """Small mesh over host devices for tests (requires host-device flag)."""
    if pods:
        return auto_mesh((pods, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))


# Hardware constants for roofline terms (TPU v5e):
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per chip per direction)
HBM_PER_CHIP = 16e9           # bytes
