"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
JAX device state. The dry-run entry point (launch/dryrun.py) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax.
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """jax.make_mesh with Auto axis types everywhere: the steps rely on
    shard_map/jit sharding inference, not Explicit sharding."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """A small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link (~per-direction)
    "hbm_bytes": 16e9,  # v5e HBM capacity
}
