"""Production mesh definitions.

Kept as FUNCTIONS so importing this module never touches jax device state —
the dry-run sets ``xla_force_host_platform_device_count`` before first jax
init, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips).

    The ``pod`` axis is data-parallel across DCN; ``data`` is in-pod DP;
    ``model`` is the TP/EP axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic rescale)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e single-chip hardware constants (the dry-run checks hbm_bytes).
HW = {
    "peak_bf16_flops": 197e12,   # FLOP/s per chip
    "hbm_bandwidth": 819e9,      # B/s per chip
    "ici_bandwidth": 50e9,       # B/s per link (~per direction)
    "hbm_bytes": 16 * 1024**3,   # HBM capacity per chip
    "dcn_bandwidth": 6.25e9,     # B/s per host cross-pod (50 Gb/s)
}
