"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first backend init, so the
dry-run must set XLA_FLAGS before anything else — see dryrun.py).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (virtual) devices exist — tests/examples."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _make_mesh((data, model), ("data", "model"))


# TPU v5e-class hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
PEAK_FLOPS_INT8 = 394e12        # per chip (2x MXU throughput for int8)
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
HBM_PER_CHIP = 16 * 2**30       # bytes
