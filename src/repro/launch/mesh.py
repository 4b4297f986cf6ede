"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The single-pod production mesh is 16 x 16 = 256
chips ("data", "model"); the multi-pod mesh is 2 x 16 x 16 = 512 chips
("pod", "data", "model").  TPU v5e numbers are used for the roofline.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — roofline denominators
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Small mesh over the real local device(s) — tests and examples."""
    n = len(jax.devices())
    shape = (2, n // 2) if n >= 2 and n % 2 == 0 else (1, n)
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
