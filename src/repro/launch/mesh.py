"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; only
``launch/dryrun.py`` forces the 512-host-device environment.

Target hardware (roofline constants used in benchmarks/roofline.py):
TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI;
single pod = 16 x 16 = 256 chips; multi-pod = 2 pods = 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Degenerate 1x1 mesh on the real local device (CPU tests/examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# hardware constants (TPU v5e)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
CHIPS_SINGLE_POD = 256
CHIPS_MULTI_POD = 512
