"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — jax locks the device count on first backend init,
and only dryrun.py (which sets XLA_FLAGS first) may ask for 512 host devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Slices jax.devices() to the mesh size so the single-pod mesh builds even
    when dryrun.py forced 512 placeholder devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            f"run via launch/dryrun.py (it forces 512 host devices)")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices[:n])


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
