"""Production meshes. Functions, not module constants, so importing this
module never touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """Mesh whose axes are all ``Auto``: the partition rules place inputs
    and state, and XLA propagates the rest (``jax.make_mesh`` would make
    them ``Explicit``, which types every intermediate's sharding)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (possibly forced-host) devices exist."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants for the roofline model (targets, not runtime).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (one direction)
