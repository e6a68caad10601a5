"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so that
importing this module touches no jax device state. The dry-run entry point
(dryrun.py) sets ``--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real single CPU device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def _auto_axes(n_axes: int) -> tuple:
    """Every mesh axis in Auto mode (the sharding rules are GSPMD-style)."""
    import jax

    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} — "
            "run under dryrun.py (sets xla_force_host_platform_device_count)")
    if len(devices) == n:
        return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))
    # build on a prefix of the device list (e.g. single-pod mesh in a
    # 512-device dry-run process)
    from jax.sharding import Mesh

    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, axes, axis_types=_auto_axes(len(axes)))


def make_test_mesh(shape: Tuple[int, ...] = (2, 2), axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for integration tests (requires forced host devices)."""
    import jax

    return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))


def hardware_constants() -> dict:
    """TPU v5e target constants for the roofline terms."""
    return {
        "peak_flops": 197e12,  # bf16 / chip
        "hbm_gbps": 819e9,  # bytes/s per chip
        "ici_gbps": 50e9,  # bytes/s per link
        "hbm_gib": 16.0,
    }
