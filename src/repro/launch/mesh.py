"""Production mesh construction (TPU v5e pods; CPU host devices in dry-run).

A pod is a 16×16 slice (256 chips); the multi-pod mesh prepends a ``pod`` axis
(2 pods = 512 chips). Importing this module never touches jax device state —
meshes are built lazily by the functions.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

# Per-chip peak rates keyed by ``jax.devices()[i].device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
# HBM, 1,600 Gbit/s inter-chip interconnect over 4 links = 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# The chip the production mesh and the dry run are modelled on.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates recorded for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with auto (GSPMD-propagated) axes on every axis."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape, axes):
    """Device mesh of ``shape`` over ``axes``.

    When ``prod(shape)`` is smaller than the device count (e.g. a 2-device
    mesh on the forced-8-virtual-device CPU test lane), the mesh is built
    over the first ``prod(shape)`` devices; a full-size mesh goes through
    ``jax.make_mesh`` so jax picks a performant device order.
    """
    n = int(np.prod(shape))
    devs = jax.devices()
    if n == len(devs):
        return _auto_mesh(shape, axes)
    if n > len(devs):
        raise ValueError(f"mesh {tuple(shape)} needs {n} devices, "
                         f"have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n]).reshape(shape), tuple(axes))


def make_host_mesh(n: Optional[int] = None, axis: str = "data"):
    """A small single-axis mesh over available (host) devices — tests/demos."""
    devs = jax.devices()
    n = n or len(devs)
    return jax.sharding.Mesh(np.array(devs[:n]), (axis,))
