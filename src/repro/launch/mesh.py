"""Meshes and per-chip peaks.

Meshes are built by FUNCTIONS so importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any jax initialization).

Single pod:  (16, 16) = 256 chips, axes (data, model).
Multi-pod:   (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
is pure data parallelism across the DCI; model parallelism never crosses a
pod boundary (ICI-only), which is the production constraint this mesh
encodes.

Every mesh here has ``Auto`` axes: the training and sharding code writes
global math and lets the compiler place the collectives, which is what
``jax.make_mesh``'s default ``Explicit`` axes refuse to do.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks, for roofline bounds and projections."""

    bf16_flops: float  # FLOP/s
    hbm_bw: float  # B/s
    ici_bw: float  # B/s of chip-to-chip interconnect per chip
    source: str


# Keyed by ``jax.devices()[0].device_kind``.  A device that is not here has
# no peak: ask for it and get an error, never a default.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        hbm_bw=819e9,
        ici_bw=1600e9 / 8,  # 1,600 Gbit/s
        source='Google Cloud documentation, "TPU v5e" page',
    ),
}
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (implicit sharding)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests: usually 1)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))
