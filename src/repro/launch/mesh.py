"""Production meshes (TPU v5e target) and the serving session mesh.

A function, not a module-level constant — importing this module must never
touch jax device state.

Two mesh families live here. The training meshes (`make_production_mesh`,
`make_local_mesh`) are 2-D/3-D ("data", "model") grids for the student
archs in `launch.shardings`. The serving mesh (`make_session_mesh`) is
1-D over a "session" axis: fused grant lifecycles (`core.batched`) stack
co-resident sessions on the leading axis, and sharding *that* axis across
an N-device host-platform mesh (`launch.host_mesh`, forced with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) is what turns the
GPU pool's modeled per-device clocks into real parallel launches."""
from __future__ import annotations

from typing import NamedTuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh for CPU tests (degenerate axes)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def make_session_mesh(n: int | None = None):
    """1-D mesh over the fused-serving "session" axis.

    ``n`` defaults to every live device (forced host devices included);
    pass an explicit count to pin the pool width. See `launch.host_mesh`
    for the env plumbing that makes n > 1 real on a CPU host."""
    if n is None:
        n = len(jax.devices())
    if n < 1 or n > len(jax.devices()):
        raise ValueError(
            f"session mesh wants {n} devices, have {len(jax.devices())}")
    return jax.make_mesh((n,), ("session",))


class ChipPeaks(NamedTuple):
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float  # FLOP/s
    hbm_bytes: float   # bytes of HBM
    hbm_bw: float      # HBM bytes/s
    ici_bw: float      # chip-to-chip bytes/s per link


# Keyed by `jax.Device.device_kind`. TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
# interchip interconnect over 4 links (50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bytes=16e9,
                             hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a kind missing from `CHIP_PEAKS` is an
    error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}") from None
