"""The device as JAX reports it, its memory peak, and the table of peaks."""

from __future__ import annotations

from typing import List, Optional

# Published peaks per chip, keyed by `device_kind` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture page:
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip, and
# an interchip (ICI) bandwidth of 1,600 Gbps per chip, 200 GB/s.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9, "ici_bytes_per_s": 200e9},
}


class DeviceError(Exception):
    pass


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise DeviceError(f"no peaks known for device kind {kind!r} "
                          f"(the table has: {sorted(PEAKS)})")
    return PEAKS[kind]


def require(chips: int, allow_cpu: bool):
    """This process's devices, opened here. Fails unless they are TPUs and
    at least ``chips`` of them; never falls back to another backend."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise DeviceError(f"jax.devices()[0].platform is {platform!r}, not 'tpu'")
    if len(devices) < chips:
        raise DeviceError(f"the cell asks for {chips} chips, JAX finds "
                          f"{len(devices)} {platform} device(s)")
    return devices


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats()  # None where the backend reports nothing
    return stats.get("peak_bytes_in_use") if stats else None


def peak_bytes_per_device(devices) -> List[Optional[int]]:
    return [peak_bytes(d) for d in devices]


def describe(devices, rehearsal: bool) -> dict:
    peaks_seen = [p for p in peak_bytes_per_device(devices) if p is not None]
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": max(peaks_seen) if peaks_seen else 0}
    if rehearsal:
        out["rehearsal_not_a_measurement"] = True
    return out
