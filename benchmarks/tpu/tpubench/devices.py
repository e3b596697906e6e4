"""The chips a cell runs on, and their published peaks."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": per chip, 197 TFLOP/s bf16 and
# 819 GB/s of HBM bandwidth.  Keyed by jax's ``device_kind``.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class DeviceError(RuntimeError):
    pass


def cell_devices(devices, chips: int):
    """The first ``chips`` devices, refusing anything that is not a TPU
    with a known peak, or too few chips."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "none"
        raise DeviceError(f"needs a TPU; JAX found {found!r} "
                          f"({len(devices)} device(s))")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in PEAKS:
        raise DeviceError(f"device kind {kind!r} has no entry in the peak table")
    return list(devices[:chips])


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))
