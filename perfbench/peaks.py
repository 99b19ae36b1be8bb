"""Peak rates of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind missing here is an error: a share of a
peak is never computed against a guessed peak.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(RuntimeError):
    """The device is not an accelerator this benchmark has peaks for."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peak rates for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def require_accelerator(devices, chips: int) -> dict:
    """Check that ``devices`` (``jax.devices()``) hold at least ``chips``
    TPU chips of a kind in the table; return that kind's peaks."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else "none"
        raise UnknownDevice(f"no TPU found (platform {platform!r}); the "
                            "benchmark measures only on the chip")
    if len(devices) < chips:
        raise UnknownDevice(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    return peaks_for(devices[0].device_kind)
