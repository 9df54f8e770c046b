"""The accelerator a run measures, and its published peaks.

Peaks are keyed by JAX's ``device_kind``; a device that is not in the
table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, too few chips, or a chip with no known peaks."""


def require(jax, chips):
    """The first ``chips`` TPU devices and their peaks, or
    :class:`NoAccelerator`. Never falls back to the CPU."""
    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform if devices else None!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s), JAX sees "
                            f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in PEAKS:
        raise NoAccelerator(f"no published peaks for device kind {kind!r}")
    return devices[:chips], PEAKS[kind]


def scan_peak(peaks, precision):
    """Peak operations per second for the scan at a storage type."""
    return peaks["int8_ops"] if precision == "int8" else peaks["bf16_flops"]
