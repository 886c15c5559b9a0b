"""Published peaks of one chip, keyed by jax's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" system architecture page: one
v5e chip has 197 TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s and 1,600 Gbit/s
of chip-to-chip interconnect. A kind that is not listed is an error, never a
default: a roofline share against a guessed peak is not a measurement.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no published peak for device_kind {device_kind!r}; "
            f"add it to benchmarks/harness/peaks.py with its source") from None
