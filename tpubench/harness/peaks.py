"""The one table of peaks, keyed by ``device_kind`` as JAX reports it.

An unknown device is an error, never a default: a share of a peak that
was not looked up means nothing.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
    # is the int8 figure), 16 GB of HBM at 819 GB/s, per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r}; add it to "
            f"tpubench/harness/peaks.py with its source") from None


def roofline_seconds(flops: float, bytes_: float, peaks: dict):
    """(least seconds the chip could take, which bound sets it)."""
    t_c = flops / peaks["bf16_flops"]
    t_m = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
