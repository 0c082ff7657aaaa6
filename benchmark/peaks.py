"""Published per-chip peaks, keyed by jax's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  A device that is not in the table is an error,
not a default: a share of a peak nobody published is not a number.
(Copied from ``bench.DEVICE_PEAKS``; the original is ROADMAP D1's to delete.)
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197.0e12,
        "hbm_bytes_per_s": 819.0e9,
        "hbm_bytes": 16 * 1024**3,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e: 197 TFLOP/s bf16, 819 GB/s, 16 GB)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peak for device_kind {device_kind!r}: a benchmark PR "
            "adds it to benchmark/peaks.py with its source") from None
