"""Peak rates of each accelerator, keyed by ``device_kind`` as JAX names it
(``harness.Window.device_kind``), for the shares of a roofline or a peak
that the metric readers compute.

``bf16_flops``: dense bfloat16 matmul operations a second, per chip;
``hbm_bytes_per_s``: HBM bandwidth a second, per chip; ``hbm_bytes``:
HBM capacity per chip. Sources: the vendor's published specifications.
"""
from __future__ import annotations

PEAKS = {
    # Cloud TPU v5e: 197 TFLOP/s bf16, 819 GB/s, 16 GiB HBM2
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30},
}


def peak(device_kind: str | None) -> dict | None:
    """The device's peaks, or None for a device the table does not hold."""
    return PEAKS.get(device_kind)


def roofline_seconds(flops: float, nbytes: float, p: dict) -> float:
    """The least time the device can take for ``flops`` and ``nbytes``:
    the larger of the compute time and the memory time at its peaks."""
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
