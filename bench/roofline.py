"""Peaks of the chips the benchmark runs on, and the least bytes an
aggregation round must move.

Peaks are keyed by ``device_kind`` as JAX reports it. A device that is
not in the table is an error: a share of a guessed peak means nothing.
"""
from __future__ import annotations

from typing import Sequence

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/roofline.py with its source")
    return PEAKS[device_kind]


def stream_chunks(k: int, k_chunk: int) -> list:
    """Row counts of a streamed round over ``k`` clients."""
    return [min(k_chunk, k - lo) for lo in range(0, k, k_chunk)]


def agg_bytes(chunks: Sequence[int], n: int) -> int:
    """Least HBM bytes of one streamed filler-mode aggregation over a
    plane of ``n`` float32 columns: each accumulate call reads its
    ``(kc, n)`` chunk, its ``kc`` weights and the three ``(n,)`` running
    buffers and writes the three buffers back; the finish call reads the
    three buffers and writes the ``(n,)`` result. Each operand is read
    once and each output written once."""
    f32 = 4
    accum = sum(kc * n * f32 + kc * f32 + 6 * n * f32 for kc in chunks)
    finish = 3 * n * f32 + n * f32
    return accum + finish
