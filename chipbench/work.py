"""The benchmark's own count of a GEMM's operations and bytes.

Taken from the unpadded shapes, so it reads the same work whatever
implements the call: ``2 * t * k * n`` operations, and the bytes of a bf16
``(t, k)`` activation slice, a bf16 ``(k, n)`` weight and an f32 ``(t, n)``
output, each moved once.
"""

from __future__ import annotations

from typing import Iterable

X_BYTES = 2    # bf16 activations
W_BYTES = 2    # bf16 weights
OUT_BYTES = 4  # f32 outputs


def flops(shapes: Iterable[tuple[int, int, int]]) -> int:
    return sum(2 * t * k * n for t, k, n in shapes)


def bytes_moved(shapes: Iterable[tuple[int, int, int]]) -> int:
    return sum(t * k * X_BYTES + k * n * W_BYTES + t * n * OUT_BYTES
               for t, k, n in shapes)


def least_seconds(shapes, peak_flops: float, peak_bytes: float) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    shapes = list(shapes)
    return max(flops(shapes) / peak_flops, bytes_moved(shapes) / peak_bytes)
