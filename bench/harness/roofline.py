"""Operations and bytes a kernel call needs, from its logical shapes.

These count the work the algorithm needs, never what an implementation
pads or uploads again, so a kernel that replaces another is judged by
the same yardstick. The least time of a call is the larger of its
operations over the chip's peak rate and its bytes over the peak
bandwidth; a roofline share is that least time over the measured time.
"""

from __future__ import annotations


def quantized_l2(b: int, n: int, d: int) -> tuple[float, float]:
    """``b`` float32 queries against ``n`` rows of ``d`` uint8 codes.

    A distance needs one multiply-add per code element and query (the
    row norms are per-row constants of the index); the codes are read
    once, with a scale, zero-point and mid per row.
    """
    ops = 2.0 * b * n * d
    nbytes = n * d + 12.0 * n + 4.0 * b * d + 4.0 * b * n
    return ops, nbytes


def dequant_matmul(m: int, k: int, n: int,
                   delta_bytes: float) -> tuple[float, float]:
    """``(m, k)`` float32 activations times a ``(k, n)`` weight held as
    int8 base codes plus delta codes of ``delta_bytes`` per element
    (1 for int8, 0.5 for nibble-packed int4)."""
    ops = 2.0 * m * k * n
    nbytes = k * n * (1.0 + delta_bytes) + 4.0 * m * k + 4.0 * m * n
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """``(seconds, bound)``: the roofline time of one call and which of
    compute or memory bounds it. Operations are charged at the bf16
    peak, the highest float rate, so no share is overstated."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
