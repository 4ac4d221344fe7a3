"""Fused dequantize-and-matmul Pallas TPU kernel — compute on compressed.

The TPU-native form of NeurStore's compression-aware inference (paper §4.3):
instead of inserting DequantizeLinear+Add graph nodes that materialize the
full-precision weight in HBM, the weight stays in HBM as **int8 base codes +
int8 (or int4-packed) delta codes** and is de-quantized **tile-wise in VMEM**
inside the matmul's K-loop. The f32 weight only ever exists as a
(block_k × block_n) VMEM tile feeding the MXU.

HBM bytes per weight element: 2.0 (int8+int8), 1.5 (int8+int4) — vs 2.0 for
bf16 and 4.0 for f32. For memory-bound decode this directly scales the
roofline memory term (see EXPERIMENTS.md §Perf).

Grid: (M/bm, N/bn, K/bk), K innermost so the f32 accumulator tile lives in a
VMEM scratch across the K sweep. Block shapes default to 128-multiples so
matmul dims are MXU-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dequant_matmul_pallas", "dequant_matmul_int4_pallas"]

# Full float32 contraction on the MXU. Mosaic's default rounds f32
# operands to bf16: on a v5e that put the kernel 2.2e-3 (relative) off a
# float64 reference at (8, 2048) x (2048, 8192), against 2.0e-7 here.
_F32 = jax.lax.Precision.HIGHEST


def _dq_matmul_kernel(x_ref, base_ref, delta_ref, scal_ref, o_ref, acc_ref, *, n_k):
    """One (bm, bn) output tile; K swept by the innermost grid dim."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base_scale = scal_ref[0, 0]
    base_zp = scal_ref[0, 1]
    delta_scale = scal_ref[0, 2]
    delta_zp = scal_ref[0, 3]

    # Dequantize this (bk, bn) weight tile in VMEM: never touches HBM.
    w = (base_ref[...].astype(jnp.float32) - base_zp) * base_scale
    w += (delta_ref[...].astype(jnp.float32) - delta_zp + 0.5) * delta_scale
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, precision=_F32,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def dequant_matmul_pallas(
    x,
    base,
    base_scale,
    base_zp,
    delta,
    delta_scale,
    delta_zp,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """y = x @ (dq(base_int8) + dq(delta_int8)); shapes x:(M,K), w:(K,N)."""
    m, k = x.shape
    k2, n = base.shape
    assert k == k2 and delta.shape == (k, n)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        "pad inputs to block multiples (ops.py does this)")
    n_k = k // block_k
    scalars = jnp.stack(
        [jnp.float32(base_scale), jnp.float32(base_zp),
         jnp.float32(delta_scale), jnp.float32(delta_zp)]
    ).reshape(1, 4)
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_dq_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 4), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, base, delta, scalars)


def _dq_matmul_int4_kernel(x_ref, base_ref, packed_ref, scal_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base_scale = scal_ref[0, 0]
    base_zp = scal_ref[0, 1]
    delta_scale = scal_ref[0, 2]
    delta_zp = scal_ref[0, 3]

    # (bk//2, bn) uint8, 2 delta nibbles per byte. Widened to int32 before
    # the bit ops: Mosaic has no uint8 -> float32 cast.
    packed = packed_ref[...].astype(jnp.int32)
    low = (packed & 0xF).astype(jnp.float32)
    high = (packed >> 4).astype(jnp.float32)
    bk2, bn = packed.shape
    delta = jnp.stack([low, high], axis=1).reshape(2 * bk2, bn)

    w = (base_ref[...].astype(jnp.float32) - base_zp) * base_scale
    w += (delta - delta_zp + 0.5) * delta_scale
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, precision=_F32,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def dequant_matmul_int4_pallas(
    x,
    base,
    base_scale,
    base_zp,
    packed_delta,
    delta_scale,
    delta_zp,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """y = x @ (dq(base_int8) + dq(unpack4(packed_delta))).

    ``packed_delta`` is (K//2, N) uint8; rows 2k/2k+1 are the low/high
    nibbles (NeurStore flexible loading at b=4 → 1.5 HBM bytes/weight).
    """
    m, k = x.shape
    k2, n = base.shape
    assert k == k2 and packed_delta.shape == (k // 2, n)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    assert block_k % 2 == 0
    n_k = k // block_k
    scalars = jnp.stack(
        [jnp.float32(base_scale), jnp.float32(base_zp),
         jnp.float32(delta_scale), jnp.float32(delta_zp)]
    ).reshape(1, 4)
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_dq_matmul_int4_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // 2, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 4), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, base, packed_delta, scalars)
