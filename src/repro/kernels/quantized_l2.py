"""Batched quantized-L2 distance Pallas kernel — the HNSW hot loop.

TPU adaptation of the paper's AVX2 ``QuantizedL2Space`` (§5): one f32 query
against a block of int8-quantized base tensors with per-row scale/zero-point.
The HNSW graph walk stays on the host (control flow); each
neighbour-expansion calls this with the frontier's candidate block.

Mirrors the **decomposed** distance used by the host index
(``repro.core.hnsw``): instead of materializing the dequantized rows and
squaring the difference, the D-sweep accumulates three per-row moments of
the raw codes —

    dot_i = Σ_d c_id·q_d      sum_i = Σ_d c_id      sq_i = Σ_d c_id²

— and the final grid step combines them with the per-row quant params and
the query statistics (‖q‖², Σq):

    dist_i = ‖q‖² + s_i²·(sq_i − 2·z_i·sum_i + D·z_i²)
             + 2·(Σq·s_i·z_i − s_i·dot_i)                 (s_i ≠ 0)
    dist_i = ‖q‖² − 2·mid_i·Σq + D·mid_i²                  (s_i = 0)

so the kernel reads the int8 codes once and never forms the (N, D)
dequantized intermediate. Zero-padded columns contribute zero to all three
moments, so only the D·z² term needs the true dimension (``d_true``).

Precision: the float32 moments carry an *absolute* error ~``s·‖q‖·ε₃₂·√D``
into the combined distance (same property as the host path in
``repro.core.hnsw``). Relative error is ≤~1e-4 for queries at typical
distances but can reach ~1e-2 when the query nearly coincides with a row
(the distance itself → 0 while the moments stay ~1e8). Nearest-base
*ranking* is unaffected — competing candidates differ by orders of
magnitude — which is the only property the HNSW walk consumes.

Grid: (N/bn, D/bd); three (bn, 1) moment tiles accumulate across the D
sweep in VMEM scratch. The dense dequantize-and-square semantics the kernel
must reproduce live in ``repro.kernels.ref.quantized_l2_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quantized_l2_pallas"]


def _ql2_kernel(q_ref, codes_ref, scal_ref, qs_ref, o_ref,
                dot_ref, sum_ref, sq_ref, *, n_d, d_true):
    dd = pl.program_id(1)

    @pl.when(dd == 0)
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    # (bn, bd) uint8 index codes; via int32 because Mosaic has no
    # uint8 -> float32 cast.
    c = codes_ref[...].astype(jnp.int32).astype(jnp.float32)
    q = q_ref[...].astype(jnp.float32)           # (1, bd) broadcasts over rows
    dot_ref[...] += jnp.sum(c * q, axis=-1, keepdims=True)
    sum_ref[...] += jnp.sum(c, axis=-1, keepdims=True)
    sq_ref[...] += jnp.sum(c * c, axis=-1, keepdims=True)

    @pl.when(dd == n_d - 1)
    def _combine():
        scales = scal_ref[:, 0:1]
        zps = scal_ref[:, 1:2]
        mids = scal_ref[:, 2:3]
        q2 = qs_ref[0, 0]
        qsum = qs_ref[0, 1]
        d = jnp.float32(d_true)
        norm = scales * scales * (sq_ref[...] - 2.0 * zps * sum_ref[...] + d * zps * zps)
        dist = q2 + norm + 2.0 * (qsum * scales * zps - scales * dot_ref[...])
        cdist = q2 - 2.0 * mids * qsum + d * mids * mids
        o_ref[...] = jnp.maximum(jnp.where(scales == 0.0, cdist, dist), 0.0)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "d_true", "interpret"))
def quantized_l2_pallas(
    query,
    codes,
    scales,
    zps,
    mids,
    *,
    block_n: int = 128,
    block_d: int = 512,
    d_true: int | None = None,
    interpret: bool = False,
):
    """Squared L2: f32 query (D,) vs N int8 rows (N, D) with per-row quant.

    Returns (N,) f32. Inputs must be padded to block multiples (ops.py pads;
    padded rows get scale=0/mid=0 and are sliced off after; zero padding
    contributes nothing to the code moments, and ``d_true`` scopes the
    zero-point correction to the real columns).
    """
    n, d = codes.shape
    assert query.shape == (d,)
    assert n % block_n == 0 and d % block_d == 0
    n_d = d // block_d
    d_true = d if d_true is None else d_true
    scal = jnp.stack(
        [scales.astype(jnp.float32), zps.astype(jnp.float32), mids.astype(jnp.float32)],
        axis=1,
    )  # (N, 3)
    qf = query.astype(jnp.float32)
    # Query statistics for the decomposed form; zero padding leaves both
    # unchanged, so computing them on the padded query is exact. A
    # multiply-and-sum, not vdot: XLA's default-precision dot on the TPU
    # rounds f32 operands to bf16.
    qs = jnp.stack([jnp.sum(qf * qf), jnp.sum(qf)]).reshape(1, 2)
    grid = (n // block_n, n_d)
    out = pl.pallas_call(
        functools.partial(_ql2_kernel, n_d=n_d, d_true=d_true),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_d), lambda i, dd: (0, dd)),
            pl.BlockSpec((block_n, block_d), lambda i, dd: (i, dd)),
            pl.BlockSpec((block_n, 3), lambda i, dd: (i, 0)),
            pl.BlockSpec((1, 2), lambda i, dd: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i, dd: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(query.reshape(1, d), codes, scal, qs)
    return out[:, 0]
