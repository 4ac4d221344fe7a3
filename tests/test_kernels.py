"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels TARGET TPU; on this CPU container they execute in interpret mode
(kernel body run in Python), which validates the block decomposition,
accumulator logic and dequant math exactly.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _mk_quant(k, n):
    base = RNG.integers(0, 256, (k, n)).astype(np.int8)
    delta = RNG.integers(-128, 128, (k, n)).astype(np.int8)
    return base, delta


def _assert_close(got, want):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5 * scale
    )


@pytest.mark.parametrize(
    "m,k,n",
    [
        (1, 128, 128),      # decode row
        (8, 256, 128),
        (64, 256, 192),     # non-multiple N → padding path
        (128, 128, 128),    # exactly one block
        (130, 384, 250),    # ragged everything
    ],
)
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_dequant_matmul_shapes(m, k, n, xdtype):
    x = jnp.asarray(RNG.normal(0, 1, (m, k)), dtype=xdtype)
    base, delta = _mk_quant(k, n)
    bs, bz, ds, dz = 0.013, 117.0, 3.1e-4, 64.0
    want = ref.dequant_matmul_ref(x, jnp.asarray(base), bs, bz, jnp.asarray(delta), ds, dz)
    got = ops.dequant_matmul(x, jnp.asarray(base), bs, bz, jnp.asarray(delta), ds, dz)
    _assert_close(got, want)


@pytest.mark.parametrize("m,k,n", [(1, 128, 128), (16, 256, 256), (64, 384, 200)])
def test_dequant_matmul_int4(m, k, n):
    x = jnp.asarray(RNG.normal(0, 1, (m, k)), dtype=jnp.float32)
    base = RNG.integers(0, 256, (k, n)).astype(np.int8)
    d4 = RNG.integers(0, 16, (k, n)).astype(np.uint8)
    packed = ops.pack_int4(d4)
    bs, bz, ds, dz = 0.02, 128.0, 5e-4, 8.0
    want = ref.dequant_matmul_int4_ref(
        x, jnp.asarray(base), bs, bz, jnp.asarray(packed), ds, dz)
    got = ops.dequant_matmul_int4(
        x, jnp.asarray(base), bs, bz, jnp.asarray(packed), ds, dz)
    _assert_close(got, want)
    # And the unpack itself is exact.
    assert (np.asarray(ref.unpack_int4_ref(jnp.asarray(packed))) == d4).all()


def test_dequant_matmul_matches_materialized_weight():
    """Fused kernel == materialize-then-matmul (the non-fused paper path)."""
    m, k, n = 32, 256, 128
    x = jnp.asarray(RNG.normal(0, 1, (m, k)), dtype=jnp.float32)
    base, delta = _mk_quant(k, n)
    bs, bz, ds, dz = 0.01, 100.0, 1e-4, 50.0
    w = ref.dequantize_weight_ref(jnp.asarray(base), bs, bz, jnp.asarray(delta), ds, dz)
    want = x @ w
    got = ops.dequant_matmul(x, jnp.asarray(base), bs, bz, jnp.asarray(delta), ds, dz)
    _assert_close(got, want)


@pytest.mark.parametrize(
    "n,d",
    [(1, 128), (7, 300), (128, 512), (200, 1000), (130, 4096)],
)
def test_quantized_l2_shapes(n, d):
    q = RNG.normal(0, 1, d).astype(np.float32)
    codes = RNG.integers(0, 256, (n, d)).astype(np.uint8)
    scales = RNG.uniform(1e-3, 2e-2, n).astype(np.float32)
    if n > 3:
        scales[3] = 0.0  # constant-row path
    zps = RNG.integers(0, 256, n).astype(np.float32)
    mids = RNG.normal(0, 0.5, n).astype(np.float32)
    want = ref.quantized_l2_ref(
        jnp.asarray(q), jnp.asarray(codes), jnp.asarray(scales),
        jnp.asarray(zps), jnp.asarray(mids))
    got = ops.quantized_l2(q, codes, scales, zps, mids)
    _assert_close(got, want)


def test_quantized_l2_matches_host_hnsw_distance():
    """Kernel == the numpy hot loop actually used by the host HNSW."""
    from repro.core.hnsw import quantized_l2_batch

    n, d = 64, 777
    q = RNG.normal(0, 1, d)
    codes = RNG.integers(0, 256, (n, d)).astype(np.uint8)
    scales = RNG.uniform(1e-3, 2e-2, n)
    zps = RNG.integers(0, 256, n).astype(np.int64)
    mids = np.zeros(n)
    want = quantized_l2_batch(q, codes, scales, zps, mids)
    got = ops.quantized_l2(
        q.astype(np.float32), codes, scales.astype(np.float32),
        zps.astype(np.float32), mids.astype(np.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3)


@pytest.mark.parametrize("block_k", [128, 256])
def test_dequant_matmul_block_sweep(block_k):
    m, k, n = 64, 512, 256
    x = jnp.asarray(RNG.normal(0, 1, (m, k)), dtype=jnp.float32)
    base, delta = _mk_quant(k, n)
    bs, bz, ds, dz = 0.01, 100.0, 1e-4, 50.0
    want = ref.dequant_matmul_ref(x, jnp.asarray(base), bs, bz, jnp.asarray(delta), ds, dz)
    got = ops.dequant_matmul(
        x, jnp.asarray(base), bs, bz, jnp.asarray(delta), ds, dz, block_k=block_k)
    _assert_close(got, want)


@pytest.mark.parametrize(
    "b,sq,sk,h,kv,dh,causal,window",
    [
        (2, 256, 256, 8, 4, 64, True, 0),
        (1, 256, 256, 4, 1, 128, True, 64),   # MQA + recurrentgemma window
        (2, 128, 128, 8, 8, 64, False, 0),    # bidirectional (hubert)
        (1, 200, 256, 8, 2, 64, True, 0),     # ragged Sq → padding path
        (1, 384, 384, 16, 16, 80, False, 0),  # hubert dims (dh=80)
    ],
)
def test_flash_attention_vs_ref(b, sq, sk, h, kv, dh, causal, window):
    q = jnp.asarray(RNG.normal(0, 1, (b, sq, h, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, sk, kv, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, sk, kv, dh)), jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    got = ops.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def test_flash_attention_matches_model_chunked_attention():
    """Kernel == the pure-JAX chunked attention used by the model stack."""
    from repro.models.layers import chunked_attention

    b, s, h, kv, dh = 2, 256, 8, 2, 64
    q = jnp.asarray(RNG.normal(0, 1, (b, s, h, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, kv, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, kv, dh)), jnp.float32)
    want = chunked_attention(q, k, v, causal=True, chunk=64)
    got = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize(
    "b,sq,sk,h,kv,dh",
    [
        (1, 37, 37, 4, 2, 64),     # non-aligned bidirectional (the old
        (2, 50, 100, 8, 4, 32),    # ValueError path: sk % block_k != 0)
        (1, 100, 50, 4, 4, 64),    # q longer than k
    ],
)
def test_flash_attention_non_causal_padded_keys(b, sq, sk, h, kv, dh):
    """Non-causal attention at non-block-multiple Sk: padded key positions
    must be masked out by the kernel's sk_true bias, not win the softmax
    (regression for the former ValueError/garbage at unaligned lengths)."""
    q = jnp.asarray(RNG.normal(0, 1, (b, sq, h, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, sk, kv, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, sk, kv, dh)), jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=False, window=0)
    got = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)


def _scalar_reconstruct(base_i8, bs, bz, packed, ds, dz):
    """Element-wise host reconstruction of dq(base)+dq(delta) — the slow
    obviously-correct oracle for the fused paths (bin-centre delta)."""
    k, n = base_i8.shape
    w = np.empty((k, n), np.float64)
    for i in range(k):
        byte_row = packed[i // 2]
        for j in range(n):
            nib = (byte_row[j] >> 4) if i % 2 else (byte_row[j] & 0xF)
            w[i, j] = ((float(base_i8[i, j]) - bz) * bs
                       + (float(nib) - dz + 0.5) * ds)
    return w


@pytest.mark.parametrize("k,n,m", [(130, 70, 1), (2, 3, 1), (64, 130, 5)])
def test_dequant_matmul_auto_parity(k, n, m):
    """Interpret-mode kernel == decomposed numpy == in-graph reconstruct ==
    scalar host oracle, on odd shapes including K=2 and decode rows (M=1)."""
    from repro.launch.compressed_serve import dequantize_leaf_jnp, quantize_leaf

    arr = RNG.normal(0, 0.5, (k, n)).astype(np.float32)
    q = quantize_leaf(arr)
    x = RNG.normal(0, 1, (m, k)).astype(np.float32)

    w_scalar = _scalar_reconstruct(q["base"], float(q["bs"]), float(q["bz"]),
                                   q["packed"], float(q["ds"]), float(q["dz"]))
    w_jnp = np.asarray(
        dequantize_leaf_jnp(q, dtype=jnp.float32)).reshape(k, n)
    np.testing.assert_allclose(w_jnp, w_scalar, rtol=1e-5, atol=1e-5)

    want = x.astype(np.float64) @ w_scalar
    for force in ("kernel", "numpy"):
        got = ops.dequant_matmul_auto(
            x, q["base"].reshape(k, n), float(q["bs"]), float(q["bz"]),
            q["packed"], float(q["ds"]), float(q["dz"]),
            packed=True, force=force)
        scale = float(np.abs(want).max()) + 1e-6
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * scale,
                                   err_msg=f"force={force}")


def test_dequant_matmul_auto_int8_paths_agree():
    """force=kernel (interpret Pallas) and force=numpy (decomposed gemm)
    agree on the unpacked int8 delta layout, with and without scratch."""
    k, n, m = 96, 200, 3
    base = RNG.integers(-128, 128, (k, n)).astype(np.int8)
    delta = RNG.integers(-128, 128, (k, n)).astype(np.int8)
    x = RNG.normal(0, 1, (m, k)).astype(np.float32)
    args = (x, base, 0.013, -11.0, delta, 3.1e-4, -64.0)
    yk = ops.dequant_matmul_auto(*args, force="kernel")
    scratch: dict = {}
    yn = ops.dequant_matmul_auto(*args, force="numpy", scratch=scratch)
    yn2 = ops.dequant_matmul_auto(*args, force="numpy", scratch=scratch)
    assert "cpu" in scratch  # combined operand cached for the decode loop
    np.testing.assert_allclose(yk, yn, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(yn, yn2)


@pytest.mark.parametrize("packed", [False, True])
def test_dequant_matmul_auto_staged_operands_bit_identical(packed):
    """With a scratch dict the kernel route stages the codes on the
    device, padded once (K and N here are not block multiples); the
    staging call, a reusing call and a call without scratch all return
    exactly what the kernel's padding wrapper returns for the same
    operands."""
    k, n, m = 192, 200, 3
    base = RNG.integers(-128, 128, (k, n)).astype(np.int8)
    if packed:
        delta = ops.pack_int4(RNG.integers(0, 16, (k, n)).astype(np.uint8))
        scalars = (0.02, -3.0, 5e-4, 8.0)
    else:
        delta = RNG.integers(-128, 128, (k, n)).astype(np.int8)
        scalars = (0.013, -11.0, 3.1e-4, -64.0)
    x = RNG.normal(0, 1, (m, k)).astype(np.float32)
    args = (x, base, scalars[0], scalars[1], delta, scalars[2], scalars[3])
    wrapper = ops.dequant_matmul_int4 if packed else ops.dequant_matmul
    want = np.asarray(wrapper(jnp.asarray(x), jnp.asarray(base), scalars[0],
                              scalars[1], jnp.asarray(delta), scalars[2],
                              scalars[3]))
    unstaged = ops.dequant_matmul_auto(*args, packed=packed, force="kernel")
    scratch: dict = {}
    staged = ops.dequant_matmul_auto(*args, packed=packed, force="kernel",
                                     scratch=scratch)
    basep, _, _, deltap, _, _ = scratch["device"]
    assert basep.shape == (256, 256) and basep.dtype == np.int8
    assert deltap.shape == ((128, 256) if packed else (256, 256))
    assert deltap.dtype == delta.dtype
    reused = ops.dequant_matmul_auto(*args, packed=packed, force="kernel",
                                     scratch=scratch)
    assert want.shape == staged.shape == reused.shape == (m, n)
    np.testing.assert_array_equal(unstaged, want)
    np.testing.assert_array_equal(staged, want)
    np.testing.assert_array_equal(reused, want)


def test_dequant_matmul_auto_rejects_bad_force():
    with pytest.raises(ValueError):
        ops.dequant_matmul_auto(
            np.zeros((1, 2), np.float32), np.zeros((2, 2), np.int8),
            1.0, 0.0, np.zeros((2, 2), np.int8), 1.0, 0.0, force="tpu")


def test_dispatch_seams_count_launches_by_route():
    """neurstore_kernel_calls_total: the interpret-mode kernel counts as
    route=interpret off the TPU, the numpy form as route=host, and
    quantized_l2 counts one launch per query row."""
    from repro.obs.metrics import default_registry

    def count(kernel, route):
        return default_registry().sample_value(
            "neurstore_kernel_calls_total",
            {"kernel": kernel, "route": route}) or 0

    k, n = 64, 128
    base = RNG.integers(-128, 128, (k, n)).astype(np.int8)
    x = RNG.normal(0, 1, (2, k)).astype(np.float32)
    args = (x, base, 0.01, 0.0, base, 1e-4, 0.0)
    before = {r: count("dequant_matmul", r) for r in ("interpret", "host")}
    ops.dequant_matmul_auto(*args, force="kernel")
    ops.dequant_matmul_auto(*args)  # small block, CPU: declined
    assert count("dequant_matmul", "interpret") == before["interpret"] + 1
    assert count("dequant_matmul", "host") == before["host"] + 1

    codes = RNG.integers(0, 256, (5, 256)).astype(np.uint8)
    quant = (np.full(5, 0.01), np.zeros(5), np.zeros(5))
    before = count("quantized_l2", "interpret")
    ops.quantized_l2_auto(RNG.normal(0, 1, (3, 256)), codes, *quant,
                          force="kernel")
    assert count("quantized_l2", "interpret") == before + 3
