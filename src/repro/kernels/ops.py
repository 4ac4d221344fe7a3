"""Jitted public wrappers over the NeurStore Pallas kernels.

These pad inputs to block multiples, pick interpret mode automatically on
CPU (the kernels TARGET TPU; interpret=True executes the kernel body in
Python for validation), and slice padding back off.

The dispatch seams time what the host does around a launch with program
spans (``repro.obs.trace``, docs/observability.md): ``upload`` covers
operand conversion, padding and host→device transfer up to the kernel's
dispatch, ``wait`` the time from dispatch until the result is in host
memory. Neither adds a synchronisation the seam would not make anyway.
Given a caller-owned ``scratch``, ``dequant_matmul_auto`` keeps a
weight's padded code operands on the device after its first kernel call,
so a later call uploads only its activations. ``dequant_matmul_group``
does the same for a group of same-shape weights (a MoE layer's held
experts): one upload, one jitted launch of a kernel per weight, one
wait.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import default_registry
from ..obs.trace import trace
from .dequant_matmul import dequant_matmul_int4_pallas, dequant_matmul_pallas
from .flash_attention import flash_attention_pallas
from .quantized_l2 import quantized_l2_pallas

__all__ = ["dequant_matmul", "dequant_matmul_auto", "dequant_matmul_group",
           "dequant_matmul_int4",
           "flash_attention", "quantized_l2", "quantized_l2_auto",
           "pack_int4", "kernel_route", "KERNEL_CALLS",
           "OPERAND_RESIDENCY", "KERNEL_DISPATCH_MIN_ELEMS"]

# Code blocks (N*D elements) below this floor never dispatch to the kernel:
# the launch + host<->device transfer would swamp the distance math.
KERNEL_DISPATCH_MIN_ELEMS = 4 << 20

# Kernel launches per dispatch seam (docs/observability.md). Route "tpu" is
# the Pallas kernel compiled for the TPU backend, "interpret" the same
# kernel in interpret mode, "host" the numpy form the seam fell back to.
KERNEL_CALLS = default_registry().counter(
    "neurstore_kernel_calls_total",
    "Dispatch-seam kernel launches by kernel and route "
    "(tpu / interpret / host).",
    ("kernel", "route"),
)

# Kernel-route calls of dequant_matmul_auto given a scratch dict: "staged"
# put the weight's code operands on the device, "reused" found them there.
OPERAND_RESIDENCY = default_registry().counter(
    "neurstore_operand_residency_total",
    "dequant_matmul_auto kernel calls that staged a weight's code operands "
    "on the device (staged) or reused the staged ones (reused).",
    ("kernel", "event"),
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_route() -> str:
    """The route a kernel launch takes in this process: ``"tpu"`` on a
    TPU backend, else ``"interpret"``."""
    return "tpu" if _on_tpu() else "interpret"


def _row_block(n: int, block: int) -> int:
    """Row block for ``n`` rows: ``block``, or ``n`` rounded up to the
    8-row sublane multiple when fewer rows exist. Padding a few index
    vertices to a full 128-row block would multiply a (n, 189M) code
    block 64x in HBM."""
    return block if n >= block else -(-n // 8) * 8


def quantized_l2_auto(queries, codes, scales, zps, mids, *,
                      min_elems: int = KERNEL_DISPATCH_MIN_ELEMS,
                      force: str | None = None):
    """Dispatch seam for the HNSW batched-distance hot loop.

    Routes a (B, D)-queries-vs-(N, D)-codes block to the Pallas
    ``quantized_l2`` kernel when running on a TPU backend and the block is
    large enough to amortize the launch. Returns the (B, N) float64
    distances, or ``None`` so the caller (``repro.core.hnsw``) falls back
    to its numpy decomposed-gemm form — on CPU that fallback *is* the fast
    path (interpret-mode Pallas executes the kernel body in Python).

    ``force="kernel"`` runs the kernel regardless of backend/size (tests
    use this for CPU interpret-mode parity); ``force="numpy"`` always
    declines. A launch opens one ``upload`` span for the float32 queries
    and the hoisted code block, then an ``upload`` and a ``wait`` per
    query row, under the caller's span (``quantized_l2`` in
    ``HNSWIndex._distance_block``).
    """
    codes = np.asarray(codes)
    if force == "numpy" or (
            force != "kernel" and (not _on_tpu() or codes.size < min_elems)):
        KERNEL_CALLS.labels("quantized_l2", "host").inc()
        return None
    n, d = codes.shape
    # Hoist the O(N*D) pad + host→device transfer out of the per-query
    # loop: once padded, the _pad_to calls inside quantized_l2 are no-ops
    # and each iteration is just one (jit-cached) kernel launch. d_true
    # carries the real dimension past the padding.
    bn = _row_block(n, 128)
    bd = min(512, max(128, d)) if d < 512 else 512
    with trace("upload"):
        q2 = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        if q2.shape[0] == 0:
            return np.zeros((0, n), dtype=np.float64)
        codes_j = _pad_to(_pad_to(jnp.asarray(codes), bn, 0), bd, 1)
        s = _pad_to(jnp.asarray(np.asarray(scales, dtype=np.float32)), bn, 0)
        z = _pad_to(jnp.asarray(np.asarray(zps, dtype=np.float32)), bn, 0)
        m = _pad_to(jnp.asarray(np.asarray(mids, dtype=np.float32)), bn, 0)
    KERNEL_CALLS.labels("quantized_l2", kernel_route()).inc(len(q2))
    out = []
    for q in q2:
        with trace("upload"):
            y = quantized_l2(_pad_to(jnp.asarray(q), bd, 0), codes_j, s, z, m,
                             d_true=d)
        with trace("wait"):
            out.append(np.asarray(y)[:n])
    return np.stack(out).astype(np.float64)


def _pad_to(x, mult, axis, value=0):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value)


def _m_block(m: int, block_m: int = 128) -> int:
    """Row block of an ``m``-row activation: ``block_m``, or ``m`` (at
    least 8) when fewer rows exist."""
    return min(block_m, max(8, m)) if m < block_m else block_m


def _launch(pallas_fn, x, basep, base_scale, base_zp, deltap, delta_scale,
            delta_zp, *, block_m=128, block_n=128, block_k=128,
            interpret=None):
    """Pad ``x`` to the kernel's blocks and launch ``pallas_fn`` on weight
    operands already padded to them; returns the padded output."""
    if interpret is None:
        interpret = not _on_tpu()
    bm = _m_block(x.shape[0], block_m)
    # NOTE: padded K rows contribute dq(0)+dq(0) * x_pad(=0) = 0 because x is
    # zero-padded along K — weight padding values are irrelevant.
    xp = _pad_to(_pad_to(x, bm, 0), block_k, 1)
    return pallas_fn(
        xp, basep, base_scale, base_zp, deltap, delta_scale, delta_zp,
        block_m=bm, block_n=block_n, block_k=block_k, interpret=interpret)


def dequant_matmul(x, base, base_scale, base_zp, delta, delta_scale, delta_zp,
                   *, block_m=128, block_n=128, block_k=128, interpret=None):
    """y = x @ (dq(base) + dq(delta)), fused; pads to MXU-aligned blocks."""
    m, n = x.shape[0], base.shape[1]
    basep = _pad_to(_pad_to(base, block_k, 0), block_n, 1)
    deltap = _pad_to(_pad_to(delta, block_k, 0), block_n, 1)
    y = _launch(dequant_matmul_pallas, x, basep, base_scale, base_zp, deltap,
                delta_scale, delta_zp, block_m=block_m, block_n=block_n,
                block_k=block_k, interpret=interpret)
    return y[:m, :n]


def pack_int4(delta4: np.ndarray) -> np.ndarray:
    """(K, N) values in [0,15] → (K//2, N) uint8, row 2k low / 2k+1 high."""
    k, n = delta4.shape
    assert k % 2 == 0
    d = np.asarray(delta4, dtype=np.uint8)
    return (d[0::2] | (d[1::2] << 4)).astype(np.uint8)


def dequant_matmul_int4(x, base, base_scale, base_zp, packed_delta,
                        delta_scale, delta_zp,
                        *, block_m=128, block_n=128, block_k=128, interpret=None):
    """y = x @ (dq(base) + dq(unpack4(packed))); 1.5 HBM bytes/weight."""
    m, n = x.shape[0], base.shape[1]
    basep = _pad_to(_pad_to(base, block_k, 0), block_n, 1)
    packedp = _pad_to(_pad_to(packed_delta, block_k // 2, 0), block_n, 1)
    y = _launch(dequant_matmul_int4_pallas, x, basep, base_scale, base_zp,
                packedp, delta_scale, delta_zp, block_m=block_m,
                block_n=block_n, block_k=block_k, interpret=interpret)
    return y[:m, :n]


def _stage_operands(base, base_scale, base_zp, delta, delta_scale, delta_zp,
                    packed, *, block_n=128, block_k=128):
    """A weight's kernel operands, put on the device once: the codes
    zero-padded on the host to the kernel's block multiples (the packed
    int4 delta to half the K block), the scales and zero-points as
    float32 scalars. Returns the staged tuple, in the kernel's argument
    order, and the padded code bytes."""
    def pad(a, rows):
        return np.pad(a, ((0, -a.shape[0] % rows), (0, -a.shape[1] % block_n)))

    basep = pad(base, block_k)
    deltap = pad(delta, block_k // 2 if packed else block_k)
    staged = jax.device_put(
        (basep, np.float32(base_scale), np.float32(base_zp),
         deltap, np.float32(delta_scale), np.float32(delta_zp)))
    return staged, basep.nbytes + deltap.nbytes


def dequant_matmul_auto(x, base, base_scale, base_zp, delta, delta_scale,
                        delta_zp, *, packed=False,
                        min_elems: int = KERNEL_DISPATCH_MIN_ELEMS,
                        force: str | None = None,
                        scratch: dict | None = None) -> np.ndarray:
    """Dispatch seam for compute-on-compressed matmuls (serving hot loop).

    ``y = x @ (dq(base) + dq(delta))`` without ever materializing the
    float weight. Routes to the fused Pallas kernel (``dequant_matmul``,
    or ``dequant_matmul_int4`` when ``packed=True``) on a TPU backend when
    the weight block is large enough to amortize the launch; otherwise
    runs the decomposed CPU form

        ``y = x@(bs·Bf + ds·Df) + (-bs·bz + ds·(0.5-dz))·rowsum(x)``

    where ``bs·Bf + ds·Df`` is a single pre-scaled float32 combination of
    the *codes* (cached in the caller-owned ``scratch`` dict across
    calls, e.g. per decode step; valid only while operands and scales are
    fixed) and the scalar zero-point/bin-centre term folds into a rowsum
    correction, so the steady-state cost is one gemm — the same as
    serving a materialized weight. On CPU this decomposition *is* the
    fast path: interpret-mode Pallas executes the kernel body in Python.

    ``x``: (M, K) float; ``base``: (K, N) int8 recentred codes; ``delta``:
    (K, N) int8 recentred codes, or (K//2, N) uint8 nibble-packed when
    ``packed=True`` (``pack_int4`` layout: row 2k low / 2k+1 high, codes
    unsigned in [0, 15] with unsigned zero-point). Zero-points/scales are
    scalars matching the code recentring.

    On the kernel route the same ``scratch`` keeps the weight's code
    operands on the device (``scratch["device"]``): the first call pads
    them to the kernel's blocks and stages them with their scalars, and
    every later call uploads only ``x``. The caller owns their lifetime
    and must drop them when the codes can change
    (``CompressedModel.close()``). Without ``scratch`` every kernel call
    pads and uploads its operands afresh.

    ``force="kernel"`` runs the Pallas kernel regardless of backend/size
    (interpret mode on CPU — the parity-test hook); ``force="numpy"``
    always takes the decomposed path. Returns (M, N) float32 numpy.

    Each call is one span named by the kernel it routes to
    (``dequant_matmul`` or ``dequant_matmul_int4``) with its ``route``
    (``tpu`` / ``interpret`` / ``host``), logical shape (``m``, ``k``,
    ``n``, ``packed``) and ``operand_bytes`` (float32 activations plus
    the code operands as passed); a kernel route adds ``upload`` and
    ``wait`` children. With ``scratch``, ``upload`` covers converting
    ``x`` and the dispatch, plus on a weight's first kernel call the
    staging, whose padded code bytes it carries as ``staged_bytes``;
    each such call counts ``staged`` or ``reused`` in
    ``neurstore_operand_residency_total``.
    """
    if force not in (None, "kernel", "numpy"):
        raise ValueError(f"force must be None, 'kernel' or 'numpy': {force!r}")
    base = np.asarray(base)
    delta = np.asarray(delta)
    use_kernel = force == "kernel" or (
        force is None and _on_tpu() and base.size >= min_elems)
    kernel, pallas_fn = (
        ("dequant_matmul_int4", dequant_matmul_int4_pallas) if packed
        else ("dequant_matmul", dequant_matmul_pallas))
    route = kernel_route() if use_kernel else "host"
    KERNEL_CALLS.labels(kernel, route).inc()
    x32 = np.asarray(x, dtype=np.float32)
    m, k = x32.shape
    n = base.shape[1]
    with trace(kernel, route=route, m=m, k=k, n=n, packed=packed,
               operand_bytes=x32.nbytes + base.nbytes + delta.nbytes):
        if use_kernel:
            with trace("upload") as upload:
                staged = scratch.get("device") if scratch is not None else None
                if staged is None:
                    staged, nbytes = _stage_operands(
                        base, base_scale, base_zp, delta, delta_scale,
                        delta_zp, packed)
                    if scratch is not None:
                        scratch["device"] = staged
                        upload.set_attr("staged_bytes", nbytes)
                        OPERAND_RESIDENCY.labels(kernel, "staged").inc()
                else:
                    OPERAND_RESIDENCY.labels(kernel, "reused").inc()
                y = _launch(pallas_fn, jnp.asarray(x32), *staged)
            with trace("wait"):
                # The staged weights are padded: the slice drops the
                # padded rows and columns on the host.
                return np.asarray(y, dtype=np.float32)[:m, :n]
        return _dequant_matmul_host(x32, base, base_scale, base_zp, delta,
                                    delta_scale, delta_zp, packed, scratch)


def _dequant_matmul_host(x32, base, base_scale, base_zp, delta, delta_scale,
                         delta_zp, packed, scratch):
    """The decomposed CPU form of :func:`dequant_matmul_auto`; ``x32`` is
    the float32 activation block."""
    ops = scratch.get("cpu") if scratch is not None else None
    if ops is None:
        bf = base.astype(np.float32) * np.float32(base_scale)
        d = delta
        if packed:
            # Unpack nibbles to the (K, N) code grid the decomposition
            # needs; the HBM-traffic win of packing belongs to the TPU
            # path — on CPU the one-time unpack is amortized via scratch.
            k2, n = d.shape
            low = (d & 0xF).astype(np.float32)
            high = (d >> 4).astype(np.float32)
            d = np.stack([low, high], axis=1).reshape(2 * k2, n)
        else:
            d = d.astype(np.float32)
        d *= np.float32(delta_scale)
        bf += d
        c = np.float32(-float(base_scale) * float(base_zp)
                       + float(delta_scale) * (0.5 - float(delta_zp)))
        ops = (bf, c)
        if scratch is not None:
            scratch["cpu"] = ops
    wf, c = ops
    y = x32 @ wf
    y += c * x32.sum(axis=1, keepdims=True)
    return y


@functools.partial(jax.jit, static_argnames=("fns", "bm", "interpret"))
def _group_launch(xp, staged, *, fns, bm, interpret):
    """One dispatch for a group: each weight's kernel over the shared
    activation block, or over its own where ``xp`` is 3-D; a tuple of
    ``(Mp, Np)`` results. Stacking them on the device would fuse every
    kernel but the first into the stack's update, and the device trace
    would then name those kernels fusions, not custom calls."""
    return tuple(
        fn(xp[i] if xp.ndim == 3 else xp, *ops, block_m=bm, block_n=128,
           block_k=128, interpret=interpret)
        for i, (fn, ops) in enumerate(zip(fns, staged)))


def dequant_matmul_group(x, operands, packed, *,
                         min_elems: int = KERNEL_DISPATCH_MIN_ELEMS,
                         force: str | None = None,
                         scratch: dict | None = None,
                         scratches=None, rows=None) -> np.ndarray:
    """Dispatch seam for a group of same-shape compressed weights, such
    as one projection of a MoE layer's held experts: ``y[e] = x_e @
    (dq(base_e) + dq(delta_e))``.

    ``x``: (M, K) float, shared by every weight, or (E, M, K), a block
    for each. ``operands``: E tuples ``(base, base_scale, base_zp,
    delta, delta_scale, delta_zp)`` in :func:`dequant_matmul_auto`'s
    layout; ``packed``: E flags, the int4 layout where set. Returns
    (E, M, N) float32 numpy.

    The gate is :func:`dequant_matmul_auto`'s, applied to the group's
    weight elements. On the kernel route the activations upload once,
    one jitted dispatch launches each weight's Pallas kernel
    (``dequant_matmul``, or ``dequant_matmul_int4`` where packed), and
    one wait brings the stacked result back: one host round trip for
    the group. The caller's ``scratch`` (one per group) keeps every
    weight's staged codes on the device, as ``dequant_matmul_auto``'s
    does for one weight. On the host route each weight runs
    ``dequant_matmul_auto``'s decomposed form with its own entry of
    ``scratches``, so the result is exactly the per-weight one.
    ``force`` is ``dequant_matmul_auto``'s.

    The call is one ``dequant_matmul_group`` span with ``route``,
    ``experts`` (E), ``m``, ``k``, ``n``, ``packed`` (how many weights
    are int4-packed), ``operand_bytes``, and, where the caller gives
    ``rows``, ``routed_rows``: how many of the M rows it routes to each
    weight (the kernel computes all M; the caller weighs the rest 0).
    The kernel route adds ``upload`` and ``wait`` children as
    ``dequant_matmul_auto`` does. ``neurstore_kernel_calls_total`` and
    ``neurstore_operand_residency_total`` count each weight's launch
    under its kernel.
    """
    if force not in (None, "kernel", "numpy"):
        raise ValueError(f"force must be None, 'kernel' or 'numpy': {force!r}")
    operands = [tuple(o) for o in operands]
    packed = [bool(p) for p in packed]
    bases = [np.asarray(o[0]) for o in operands]
    deltas = [np.asarray(o[3]) for o in operands]
    n_w = len(operands)
    if n_w == 0 or len(packed) != n_w:
        raise ValueError("a group needs one packed flag per weight, and "
                         "at least one weight")
    k, n = bases[0].shape
    if any(b.shape != (k, n) for b in bases):
        raise ValueError("a group's weights must share one (K, N) shape")
    x32 = np.asarray(x, dtype=np.float32)
    per_weight = x32.ndim == 3
    if per_weight and x32.shape[0] != n_w:
        raise ValueError(f"{x32.shape[0]} activation blocks for {n_w} weights")
    m = x32.shape[-2]
    use_kernel = force == "kernel" or (
        force is None and _on_tpu() and n_w * k * n >= min_elems)
    kernels = ["dequant_matmul_int4" if p else "dequant_matmul" for p in packed]
    route = kernel_route() if use_kernel else "host"
    for kernel in kernels:
        KERNEL_CALLS.labels(kernel, route).inc()
    attrs = {"route": route, "experts": n_w, "m": m, "k": k, "n": n,
             "packed": sum(packed),
             "operand_bytes": x32.nbytes + sum(
                 b.nbytes + d.nbytes for b, d in zip(bases, deltas))}
    if rows is not None:
        attrs["routed_rows"] = [int(r) for r in rows]
    with trace("dequant_matmul_group", **attrs):
        if not use_kernel:
            scratches = scratches or [None] * n_w
            return np.stack([
                _dequant_matmul_host(
                    x32[i] if per_weight else x32, bases[i], o[1], o[2],
                    deltas[i], o[4], o[5], packed[i], scratches[i])
                for i, o in enumerate(operands)])
        with trace("upload") as upload:
            staged = scratch.get("device") if scratch is not None else None
            if staged is None:
                pairs = [_stage_operands(bases[i], o[1], o[2], deltas[i],
                                         o[4], o[5], packed[i])
                         for i, o in enumerate(operands)]
                staged = tuple(p for p, _ in pairs)
                if scratch is not None:
                    scratch["device"] = staged
                    upload.set_attr("staged_bytes", sum(b for _, b in pairs))
                    for kernel in kernels:
                        OPERAND_RESIDENCY.labels(kernel, "staged").inc()
            else:
                for kernel in kernels:
                    OPERAND_RESIDENCY.labels(kernel, "reused").inc()
            bm = _m_block(m)
            pad = [(0, 0)] * (x32.ndim - 2) + [(0, -m % bm), (0, -k % 128)]
            y = _group_launch(
                jnp.asarray(np.pad(x32, pad)), staged,
                fns=tuple(dequant_matmul_int4_pallas if p
                          else dequant_matmul_pallas for p in packed),
                bm=bm, interpret=not _on_tpu())
        with trace("wait"):
            return np.stack([a[:m, :n] for a in jax.device_get(y)])


def quantized_l2(query, codes, scales, zps, mids,
                 *, block_n=128, block_d=512, d_true=None, interpret=None):
    """HNSW distance hot loop; pads N and D, returns (N,) f32.

    The kernel computes the decomposed form (code moments + per-row quant
    params; see ``quantized_l2.py``) — zero padding is exact because padded
    codes/query columns contribute nothing to the accumulated moments.
    ``d_true`` overrides the unpadded dimension when the caller passes
    already-padded inputs (``quantized_l2_auto`` hoists the padding out of
    its per-query loop); it scopes the zero-point D·z² correction to the
    real columns.
    """
    if interpret is None:
        interpret = not _on_tpu()
    n, d = codes.shape
    bn = _row_block(n, block_n)
    bd = min(block_d, max(128, d)) if d < block_d else block_d
    qp = _pad_to(jnp.asarray(query), bd, 0)
    codesp = _pad_to(_pad_to(jnp.asarray(codes), bn, 0), bd, 1)
    # Padded rows: scale=0, mid=0 → dequantize to 0; padded query dims are 0,
    # so padded D contributes 0 and padded rows are sliced off below.
    scalesp = _pad_to(jnp.asarray(scales), bn, 0)
    zpsp = _pad_to(jnp.asarray(zps), bn, 0)
    midsp = _pad_to(jnp.asarray(mids), bn, 0)
    out = quantized_l2_pallas(qp, codesp, scalesp, zpsp, midsp,
                              block_n=bn, block_d=bd,
                              d_true=d if d_true is None else d_true,
                              interpret=interpret)
    return out[:n]


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=None):
    """Flash attention fwd (grouped GQA); pads Sq/Sk to block multiples."""
    if interpret is None:
        interpret = not _on_tpu()
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    bq = min(block_q, max(8, sq)) if sq < block_q else block_q
    bk = min(block_k, max(8, sk)) if sk < block_k else block_k
    qp = _pad_to(q, bq, 1)
    kp = _pad_to(k, bk, 1)
    vp = _pad_to(v, bk, 1)
    # Padded K positions must never win the softmax: the kernel masks
    # positions >= sk with its large-negative bias (sk_true), which covers
    # bidirectional (hubert-shaped) inputs at any length — causal masking
    # alone only protected them when q ran ahead of k. Padded q rows
    # attend real keys and produce finite garbage, sliced off below.
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 block_q=bq, block_k=bk, sk_true=sk,
                                 interpret=interpret)
    return out[:, :sq]
