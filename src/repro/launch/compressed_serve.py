"""Compressed-weight serving: NeurStore storage format as the *runtime*
weight format (paper §4.3 pushed to the serving fleet).

Two paths share this module:

**Store-backed (the real NeurStore path).** A stored decoder is served
straight off the engine by :func:`greedy_decode`, which picks its blocks
by spec: a llama3-shaped decoder (:class:`DecoderSpec`: GQA + RMSNorm +
SwiGLU), or a DeepSeek-V3-shaped one (:class:`DeepseekV3Spec`: latent
attention, then leading dense layers and expert-parallel MoE layers
that hold a share of the routed experts). A decoder is saved through
``StorageEngine.save_model`` and served as ``load_model(name,
bits=8|4)`` → :class:`~repro.core.compressed.CompressedModel` → every
large matmul of :func:`greedy_decode` consumes int8 base codes +
int8/int4-packed deltas through ``kernels.ops.dequant_matmul_auto`` (a
MoE layer's held experts through ``dequant_matmul_group``, one call a
projection). The snapshot's buffer-pool
frame stays pinned for the serving session and ``materialize()`` is never
called on kernel-served tensors — HBM traffic per weight element drops
from 2.0 bytes (bf16) to 2.0 (int8 base + int8 delta) or 1.5 (int8 +
int4 packed), and the up-front full-precision decode of every weight is
skipped entirely. :class:`MaterializedProvider` is the materialize-then-
serve baseline behind the same provider interface, so the benchmark
(``benchmarks/compressed_serve_bench.py``) swaps only the weight source.

Weights are stored **(in, out)** — ``y = x @ W`` directly, matching the
kernel's (K, N) layout (HF checkpoints store the transpose).

**Host-quantized jnp path (demo/legacy).** ``quantize_params`` converts a
params pytree to the storage format from scratch and
``make_compressed_serve_step`` serves it through in-graph dequantization
that XLA fuses into the consuming matmul — the jnp analogue of the
``dequant_matmul`` Pallas kernel, kept for the tpu-graph serve demos.

Accuracy: deltas at 4 bits relative to the 8-bit base reproduce the
paper's flexible-loading error regime (§6.4.2); greedy decode at b=8
agrees with the materialized forward pass (tests/test_compressed_domain.py).
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from ..core.quantize import dequantize_linear, extract_msb, quantize_delta, quantize_linear
from ..models import decode_step
from ..models.config import ModelConfig
from ..obs.metrics import default_registry
from ..obs.trace import trace

__all__ = [
    "DECODE_POSITIONS", "DecoderSpec", "DeepseekV3Spec", "MOE_ASSIGNMENTS",
    "MaterializedProvider", "PREFILL_ROWS", "decoder_architecture",
    "greedy_decode", "init_decoder_tensors", "save_decoder",
    "spec_from_architecture", "quantize_params", "quantize_leaf",
    "dequantize_leaf_jnp", "make_compressed_serve_step",
    "compressed_param_specs",
]

#: Rows a prompt forward carries: :func:`greedy_decode` consumes a prompt
#: in chunks of ``max(1, PREFILL_ROWS // batch)`` positions. A forward's
#: fixed cost (a host round trip a kernel-routed matmul, and the host code
#: around them) is paid once a chunk; the kernel reads the same code bytes
#: at any row count while it is memory-bound (see PERF.md for the sweep it
#: was set from).
PREFILL_ROWS = 256

# Leaves smaller than this stay raw (norm vectors, biases).
MIN_QUANT_SIZE = 65_536
DELTA_BITS = 4

# Token-expert assignments of the MoE layers' routers, by whether the
# model being served holds the chosen expert (expert parallelism: the
# absent ones are other chips' share).
MOE_ASSIGNMENTS = default_registry().counter(
    "neurstore_moe_assignments_total",
    "Token-expert assignments chosen by MoE routers, to experts the served "
    "model holds (held) or not (absent).",
    ("placement",),
)

DECODE_POSITIONS = default_registry().counter(
    "neurstore_decode_positions_total",
    "Positions consumed by greedy decode, summed over a batch's sequences: "
    "the prompt's (prefill), generated tokens fed back (decode), and the "
    "filler that pads a prompt's last chunk (padding).",
    ("phase",),
)


# --------------------------------------------------------------------------
# Store-backed serving: llama3-shaped decoder over StorageEngine weights
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """Shape of the stored decoder (llama3 family, GQA)."""

    kind: ClassVar[str] = "llama3_decoder"

    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 512
    n_layers: int = 2
    vocab_size: int = 512
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


@dataclasses.dataclass(frozen=True)
class DeepseekV3Spec:
    """Shape of a stored DeepSeek-V3-family decoder (HF ``deepseek_v3``):
    latent attention (MLA) with no query LoRA, the first
    ``first_k_dense`` layers dense SwiGLUs, the rest MoE layers (every
    width but the attention's is read off the stored weights). A MoE layer's router scores all ``n_experts`` experts
    (sigmoid), chooses ``top_k`` on score plus its
    ``e_score_correction_bias`` (one group, so no group selection),
    and weighs them by their unbiased scores, normalised when
    ``norm_topk_prob``, times ``routed_scaling_factor``. The served
    model holds the routed experts ``held_experts`` (expert
    parallelism: the rest are other chips' share, and their part of
    the result is theirs) and the shared experts, one SwiGLU."""

    kind: ClassVar[str] = "deepseek_v3"

    d_model: int = 256
    n_heads: int = 4
    n_layers: int = 3
    vocab_size: int = 512
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    first_k_dense: int = 1
    n_experts: int = 16
    held_experts: tuple = tuple(range(4))
    top_k: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5


_SPECS = {s.kind: s for s in (DecoderSpec, DeepseekV3Spec)}


def decoder_architecture(spec) -> dict:
    """Catalog ``architecture`` payload for a saved decoder."""
    return {"kind": spec.kind, **dataclasses.asdict(spec)}


def spec_from_architecture(arch: dict):
    arch = dict(arch)
    cls = _SPECS[arch.get("kind", DecoderSpec.kind)]
    fields = {f.name for f in dataclasses.fields(cls)}
    out = {k: v for k, v in arch.items() if k in fields}
    if "held_experts" in out:
        out["held_experts"] = tuple(out["held_experts"])
    return cls(**out)


def init_decoder_tensors(spec: DecoderSpec, seed: int = 0) -> dict:
    """Random-init decoder weights, llama3/HF naming, (in, out) layout."""
    rng = np.random.default_rng(seed)
    d, dh = spec.d_model, spec.head_dim
    h, kv, f = spec.n_heads, spec.n_kv_heads, spec.d_ff

    def w(k_dim, n_dim):
        return rng.normal(0.0, k_dim ** -0.5, (k_dim, n_dim)).astype(np.float32)

    tensors = {"model.embed_tokens.weight":
               rng.normal(0.0, 1.0, (spec.vocab_size, d)).astype(np.float32)}
    for i in range(spec.n_layers):
        pre = f"model.layers.{i}."
        tensors[pre + "input_layernorm.weight"] = np.ones(d, np.float32)
        tensors[pre + "self_attn.q_proj.weight"] = w(d, h * dh)
        tensors[pre + "self_attn.k_proj.weight"] = w(d, kv * dh)
        tensors[pre + "self_attn.v_proj.weight"] = w(d, kv * dh)
        tensors[pre + "self_attn.o_proj.weight"] = w(h * dh, d)
        tensors[pre + "post_attention_layernorm.weight"] = np.ones(d, np.float32)
        tensors[pre + "mlp.gate_proj.weight"] = w(d, f)
        tensors[pre + "mlp.up_proj.weight"] = w(d, f)
        tensors[pre + "mlp.down_proj.weight"] = w(f, d)
    tensors["model.norm.weight"] = np.ones(d, np.float32)
    tensors["lm_head.weight"] = w(d, spec.vocab_size)
    return tensors


def save_decoder(engine, name: str, spec: DecoderSpec, seed: int = 0):
    """Save a random-init decoder; returns the engine's SaveReport."""
    return engine.save_model(
        name, decoder_architecture(spec), init_decoder_tensors(spec, seed))


class MaterializedProvider:
    """materialize-then-serve baseline: float32 weights, provider interface.

    Pays the full up-front de-quantization of every stored tensor
    (``LoadedModel.materialize()``), then serves plain float32 gemms.
    Bytes-moved counts float32 weight-operand traffic per matmul — what a
    serving host actually streams when the weights live uncompressed.
    """

    def __init__(self, lm):
        self.lm = lm
        self.params = lm.materialize()
        self._2d: dict[str, np.ndarray] = {}
        self.counters = {"matmul_calls": 0, "gather_calls": 0,
                         "bytes_moved": 0, "fused_elems": 0}

    def matmul(self, x: np.ndarray, name: str) -> np.ndarray:
        w = self._2d.get(name)
        if w is None:
            arr = self.params[name]
            w = self._2d[name] = arr.reshape(arr.shape[0], -1)
        c = self.counters
        c["matmul_calls"] += 1
        c["bytes_moved"] += w.nbytes
        c["fused_elems"] += w.size
        return np.asarray(x, np.float32) @ w

    def expert_matmul(self, x: np.ndarray, names, rows=None) -> np.ndarray:
        """:meth:`matmul` for each weight of a group; ``x`` (M, K) shared
        or (E, M, K) one block per weight, returns (E, M, N)."""
        return np.stack([self.matmul(x[i] if x.ndim == 3 else x, name)
                         for i, name in enumerate(names)])

    def gather_rows(self, name: str, ids: np.ndarray) -> np.ndarray:
        rows = self.params[name][np.asarray(ids)]
        self.counters["gather_calls"] += 1
        self.counters["bytes_moved"] += rows.nbytes
        return rows

    def vector(self, name: str) -> np.ndarray:
        return self.params[name]

    def reset_counters(self) -> None:
        for key in self.counters:
            self.counters[key] = 0

    def close(self) -> None:
        self.lm.close()


def _rms_norm(x: np.ndarray, gamma: np.ndarray, eps: float) -> np.ndarray:
    ms = np.mean(np.square(x), axis=-1, keepdims=True)
    return (x / np.sqrt(ms + eps)) * gamma


def _silu(x: np.ndarray) -> np.ndarray:
    return x / (1.0 + np.exp(-x))


def _rope(x: np.ndarray, pos: np.ndarray, theta: float) -> np.ndarray:
    """Interleaved-pair rotary embedding; x (b, T, ..., dh) at the T
    positions ``pos``."""
    dh = x.shape[-1]
    inv = theta ** (-np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = np.multiply.outer(np.asarray(pos, np.float32), inv)
    ang = ang.reshape(len(pos), *(1,) * (x.ndim - 3), dh // 2)
    cos, sin = np.cos(ang), np.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = np.empty_like(x)
    out[..., 0::2] = x1 * cos - x2 * sin
    out[..., 1::2] = x1 * sin + x2 * cos
    return out


def _attend(s: np.ndarray, vals: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """Causal attention of a run of queries: scores ``s`` (..., T, S) of
    the queries at positions ``pos`` over the cache's first S positions,
    softmaxed over the keys each query may see (position t sees keys at
    positions <= t), applied to ``vals`` (..., S, dv). Overwrites ``s``.
    Only the run's own keys, the last columns, can lie ahead of a query."""
    tail = s[..., pos[0]:]
    tail[..., np.arange(tail.shape[-1]) > np.arange(len(pos))[:, None]] = -np.inf
    s -= s.max(axis=-1, keepdims=True)
    np.exp(s, out=s)
    s /= s.sum(axis=-1, keepdims=True)
    return s @ vals


def _attn_block(provider, li: int, x: np.ndarray, kc, vc, pos: np.ndarray,
                n: int, spec: DecoderSpec) -> np.ndarray:
    """GQA over a run: ``x`` (b*T, d) holds each sequence's T positions
    ``pos``, the first ``n`` real. Their keys and values go into the
    cache; a filler position's never do."""
    t = len(pos)
    b = x.shape[0] // t
    h, kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    g = h // kv
    pre = f"model.layers.{li}."
    xn = _rms_norm(x, provider.vector(pre + "input_layernorm.weight"),
                   spec.norm_eps)
    q = provider.matmul(xn, pre + "self_attn.q_proj.weight").reshape(
        b, t, kv, g, dh)
    k = provider.matmul(xn, pre + "self_attn.k_proj.weight").reshape(b, t, kv, dh)
    v = provider.matmul(xn, pre + "self_attn.v_proj.weight").reshape(b, t, kv, dh)
    end = pos[0] + n
    kc[li][:, :, pos[0]:end] = _rope(k, pos, spec.rope_theta)[:, :n].swapaxes(1, 2)
    vc[li][:, :, pos[0]:end] = v[:, :n].swapaxes(1, 2)
    # Grouped-query attention: the g query heads of each KV head, at
    # every position of the run, are the rows of one gemm.
    q = _rope(q, pos, spec.rope_theta).transpose(0, 2, 3, 1, 4).reshape(
        b, kv, g * t, dh)
    s = (q @ kc[li][:, :, :end].swapaxes(-1, -2)) / np.sqrt(dh)
    s = s.reshape(b, kv, g, t, end)
    o = _attend(s, vc[li][:, :, None, :end], pos)  # (b, kv, g, t, dh)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b * t, h * dh)
    return provider.matmul(o, pre + "self_attn.o_proj.weight")


def _swiglu(provider, xn: np.ndarray, pre: str) -> np.ndarray:
    gate = provider.matmul(xn, pre + "gate_proj.weight")
    up = provider.matmul(xn, pre + "up_proj.weight")
    return provider.matmul(_silu(gate) * up, pre + "down_proj.weight")


def _mlp_block(provider, li: int, x: np.ndarray, spec) -> np.ndarray:
    pre = f"model.layers.{li}."
    xn = _rms_norm(x, provider.vector(pre + "post_attention_layernorm.weight"),
                   spec.norm_eps)
    return _swiglu(provider, xn, pre + "mlp.")


def _mla_block(provider, li: int, x: np.ndarray, cache: dict,
               pos: np.ndarray, n: int, spec: DeepseekV3Spec) -> np.ndarray:
    """Latent attention over a run: ``x`` (b*T, d) holds each sequence's
    T positions ``pos``, the first ``n`` real. The cache is held
    expanded, as HF's ``deepseek_v3`` holds it: a real position's normed
    latent is up-projected through ``kv_b_proj``, and its per-head
    no-rope keys and values are cached beside the one rope key all heads
    share; a filler position's never are."""
    t = len(pos)
    b, h = x.shape[0] // t, spec.n_heads
    nope, rope, dv = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                      spec.v_head_dim)
    lora = spec.kv_lora_rank
    pre = f"model.layers.{li}."
    att = pre + "self_attn."
    with trace("mla"):
        xn = _rms_norm(x, provider.vector(pre + "input_layernorm.weight"),
                       spec.norm_eps)
        q = provider.matmul(xn, att + "q_proj.weight").reshape(b, t, h, nope + rope)
        kv_a = provider.matmul(xn, att + "kv_a_proj_with_mqa.weight")
        latent = _rms_norm(kv_a[:, :lora],
                           provider.vector(att + "kv_a_layernorm.weight"),
                           spec.norm_eps)
        kv = provider.matmul(latent, att + "kv_b_proj.weight").reshape(
            b, t, h, nope + dv)[:, :n].swapaxes(1, 2)
        k_nope, k_rope, v = cache["k_nope"][li], cache["k_rope"][li], cache["v"][li]
        end = pos[0] + n
        k_nope[:, :, pos[0]:end] = kv[..., :nope]
        v[:, :, pos[0]:end] = kv[..., nope:]
        k_rope[:, pos[0]:end] = _rope(kv_a[:, lora:].reshape(b, t, rope), pos,
                                      spec.rope_theta)[:, :n]
        q_rope = _rope(q[..., nope:], pos, spec.rope_theta).swapaxes(1, 2)
        # (b, h, T, d) @ (b, h, d, S): one small gemm per sequence and head.
        s = q[..., :nope].swapaxes(1, 2) @ k_nope[:, :, :end].swapaxes(-1, -2)
        s += q_rope @ k_rope[:, None, :end].swapaxes(-1, -2)
        s /= np.sqrt(nope + rope)
        o = _attend(s, v[:, :, :end], pos).swapaxes(1, 2)  # (b, T, h, dv)
        return provider.matmul(o.reshape(b * t, h * dv), att + "o_proj.weight")


def _route(provider, pre: str, xn: np.ndarray, spec: DeepseekV3Spec):
    """``(ids, weights, biased)``: each token's ``top_k`` experts out of
    all ``n_experts``, chosen on sigmoid score plus the correction bias,
    their weights (the unbiased scores, normalised, scaled), and the
    biased scores the choice was made on."""
    logits = provider.matmul(xn, pre + "mlp.gate.weight")
    scores = 1.0 / (1.0 + np.exp(-logits))
    biased = scores + provider.vector(pre + "mlp.gate.e_score_correction_bias")
    ids = np.argsort(-biased, axis=-1, kind="stable")[:, :spec.top_k]
    w = np.take_along_axis(scores, ids, axis=-1)
    if spec.top_k > 1 and spec.norm_topk_prob:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return ids, w * np.float32(spec.routed_scaling_factor), biased


def _moe_block(provider, li: int, x: np.ndarray, spec: DeepseekV3Spec,
               routing: list | None, live: np.ndarray | None = None) -> np.ndarray:
    """One MoE layer's share: the router over every expert, this model's
    held experts' part of the routed result, and the shared experts.
    Each held expert runs over the whole batch, in one grouped seam call
    a projection; rows not routed to it are combined with weight 0.
    ``live`` (M,), where given, marks the rows of real positions: the
    rest are routed to no expert and counted in no assignment."""
    pre = f"model.layers.{li}."
    xn = _rms_norm(x, provider.vector(pre + "post_attention_layernorm.weight"),
                   spec.norm_eps)
    held = np.asarray(spec.held_experts)
    with trace("route"):
        ids, w, biased = _route(provider, pre, xn, spec)
        hit = ids[..., None] == held  # (b, top_k, held)
        if live is not None:
            hit &= live[:, None, None]
        combine = np.einsum("bk,bke->be", w, hit).astype(np.float32)
        n_held = int(hit.sum())
        n_live = len(ids) if live is None else int(live.sum())
        MOE_ASSIGNMENTS.labels("held").inc(n_held)
        MOE_ASSIGNMENTS.labels("absent").inc(n_live * spec.top_k - n_held)
    if routing is not None:
        routing.append((ids, biased))
    names = [f"{pre}mlp.experts.{e}." for e in spec.held_experts]
    with trace("experts"):
        rows = hit.any(axis=1).sum(axis=0)
        gate = provider.expert_matmul(xn, [n + "gate_proj.weight" for n in names],
                                      rows=rows)
        up = provider.expert_matmul(xn, [n + "up_proj.weight" for n in names],
                                    rows=rows)
        y = provider.expert_matmul(_silu(gate) * up,
                                   [n + "down_proj.weight" for n in names],
                                   rows=rows)
        routed = np.einsum("be,ebd->bd", combine, y)
    return routed + _swiglu(provider, xn, pre + "mlp.shared_experts.")


def _llama_layers(provider, x, cache, pos, n, spec, routing):
    for li in range(spec.n_layers):
        x = x + _attn_block(provider, li, x, cache["k"], cache["v"], pos, n,
                            spec)
        x = x + _mlp_block(provider, li, x, spec)
    return x


def _deepseek_layers(provider, x, cache, pos, n, spec, routing):
    live = np.tile(np.arange(len(pos)) < n, x.shape[0] // len(pos))
    for li in range(spec.n_layers):
        x = x + _mla_block(provider, li, x, cache, pos, n, spec)
        if li < spec.first_k_dense:
            x = x + _mlp_block(provider, li, x, spec)
        else:
            x = x + _moe_block(provider, li, x, spec, routing, live)
    return x


def _new_cache(spec, b: int, total: int) -> dict:
    """Zeroed per-layer caches of ``b`` sequences of ``total`` positions."""
    def zeros(*shape):
        return np.zeros((spec.n_layers, b, *shape), np.float32)

    if isinstance(spec, DeepseekV3Spec):
        return {"k_nope": zeros(spec.n_heads, total, spec.qk_nope_head_dim),
                "k_rope": zeros(total, spec.qk_rope_head_dim),
                "v": zeros(spec.n_heads, total, spec.v_head_dim)}
    return {"k": zeros(spec.n_kv_heads, total, spec.head_dim),
            "v": zeros(spec.n_kv_heads, total, spec.head_dim)}


def greedy_decode(provider, spec, prompt: np.ndarray, steps: int,
                  return_logits: bool = False, return_routing: bool = False):
    """Greedy decode ``steps`` tokens after consuming ``prompt`` (B, P).

    ``provider`` is anything with the matmul/gather_rows/vector interface
    (:class:`~repro.core.compressed.CompressedModel` for compressed-domain
    serving, :class:`MaterializedProvider` for the float baseline), and
    ``expert_matmul`` for a spec with MoE layers. Every projection and
    the LM head go through ``provider.matmul``, the held experts through
    ``provider.expert_matmul``; the embedding lookup through
    ``provider.gather_rows`` — the decode loop itself owns no weights.
    ``spec`` picks the blocks: :class:`DecoderSpec` or
    :class:`DeepseekV3Spec`. Returns (B, steps) int64 tokens, then the
    per-step (B, steps, V) logits when ``return_logits``, then, when
    ``return_routing`` (MoE specs only), each MoE layer's routing at
    every position consumed: ``{"ids": (L_moe, B, P - 1 + steps,
    top_k), "scores": (L_moe, B, P - 1 + steps, n_experts)}``, the
    chosen experts and the biased scores they were chosen on.

    One forward consumes a run of positions of every sequence, B x T
    rows, attending causally over the cache and the run. The prompt runs
    in chunks of ``C = max(1, PREFILL_ROWS // B)`` positions, the last
    padded at its end with filler positions that are never cached,
    routed or returned; then each generated token but the last is one
    forward of one position. The LM head runs only where a token is
    chosen: on the last prompt position and on each generated token.

    The call is one ``generate`` span (``batch``, ``prompt``, ``steps``)
    with a ``forward`` child per chunk and per generated token but the
    last: ``ceil(P / C) + steps - 1`` of them, each with its real
    ``positions`` and ``padded`` filler positions a sequence. A forward's
    self time is the host math (embedding gather, norms, rope,
    attention, argmax); its matmuls are the seam's spans. Under a
    :class:`DeepseekV3Spec` a forward holds an ``mla`` span a layer and
    a ``route`` and an ``experts`` span a MoE layer.
    ``neurstore_decode_positions_total`` counts the positions consumed,
    summed over the sequences, by phase: ``prefill`` (the prompt's),
    ``decode`` (generated tokens fed back) and ``padding`` (filler).
    """
    moe = isinstance(spec, DeepseekV3Spec)
    if return_routing and not moe:
        raise ValueError("return_routing needs a spec with MoE layers")
    layers = _deepseek_layers if moe else _llama_layers
    prompt = np.atleast_2d(np.asarray(prompt, dtype=np.int64))
    b, p = prompt.shape
    chunk = max(1, PREFILL_ROWS // b)
    cache = _new_cache(spec, b, p + steps)
    generated: list[np.ndarray] = []
    logits_trace: list[np.ndarray] = []
    routing_trace: list[list] = []
    start = 0
    with trace("generate", batch=b, prompt=p, steps=steps):
        while len(generated) < steps:
            if start < p:
                n = min(chunk, p - start)
                ids = np.pad(prompt[:, start:start + n], ((0, 0), (0, chunk - n)))
                DECODE_POSITIONS.labels("prefill").inc(b * n)
                DECODE_POSITIONS.labels("padding").inc(b * (chunk - n))
            else:
                n, ids = 1, tok[:, None]
                DECODE_POSITIONS.labels("decode").inc(b)
            t = ids.shape[1]
            chooses = start + n >= p
            routing = [] if return_routing else None
            with trace("forward", positions=n, padded=t - n):
                x = provider.gather_rows("model.embed_tokens.weight",
                                         ids.reshape(-1))
                x = layers(provider, x, cache, start + np.arange(t), n, spec,
                           routing)
                if chooses:
                    x = _rms_norm(x.reshape(b, t, -1)[:, n - 1],
                                  provider.vector("model.norm.weight"),
                                  spec.norm_eps)
                    logits = provider.matmul(x, "lm_head.weight")
                    tok = np.argmax(logits, axis=1)
            if return_routing:
                routing_trace.append([[a.reshape(b, t, -1)[:, :n] for a in layer]
                                      for layer in routing])
            start += n
            if chooses:
                generated.append(tok)
                if return_logits:
                    logits_trace.append(logits)
    out = [np.stack(generated, axis=1)]
    if return_logits:
        out.append(np.stack(logits_trace, axis=1))
    if return_routing:
        # routing_trace[forward][layer] = (ids (B, n, k), scores (B, n, E)).
        out.append({key: np.concatenate(
            [np.stack([layer[j] for layer in fwd]) for fwd in routing_trace],
            axis=2) for j, key in enumerate(("ids", "scores"))})
    return out[0] if len(out) == 1 else tuple(out)


# --------------------------------------------------------------------------
# Host-quantized jnp path (demo/legacy): storage format built from scratch
# --------------------------------------------------------------------------

def _quantizable(leaf) -> bool:
    return (np.issubdtype(np.asarray(leaf).dtype if not hasattr(leaf, "dtype")
                          else leaf.dtype, np.floating)
            and leaf.ndim >= 2 and leaf.size >= MIN_QUANT_SIZE
            and leaf.shape[0] % 2 == 0)


def quantize_leaf(arr: np.ndarray) -> dict:
    """Host-side: tensor → int8 base + packed int4 delta (storage format)."""
    flat = np.asarray(arr, np.float64).ravel()
    base_q, base_meta = quantize_linear(flat, nbit=8)
    base = dequantize_linear(base_q, base_meta)
    delta = flat - base
    dq, dmeta = quantize_delta(delta, p=2.0 ** -24)
    dq4, dmeta4 = extract_msb(dq, dmeta, DELTA_BITS)
    if dmeta4.nbit < DELTA_BITS:  # pad code space so packing is uniform
        dq4 = dq4 << (DELTA_BITS - dmeta4.nbit)
        dmeta4 = type(dmeta4)(scale=dmeta4.scale / (1 << (DELTA_BITS - dmeta4.nbit)),
                              zero_point=dmeta4.zero_point << (DELTA_BITS - dmeta4.nbit),
                              nbit=DELTA_BITS, mid=dmeta4.mid)
    v = dq4.astype(np.uint8).reshape(arr.shape[0], -1)
    packed = (v[0::2] | (v[1::2] << 4)).astype(np.uint8)  # pack along dim 0
    return {
        "base": (base_q.astype(np.int16) - 128).astype(np.int8).reshape(arr.shape),
        "packed": packed,
        "bs": np.float32(base_meta.scale),
        "bz": np.float32(base_meta.zero_point - 128),
        "bmid": np.float32(base_meta.mid),
        "ds": np.float32(dmeta4.scale),
        "dz": np.float32(dmeta4.zero_point),
    }


def quantize_params(params) -> dict:
    """Whole-tree storage-format conversion (host side, done once)."""
    def conv(leaf):
        leaf = np.asarray(leaf)
        if _quantizable(leaf):
            return quantize_leaf(leaf)
        return {"raw": leaf}

    return jax.tree.map(conv, params)


def dequantize_leaf_jnp(q: dict, dtype=jnp.bfloat16):
    """In-graph reconstruction — fuses into the consuming matmul on TPU."""
    if "raw" in q:
        return q["raw"]
    base = (q["base"].astype(jnp.float32) - q["bz"]) * q["bs"]
    packed = q["packed"]
    low = (packed & 0xF).astype(jnp.float32)
    high = (packed >> 4).astype(jnp.float32)
    d0_half = packed.shape[0]
    nibbles = jnp.stack([low, high], axis=1).reshape(2 * d0_half, -1)
    delta = (nibbles - q["dz"] + 0.5) * q["ds"]
    return (base + delta.reshape(base.shape)).astype(dtype)


def make_compressed_serve_step(cfg: ModelConfig):
    """serve_step over storage-format weights (greedy decode one token)."""
    is_q = lambda x: isinstance(x, dict) and ("raw" in x or "base" in x)  # noqa: E731

    def step(qparams, cache, batch, pos):
        params = jax.tree.map(
            lambda q: dequantize_leaf_jnp(q, jnp.dtype(cfg.compute_dtype)),
            qparams, is_leaf=is_q)
        logits, new_cache = decode_step(params, cache, batch, pos, cfg)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    return step


def compressed_param_specs(cfg: ModelConfig):
    """ShapeDtypeStruct tree of the storage-format weights (dry-run)."""
    from .specs import model_specs

    def conv(leaf):
        if _quantizable(leaf):
            n_cols = leaf.size // leaf.shape[0]
            return {
                "base": jax.ShapeDtypeStruct(leaf.shape, jnp.int8),
                "packed": jax.ShapeDtypeStruct(
                    (leaf.shape[0] // 2, n_cols), jnp.uint8),
                "bs": jax.ShapeDtypeStruct((), jnp.float32),
                "bz": jax.ShapeDtypeStruct((), jnp.float32),
                "bmid": jax.ShapeDtypeStruct((), jnp.float32),
                "ds": jax.ShapeDtypeStruct((), jnp.float32),
                "dz": jax.ShapeDtypeStruct((), jnp.float32),
            }
        return {"raw": leaf}

    return jax.tree.map(conv, model_specs(cfg))
