"""Compressed-weight serving: NeurStore storage format as the *runtime*
weight format (paper §4.3 pushed to the serving fleet).

Two paths share this module:

**Store-backed (the real NeurStore path).** A llama3-shaped decoder
(GQA + RMSNorm + SwiGLU) is saved through ``StorageEngine.save_model``
and served straight off the engine: ``load_model(name, bits=8|4)`` →
:class:`~repro.core.compressed.CompressedModel` → every large matmul of
:func:`greedy_decode` consumes int8 base codes + int8/int4-packed deltas
through ``kernels.ops.dequant_matmul_auto``. The snapshot's buffer-pool
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

import jax
import jax.numpy as jnp
import numpy as np

from ..core.quantize import dequantize_linear, extract_msb, quantize_delta, quantize_linear
from ..models import decode_step
from ..models.config import ModelConfig
from ..obs.trace import trace

__all__ = [
    "DecoderSpec", "MaterializedProvider", "decoder_architecture",
    "greedy_decode", "init_decoder_tensors", "save_decoder",
    "spec_from_architecture", "quantize_params", "quantize_leaf",
    "dequantize_leaf_jnp", "make_compressed_serve_step",
    "compressed_param_specs",
]

# Leaves smaller than this stay raw (norm vectors, biases).
MIN_QUANT_SIZE = 65_536
DELTA_BITS = 4


# --------------------------------------------------------------------------
# Store-backed serving: llama3-shaped decoder over StorageEngine weights
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """Shape of the stored decoder (llama3 family, GQA)."""

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


def decoder_architecture(spec: DecoderSpec) -> dict:
    """Catalog ``architecture`` payload for a saved decoder."""
    return {"kind": "llama3_decoder", **dataclasses.asdict(spec)}


def spec_from_architecture(arch: dict) -> DecoderSpec:
    fields = {f.name for f in dataclasses.fields(DecoderSpec)}
    return DecoderSpec(**{k: v for k, v in dict(arch).items() if k in fields})


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


def _softmax(x: np.ndarray) -> np.ndarray:
    m = x.max(axis=-1, keepdims=True)
    e = np.exp(x - m)
    return e / e.sum(axis=-1, keepdims=True)


def _silu(x: np.ndarray) -> np.ndarray:
    return x / (1.0 + np.exp(-x))


def _rope(x: np.ndarray, pos: int, theta: float) -> np.ndarray:
    """Interleaved-pair rotary embedding at one position; x (..., dh)."""
    dh = x.shape[-1]
    inv = theta ** (-np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = pos * inv
    cos, sin = np.cos(ang), np.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = np.empty_like(x)
    out[..., 0::2] = x1 * cos - x2 * sin
    out[..., 1::2] = x1 * sin + x2 * cos
    return out


def _attn_block(provider, li: int, x: np.ndarray, kc, vc, pos: int,
                spec: DecoderSpec) -> np.ndarray:
    b = x.shape[0]
    h, kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    pre = f"model.layers.{li}."
    xn = _rms_norm(x, provider.vector(pre + "input_layernorm.weight"),
                   spec.norm_eps)
    q = provider.matmul(xn, pre + "self_attn.q_proj.weight").reshape(b, h, dh)
    k = provider.matmul(xn, pre + "self_attn.k_proj.weight").reshape(b, kv, dh)
    v = provider.matmul(xn, pre + "self_attn.v_proj.weight").reshape(b, kv, dh)
    q = _rope(q, pos, spec.rope_theta)
    k = _rope(k, pos, spec.rope_theta)
    kc[li][:, :, pos] = k
    vc[li][:, :, pos] = v
    # Grouped-query attention: g query heads share each KV head.
    g = h // kv
    qg = q.reshape(b, kv, g, dh)
    keys = kc[li][:, :, :pos + 1]
    vals = vc[li][:, :, :pos + 1]
    s = np.einsum("bkgd,bktd->bkgt", qg, keys) / np.sqrt(dh)
    o = np.einsum("bkgt,bktd->bkgd", _softmax(s), vals).reshape(b, h * dh)
    return provider.matmul(o, pre + "self_attn.o_proj.weight")


def _mlp_block(provider, li: int, x: np.ndarray, spec: DecoderSpec) -> np.ndarray:
    pre = f"model.layers.{li}."
    xn = _rms_norm(x, provider.vector(pre + "post_attention_layernorm.weight"),
                   spec.norm_eps)
    gate = provider.matmul(xn, pre + "mlp.gate_proj.weight")
    up = provider.matmul(xn, pre + "mlp.up_proj.weight")
    return provider.matmul(_silu(gate) * up, pre + "mlp.down_proj.weight")


def greedy_decode(provider, spec: DecoderSpec, prompt: np.ndarray,
                  steps: int, return_logits: bool = False):
    """Greedy decode ``steps`` tokens after consuming ``prompt`` (B, P).

    ``provider`` is anything with the matmul/gather_rows/vector interface
    (:class:`~repro.core.compressed.CompressedModel` for compressed-domain
    serving, :class:`MaterializedProvider` for the float baseline). Every
    projection and the LM head go through ``provider.matmul``; the
    embedding lookup through ``provider.gather_rows`` — the decode loop
    itself owns no weights. Returns (B, steps) int64 tokens, plus the
    per-step (B, steps, V) logits when ``return_logits``.

    The call is one ``generate`` span (``batch``, ``prompt``, ``steps``)
    with a ``forward`` child per position: ``P - 1 + steps`` of them.
    A forward's self time is the host math (embedding gather, norms,
    rope, attention, argmax); its matmuls are the seam's spans.
    """
    prompt = np.atleast_2d(np.asarray(prompt, dtype=np.int64))
    b, p = prompt.shape
    total = p + steps
    shape = (spec.n_layers, b, spec.n_kv_heads, total, spec.head_dim)
    kc = np.zeros(shape, np.float32)
    vc = np.zeros(shape, np.float32)
    generated: list[np.ndarray] = []
    logits_trace: list[np.ndarray] = []
    tok = prompt[:, 0]
    pos = 0
    with trace("generate", batch=b, prompt=p, steps=steps):
        while len(generated) < steps:
            with trace("forward"):
                x = provider.gather_rows("model.embed_tokens.weight", tok)
                for li in range(spec.n_layers):
                    x = x + _attn_block(provider, li, x, kc, vc, pos, spec)
                    x = x + _mlp_block(provider, li, x, spec)
                x = _rms_norm(x, provider.vector("model.norm.weight"),
                              spec.norm_eps)
                logits = provider.matmul(x, "lm_head.weight")
                nxt = np.argmax(logits, axis=1)
            pos += 1
            if pos < p:
                tok = prompt[:, pos]
            else:
                tok = nxt
                generated.append(nxt)
                if return_logits:
                    logits_trace.append(logits)
    tokens = np.stack(generated, axis=1)
    if return_logits:
        return tokens, np.stack(logits_trace, axis=1)
    return tokens


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
