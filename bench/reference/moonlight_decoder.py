"""Plain reference of a DeepSeek-V3-family decoder (Moonlight-16B-A3B) in
jax.numpy.

The whole forward pass over a batch of sequences, causal, with no cache,
no kernels and no batching tricks (DeepSeek-V3, arXiv:2412.19437 §2.1;
MLA as in DeepSeek-V2, arXiv:2405.04434 §2.1): token embedding, then per
layer RMSNorm and latent attention (no query LoRA: ``q_proj``;
``kv_a_proj_with_mqa`` gives the latent and the one rope key all heads
share; ``kv_a_layernorm``; ``kv_b_proj`` gives the per-head no-rope keys
and the values; softmax scale ``(nope + rope) ** -0.5``), its residual,
RMSNorm and either a dense SwiGLU (the first ``first_k_dense_replace``
layers) or a MoE layer, its residual; a final RMSNorm and the LM head.

A MoE layer's router scores every expert with a sigmoid of ``x @ gate``,
computed in float32 whatever the model's type (as HF's ``deepseek_v3``
does), chooses ``num_experts_per_tok`` of them on score plus
``e_score_correction_bias`` (``noaux_tc`` with one group, so no group
is masked), and weighs them by their unbiased scores, normalised
(``norm_topk_prob``, with HF's 1e-20 in the denominator) and times
``routed_scaling_factor``. The routed result is the weighted sum of the
chosen experts' SwiGLUs; the shared experts, one SwiGLU, are added to
it. Weights are named as HF names them and stored (in, out).

Departures from the published model, shared with the program: only the
routed experts in ``held`` are computed (expert parallelism: the rest of
the routed result is other chips' share), and ``held`` may be every
expert, the uncut layer; the vocabulary is a slice; rotary pairs are
interleaved (DeepSeek's own form, into which HF's modeling code
permutes them). :func:`forward` may be given the experts to route to
(``route_ids``), in place of its own choice, so that it follows what a
program chose; the weights are still computed from its own scores.

At float32 the matmuls run at ``highest`` precision, as float32 on a
TPU otherwise rounds operands to bfloat16. :func:`forward` with
``dtype=bfloat16`` is the control: the same reference one precision
lower.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, gamma, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * gamma


def _rope(t, cos, sin):
    t1, t2 = t[..., 0::2], t[..., 1::2]
    return jnp.stack([t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                     axis=-1).reshape(t.shape)


def _swiglu(w, xn, pre):
    gate = xn @ w(pre + "gate_proj.weight")
    up = xn @ w(pre + "up_proj.weight")
    return (jax.nn.silu(gate) * up) @ w(pre + "down_proj.weight")


def moe(params, pre: str, xn, held, top_k: int, scale: float,
        norm_topk: bool, route_ids=None, dtype=jnp.float32):
    """One MoE layer's parts at normed input ``xn`` (..., d):
    ``(routed, shared, biased)``: the part of the routed result the
    experts in ``held`` give, the shared experts' result, and the biased
    scores ``(..., n_experts)`` the router chooses on."""
    def w(name):
        return params[name].astype(dtype)

    logits = xn.astype(jnp.float32) @ params[pre + "gate.weight"].astype(
        jnp.float32)
    scores = jax.nn.sigmoid(logits)
    biased = scores + params[pre + "gate.e_score_correction_bias"].astype(
        jnp.float32)
    ids = jax.lax.top_k(biased, top_k)[1] if route_ids is None else route_ids
    weight = jnp.take_along_axis(scores, ids, axis=-1)
    if top_k > 1 and norm_topk:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-20)
    weight = weight * scale
    routed = jnp.zeros_like(xn)
    for e in held:
        w_e = jnp.sum(jnp.where(ids == e, weight, 0.0), axis=-1)
        routed = routed + (w_e[..., None].astype(dtype)
                           * _swiglu(w, xn, f"{pre}experts.{e}."))
    return routed, _swiglu(w, xn, pre + "shared_experts."), biased


@functools.partial(jax.jit, static_argnames=("dims", "held", "dtype"))
def _forward(params, ids, route_ids, dims, held, dtype):
    (h, n_layers, first_dense, top_k, scale, norm_topk, eps, theta) = dims
    b, s = ids.shape

    def w(name):
        return params[name].astype(dtype)

    pre0 = "model.layers.0.self_attn."
    lora = params[pre0 + "kv_a_layernorm.weight"].shape[0]
    rope = params[pre0 + "kv_a_proj_with_mqa.weight"].shape[1] - lora
    qk = params[pre0 + "q_proj.weight"].shape[1] // h
    nope = qk - rope
    dv = params[pre0 + "kv_b_proj.weight"].shape[1] // h - nope

    x = params["model.embed_tokens.weight"][ids].astype(dtype)
    inv = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    biased_all = []
    for i in range(n_layers):
        pre = f"model.layers.{i}."
        att = pre + "self_attn."
        xn = _rms(x, w(pre + "input_layernorm.weight"), eps)
        q = (xn @ w(att + "q_proj.weight")).reshape(b, s, h, qk)
        kv_a = xn @ w(att + "kv_a_proj_with_mqa.weight")
        latent = _rms(kv_a[..., :lora], w(att + "kv_a_layernorm.weight"), eps)
        kv = (latent @ w(att + "kv_b_proj.weight")).reshape(b, s, h, nope + dv)
        q_rope = _rope(q[..., nope:], cos, sin)
        k_rope = _rope(kv_a[..., None, lora:], cos, sin)[:, :, 0]
        scores = (jnp.einsum("bqhd,bthd->bhqt", q[..., :nope], kv[..., :nope])
                  + jnp.einsum("bqhd,btd->bhqt", q_rope, k_rope))
        scores = scores / jnp.sqrt(jnp.asarray(qk, dtype))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqt,bthd->bqhd", probs, kv[..., nope:])
        x = x + o.reshape(b, s, h * dv) @ w(att + "o_proj.weight")
        xn = _rms(x, w(pre + "post_attention_layernorm.weight"), eps)
        if i < first_dense:
            x = x + _swiglu(w, xn, pre + "mlp.")
            continue
        forced = None if route_ids is None else route_ids[i - first_dense]
        routed, shared, biased = moe(params, pre + "mlp.", xn, held, top_k,
                                     scale, norm_topk, forced, dtype)
        x = x + (routed + shared)
        biased_all.append(biased)
    x = _rms(x, w("model.norm.weight"), eps)
    return (x @ w("lm_head.weight")).astype(jnp.float32), jnp.stack(biased_all)


def dims(config: dict) -> tuple:
    """The configuration's counts and constants (no widths: those are
    read from the tensors' shapes)."""
    return (config["num_attention_heads"], config["num_hidden_layers"],
            config["first_k_dense_replace"], config["num_experts_per_tok"],
            float(config["routed_scaling_factor"]),
            bool(config["norm_topk_prob"]), float(config["rms_norm_eps"]),
            float(config["rope_theta"]))


def forward(params: dict, config: dict, ids, held, route_ids=None,
            dtype=jnp.float32):
    """``(logits (B, S, V), biased scores (L_moe, B, S, n_experts))`` at
    every position of ``ids`` (B, S), as device arrays, with the routed
    experts ``held`` computed. ``route_ids`` (L_moe, B, S, top_k), where
    given, are the experts each MoE layer routes each token to."""
    ids = jnp.asarray(np.asarray(ids, np.int32))
    if route_ids is not None:
        route_ids = jnp.asarray(np.asarray(route_ids, np.int32))
    with jax.default_matmul_precision("highest" if dtype == jnp.float32
                                      else "default"):
        return _forward(params, ids, route_ids, dims(config),
                        tuple(int(e) for e in held), dtype)


def top_k_sets(biased: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """``(ids, margin)``: each row's ``k`` highest-scoring experts, sorted
    by id, and the gap between its ``k``-th and ``k+1``-th scores."""
    order = np.argsort(-biased, axis=-1, kind="stable")
    top = np.take_along_axis(biased, order[..., :k + 1], axis=-1)
    return np.sort(order[..., :k], axis=-1), top[..., k - 1] - top[..., k]
