"""Plain reference of a GQA decoder (InternLM2 / llama family) in jax.numpy.

The whole forward pass over a batch of sequences, causal, with no cache,
no kernels and no batching tricks: token embedding, then per layer
RMSNorm, q/k/v projections, rotary position embedding, grouped-query
attention, the output projection and a SwiGLU MLP, each with its
residual; a final RMSNorm and the LM head. Weights are named as the
configuration's tensor list names them and stored (in, out).

Departures from the published description, shared with the program:
rotary pairs are interleaved (Meta's form; HF's rotate-half is the same
model with q/k output channels permuted), and q/k/v are separate
tensors where the published checkpoint fuses them.

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


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _forward(params, ids, dims, dtype):
    d, h, kv, dh, n_layers, eps, theta = dims
    b, s = ids.shape

    def w(name):
        return params[name].astype(dtype)

    x = params["model.embed_tokens.weight"][ids].astype(dtype)
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    g = h // kv
    for i in range(n_layers):
        pre = f"model.layers.{i}."
        xn = _rms(x, w(pre + "input_layernorm.weight"), eps)
        q = (xn @ w(pre + "self_attn.q_proj.weight")).reshape(b, s, h, dh)
        k = (xn @ w(pre + "self_attn.k_proj.weight")).reshape(b, s, kv, dh)
        v = (xn @ w(pre + "self_attn.v_proj.weight")).reshape(b, s, kv, dh)
        q = _rope(q, cos, sin).reshape(b, s, kv, g, dh)
        k = _rope(k, cos, sin)
        scores = jnp.einsum("bqkgd,btkd->bkgqt", q, k) / jnp.sqrt(
            jnp.asarray(dh, dtype))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", probs, v).reshape(b, s, h * dh)
        x = x + o @ w(pre + "self_attn.o_proj.weight")
        xn = _rms(x, w(pre + "post_attention_layernorm.weight"), eps)
        gate = xn @ w(pre + "mlp.gate_proj.weight")
        up = xn @ w(pre + "mlp.up_proj.weight")
        x = x + (jax.nn.silu(gate) * up) @ w(pre + "mlp.down_proj.weight")
    x = _rms(x, w("model.norm.weight"), eps)
    return (x @ w("lm_head.weight")).astype(jnp.float32)


def dims(config: dict) -> tuple:
    return (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["num_hidden_layers"], float(config["rms_norm_eps"]),
            float(config["rope_theta"]))


def forward(params: dict, config: dict, ids, dtype=jnp.float32) -> np.ndarray:
    """Logits ``(B, S, V)`` at every position of ``ids`` ``(B, S)``."""
    ids = jnp.asarray(np.asarray(ids, np.int32))
    with jax.default_matmul_precision("highest" if dtype == jnp.float32
                                      else "default"):
        return np.asarray(_forward(params, ids, dims(config), dtype))


def served_positions(prompt: np.ndarray, tokens: np.ndarray):
    """``(ids, start)``: the sequence a greedy decode consumed, and the
    position whose logits chose its first served token."""
    ids = np.concatenate([prompt, tokens[:, :-1]], axis=1)
    return ids, prompt.shape[1] - 1


def logit_gaps(ref: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's logit lies below the reference's best:
    ``ref`` ``(B, T, V)``, ``chosen`` ``(B, T)``; 0 where they agree."""
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    return best - got
