"""Greedy decode of a stored MoE fine-tune with latent attention
(DeepSeek-V3 family), straight from its compressed frames.

Traffic parameters: those of ``decode`` (see its docstring), whose
lengths, requests, rate and release this driver shares.

Set-up makes the base and one fine-tune on the device from the seed,
saves both through ``NeurStore.save``, loads the fine-tune compressed
(``load_model(bits=...)``, ``CompressedModel``) and serves one warm
request of the window's batch. The model holds the routed experts
``0 .. n_routed_experts - 1`` of each MoE layer (the configuration's
share under expert parallelism); its router scores every expert the
router's width names. Every MLA and MoE width is read from the tensor
shapes, so a configuration shrunk for the CPU tests stays consistent.

The window serves requests back to back, as ``decode`` does, and keeps
each request's routing: the experts each MoE layer chose for each token,
and the biased scores it chose on. The check runs the plain float32
reference over every finished request's prompt and served tokens, from
weights it makes again from the seed, with the program's routing forced
so that both follow one path, and reads:

- ``served_logit_gap``: how far a served token's logit lies below the
  reference's best;
- ``routing_disagreements``: (token, layer) pairs where the program's
  top-k set differs from the reference's own choice, and the reference's
  margin between its k-th and k+1-th biased scores exceeds ``DELTA``.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import numpy as np

from bench.drivers import decode
from bench.harness import weights
from bench.harness.cell import Check, Window
from bench.harness.roofline import dequant_matmul
from bench.reference import llama_decoder
from bench.reference import moonlight_decoder as ref

#: Widest gap, in logits, by which a served token may lie below the
#: reference's best token. On the chip the program read 0.0012, 0.0045
#: and 0.0123 (three seeds, batch 64) and the bfloat16 control 0.051 and
#: 0.056: the limit lies twice over the one and twice under the other
#: (see PERF.md).
GAP_LIMIT = 0.025
#: A router's choice is held to the reference's only where the
#: reference's k-th biased score leads its k+1-th by more than this. A
#: closer pair can be reordered by the program's score error, which read
#: up to 0.0029 and 0.0057 on the chip (two seeds; part of it the 8-bit
#: load of the correction biases, which a save stores as deltas on one
#: another): a pair needs a lead of twice the error to be held, and the
#: limit leaves room for seeds that read higher. The bfloat16 control's
#: scores lay 0.017 and 0.018 away (see PERF.md).
DELTA = 0.02

State = decode.State
end_to_end = decode.end_to_end
release = decode.release
reference_params = decode.reference_params


def decoder_spec(config: dict):
    from repro.launch.compressed_serve import DeepseekV3Spec

    shapes = {name: shape for name, shape, _ in weights.tensor_specs(config)}
    h = config["num_attention_heads"]
    att = "model.layers.0.self_attn."
    lora = shapes[att + "kv_a_layernorm.weight"][0]
    rope = shapes[att + "kv_a_proj_with_mqa.weight"][1] - lora
    qk = shapes[att + "q_proj.weight"][1] // h
    dense = config["first_k_dense_replace"]
    return DeepseekV3Spec(
        d_model=config["hidden_size"], n_heads=h,
        n_layers=config["num_hidden_layers"], vocab_size=config["vocab_size"],
        kv_lora_rank=lora, qk_nope_head_dim=qk - rope, qk_rope_head_dim=rope,
        v_head_dim=shapes[att + "kv_b_proj.weight"][1] // h - (qk - rope),
        first_k_dense=dense,
        n_experts=shapes[f"model.layers.{dense}.mlp.gate.weight"][1],
        held_experts=tuple(range(config["n_routed_experts"])),
        top_k=config["num_experts_per_tok"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]))


def setup(cell) -> State:
    # The program's MoE path first: a program without it fails here, in
    # seconds, before any weights are made.
    from repro.launch.compressed_serve import decoder_architecture, greedy_decode
    spec = decoder_spec(cell.config)

    from repro.core.compressed import CompressedModel
    from repro.store import NeurStore, SaveRequest

    tr, s = cell.traffic, cell.config["store"]
    base, (ft,) = weights.make_models(cell.config, cell.seed, 1,
                                      tr["ft_rel_std"])
    store = NeurStore.open(str(cell.workdir / "store"), tau=s["tau"],
                           tolerance=s["tolerance"], pool_bytes=s["pool_bytes"])
    arch = decoder_architecture(spec)
    store.save(SaveRequest("base", base, architecture=arch))
    store.save(SaveRequest("ft", ft, architecture=arch))
    del base, ft
    lm = store.engine.load_model("ft", bits=tr["bits"])
    model = CompressedModel(lm)
    rng = np.random.default_rng([cell.seed, 11])
    # Every shape the window runs: the kernels see (batch, K) activations
    # and (held, batch, K) expert blocks whatever the lengths and routing.
    warm = rng.integers(0, cell.config["vocab_size"], (tr["batch"], 2))
    greedy_decode(model, spec, warm, 2)
    return State(cell, spec, store, lm, model, model, rng)


def window(st: State, seconds: float, mark=None) -> Window:
    """``decode``'s window, keeping each request's routing."""
    from repro.launch import compressed_serve as cs

    done, errors = [], []
    attempted = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        attempted += 1
        prompt, steps = decode.request(st.rng, st.cell)
        try:
            if mark:
                with mark("bench.request"):
                    tokens, routing = cs.greedy_decode(
                        st.provider, st.spec, prompt, steps,
                        return_routing=True)
            else:
                tokens, routing = cs.greedy_decode(
                    st.provider, st.spec, prompt, steps, return_routing=True)
        except Exception as exc:  # noqa: BLE001 — counted, not fatal
            errors.append(repr(exc))
            continue
        done.append((prompt, np.asarray(tokens), routing))
    t1 = time.perf_counter()
    data = {"requests": done,
            "tokens": sum(p.size + t.size for p, t, _ in done),
            "forwards": sum(p.shape[1] - 1 + t.shape[1] for p, t, _ in done)}
    return Window(t0, t1, attempted, len(errors), errors, data)


class TimedProvider(decode.TimedProvider):
    """``decode``'s timed provider, with the grouped expert calls: each
    held expert's call is recorded with the rows routed to it, the work
    the algorithm needs, whatever rows the kernel runs."""

    def expert_matmul(self, x, names, rows):
        ws = [self.model.weight(n) for n in names]
        t = time.perf_counter()
        with self.mark("bench.matmul"):
            y = self.model.expert_matmul(x, names, rows=rows)
        seconds = time.perf_counter() - t
        eligible = sum(w.k * w.n for w in ws) >= self.model.min_elems
        for w, m in zip(ws, rows):
            m = int(m)
            o, nbytes = (dequant_matmul(m, w.k, w.n, 0.5 if w.packed else 1.0)
                         if m else (0.0, 0.0))
            self.calls.append({"kernel": "dequant_matmul", "m": m, "k": w.k,
                               "n": w.n, "packed": w.packed,
                               "seconds": seconds / len(ws),
                               "eligible": eligible, "ops": o,
                               "bytes": nbytes})
        return y


@contextmanager
def recorder(st: State):
    """``decode``'s recorder over this driver's timed provider."""
    from bench.harness.cell import mark

    calls: list[dict] = []
    st.provider = TimedProvider(st.model, calls, mark)
    before = decode.kernel_launches()
    try:
        yield calls
    finally:
        st.provider = st.model
        launched = decode.kernel_launches() - before
        admitted = sum(c["eligible"] for c in calls)
        for c in calls:
            c["offloaded"] = c["eligible"] and launched == admitted


def held(config: dict) -> tuple[int, ...]:
    return tuple(range(config["n_routed_experts"]))


def reference(params: dict, cell, prompt, tokens, route_ids, dtype=None):
    """``(logits (B, T, V), biased (L_moe, B, S, E))``: the reference's
    logits at the positions that chose the ``T`` served tokens and its
    biased router scores at all ``S`` positions consumed, routed as
    ``route_ids`` says. Every request is padded at its end to the longest
    the traffic allows (causal, so padding changes no earlier value), so
    that one program serves them all."""
    ids, start = llama_decoder.served_positions(prompt, tokens)
    s = ids.shape[1]
    pad = cell.traffic["prompt_max"] + cell.traffic["output_max"] - s
    ids = np.pad(ids, ((0, 0), (0, pad)))
    route = np.pad(route_ids, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kw = {} if dtype is None else {"dtype": dtype}
    logits, biased = ref.forward(params, cell.config, ids, held(cell.config),
                                 route, **kw)
    return (np.asarray(logits[:, start:start + tokens.shape[1]]),
            np.asarray(biased[:, :, :s]))


def disagreements(chosen: np.ndarray, biased: np.ndarray, k: int) -> int:
    """(token, layer) pairs whose ``chosen`` top-``k`` set differs from
    the one ``biased`` gives, where that one's margin exceeds DELTA."""
    own, margin = ref.top_k_sets(biased, k)
    differ = (np.sort(chosen, axis=-1) != own).any(axis=-1)
    return int((differ & (margin > DELTA)).sum())


def check(st: State, win: Window) -> list[Check]:
    """Every finished request, against the float32 reference."""
    done = win.data["requests"]
    if not done:
        return []
    params = reference_params(st.cell)
    k = st.cell.config["num_experts_per_tok"]
    widest, disagree = 0.0, 0
    for prompt, tokens, routing in done:
        logits, biased = reference(params, st.cell, prompt, tokens,
                                   routing["ids"])
        widest = max(widest,
                     float(llama_decoder.logit_gaps(logits, tokens).max()))
        disagree += disagreements(routing["ids"], biased, k)
    return [Check("served_logit_gap", widest, GAP_LIMIT),
            Check("routing_disagreements", disagree, 0)]


def control(st: State, win: Window) -> list[Check]:
    """The reference in the program's place, one precision lower
    (bfloat16), on the same prompts, served tokens and routing: the gap
    of the token it puts first, and its own routing against the float32
    reference's. Beside them, the largest difference between the float32
    reference's biased scores and the bfloat16 reference's
    (``router_score_diff``) and the program's
    (``program_router_score_diff``): the readings DELTA is set from."""
    import jax.numpy as jnp

    params = reference_params(st.cell)
    k = st.cell.config["num_experts_per_tok"]
    widest, disagree, diff_low, diff_program = 0.0, 0, 0.0, 0.0
    for prompt, tokens, routing in win.data["requests"]:
        exact, biased = reference(params, st.cell, prompt, tokens,
                                  routing["ids"])
        low, biased_low = reference(params, st.cell, prompt, tokens,
                                    routing["ids"], jnp.bfloat16)
        widest = max(widest, float(
            llama_decoder.logit_gaps(exact, low.argmax(-1)).max()))
        disagree += disagreements(ref.top_k_sets(biased_low, k)[0], biased, k)
        diff_low = max(diff_low, float(np.abs(biased_low - biased).max()))
        diff_program = max(diff_program, float(
            np.abs(routing["scores"] - biased).max()))
    return [Check("served_logit_gap", widest, GAP_LIMIT),
            Check("routing_disagreements", disagree, 0),
            Check("router_score_diff", diff_low, DELTA),
            Check("program_router_score_diff", diff_program, DELTA)]
