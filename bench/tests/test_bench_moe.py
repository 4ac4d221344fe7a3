"""The Moonlight-16B-A3B configuration's program against its plain
reference, at tiny widths on the CPU: a stored fine-tune served from its
compressed frames (prefill one position a forward, then decode) against
the reference's full forward with the program's routing forced; the
expert-parallel shares against the uncut layer; the new per-layer
readers on span trees built by hand."""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import decode_moe
from bench.harness import cell, weights
from bench.harness.cell import Window
from bench.harness.metrics_ctx import LayerContext
from bench.harness.store import open_store
from bench.reference import llama_decoder
from bench.reference import moonlight_decoder as ref
from bench.tests.test_bench_program_spans import Span
from bench.tests.tiny import shrink

CELL = "decode_moe.moonlight-16b-a3b"
CONFIG = cell.ROOT / "bench/configs/moonlight-16b-a3b.json"


def tiny_config(n_layers: int = 2, cfg: dict | None = None) -> dict:
    """The configuration (or ``cfg``, a shrunk copy of it) at its tiny
    widths, cut to the dense layer and ``n_layers - 1`` MoE layers."""
    cfg = cfg or shrink(json.loads(CONFIG.read_text()))
    keep = {f"model.layers.{i}." for i in range(n_layers)}

    def kept(entry):
        name = entry["tensors"][0][0] if isinstance(entry, dict) else entry[0]
        return not name.startswith("model.layers.") or any(
            name.startswith(k) for k in keep)

    cfg["tensors"] = [e for e in cfg["tensors"] if kept(e)]
    cfg["num_hidden_layers"] = n_layers
    return cfg


#: Largest |program - reference| logit at the served positions, with the
#: program's routing forced, by the delta bits the model is loaded at.
#: Each weight is served within its delta's truncation to those bits (a
#: few 1e-6 of a 0.02-scale matrix at 8 bits; the correction biases,
#: which a save stores as deltas on one another, within about 1e-3);
#: 4 bits truncate 16 times as coarsely. Here the logits are of order
#: 0.3 and the readings were 2.7e-4 and 3.8e-3: the limits leave a
#: little over twice that.
LOGIT_TOL = {8: 6e-4, 4: 8e-3}


@pytest.mark.parametrize("bits", [8, 4])
def test_served_logits_match_reference_with_routing_forced(bits, tmp_path):
    from repro.core.compressed import CompressedModel
    from repro.launch import compressed_serve as cs
    from repro.store import SaveRequest

    cfg = tiny_config()
    spec = decode_moe.decoder_spec(cfg)
    base, (ft,) = weights.make_models(cfg, 2**32 + 15, 1, 1e-3)
    store = open_store(cfg, str(tmp_path / "store"))
    arch = cs.decoder_architecture(spec)
    store.save(SaveRequest("base", base, architecture=arch))
    store.save(SaveRequest("ft", ft, architecture=arch))
    lm = store.engine.load_model("ft", bits=bits)
    model = CompressedModel(lm)
    prompt = np.random.default_rng(bits).integers(0, spec.vocab_size, (8, 5))
    tokens, logits, routing = cs.greedy_decode(
        model, spec, prompt, 4, return_logits=True, return_routing=True)
    model.close()
    store.close()
    ids, start = llama_decoder.served_positions(prompt, tokens)
    want, biased = ref.forward({k: jnp.asarray(v) for k, v in ft.items()},
                               cfg, ids, decode_moe.held(cfg), routing["ids"])
    want = np.asarray(want)[:, start:]
    np.testing.assert_allclose(logits, want, rtol=0, atol=LOGIT_TOL[bits])
    # The program's own choice is the reference's wherever the
    # reference's margin leaves room for the program's score error.
    assert decode_moe.disagreements(routing["ids"], np.asarray(biased),
                                    spec.top_k) == 0


def test_expert_shares_add_up_to_the_uncut_layer():
    """Expert parallelism over 8 chips: each chip's 8 of the 64 routed
    experts give its part of the routed result; the parts, with the
    shared experts counted once, add up to the uncut layer. The program's
    layer holding experts 0 to 7 gives the first share."""
    from repro.launch import compressed_serve as cs

    rng = np.random.default_rng(64)
    d, width, n_exp = 32, 16, 64
    pre = "model.layers.1.mlp."
    params = {pre + "gate.weight": rng.normal(0, 0.3, (d, n_exp)),
              pre + "gate.e_score_correction_bias": rng.normal(0, 0.02, n_exp)}
    for group in [f"experts.{e}." for e in range(n_exp)] + ["shared_experts."]:
        w = 2 * width if group == "shared_experts." else width
        params[pre + group + "gate_proj.weight"] = rng.normal(0, 0.2, (d, w))
        params[pre + group + "up_proj.weight"] = rng.normal(0, 0.2, (d, w))
        params[pre + group + "down_proj.weight"] = rng.normal(0, 0.2, (w, d))
    params = {k: v.astype(np.float32) for k, v in params.items()}
    params["model.layers.1.post_attention_layernorm.weight"] = np.ones(
        d, np.float32)
    x = rng.normal(0, 1, (40, d)).astype(np.float32)
    xn = cs._rms_norm(x, params["model.layers.1.post_attention_layernorm.weight"],
                      1e-5)
    jp = {k: jnp.asarray(v) for k, v in params.items()}

    def layer(held):
        return [np.asarray(a) for a in ref.moe(jp, pre, jnp.asarray(xn), held,
                                               6, 2.446, True)]

    routed, shared, _ = layer(tuple(range(n_exp)))
    shares = [layer(tuple(range(8 * s, 8 * s + 8))) for s in range(8)]
    for _, sh, _ in shares:
        np.testing.assert_array_equal(sh, shared)
    np.testing.assert_allclose(sum(r for r, _, _ in shares) + shared,
                               routed + shared, rtol=1e-5, atol=1e-6)

    spec = cs.DeepseekV3Spec(
        d_model=d, n_heads=1, n_layers=2, n_experts=n_exp,
        held_experts=tuple(range(8)), top_k=6, routed_scaling_factor=2.446)
    got = cs._moe_block(_Float(params), 1, x, spec, None)
    np.testing.assert_allclose(got, shares[0][0] + shared, rtol=1e-5,
                               atol=1e-6)


class _Float:
    def __init__(self, t):
        self.t = t

    def matmul(self, x, name):
        return x @ self.t[name]

    def expert_matmul(self, x, names, rows=None):
        return np.stack([(x[i] if x.ndim == 3 else x) @ self.t[n]
                         for i, n in enumerate(names)])

    def vector(self, name):
        return self.t[name]


def moe_roots():
    """Two forwards of one request with the MoE spans; a forward outside
    any request."""
    def fwd(seconds, mla, route, experts):
        return Span("forward", seconds,
                    Span("mla", mla, Span("dequant_matmul", mla / 2)),
                    Span("route", route), Span("experts", experts,
                                               Span("dequant_matmul_group",
                                                    experts / 2)))
    req = Span("generate", 1.0, fwd(0.2, 0.05, 0.01, 0.08),
               fwd(0.1, 0.03, 0.002, 0.04))
    stray = Span("other", 1.0, fwd(1.0, 1.0, 1.0, 1.0))
    return [req, stray]


NEW = {"mla_ms_per_forward.moe": 40.0, "route_ms_per_forward.moe": 6.0,
       "expert_ms_per_forward.moe": 60.0}


def _read(name, roots):
    win = Window(0.0, 1.0, 1, 0, [], {})
    ctx = LayerContext(win=win, calls=None, summary=None, peak=None,
                       state=None)
    ctx.roots = roots
    return cell.load_module(cell.reader_path(name)).read(ctx, name)


@pytest.mark.parametrize("name", sorted(NEW))
def test_moe_span_readers(name):
    assert _read(name, moe_roots()) == pytest.approx(NEW[name])
    assert _read(name, []) is None
    # The llama decode's trees (and the parent program's): no such spans.
    llama = [Span("generate", 1.0, Span("forward", 1.0,
                                        Span("dequant_matmul", 0.5)))]
    assert _read(name, llama) is None


def test_moe_metrics_are_declared_for_the_cell_alone():
    bench = cell.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    moe = [n for n in per_layer if n.endswith(".moe")]
    assert len(moe) == 9
    for name in moe:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "decode_tok_s"
        assert cell.reader_path(name).exists()
    (tok_s,) = [m for m in bench["end_to_end"] if m["name"] == "decode_tok_s"]
    assert tok_s["workloads"][-1] == CELL
    (cfg,) = [c for c in bench["configs"] if c["name"] == "moonlight-16b-a3b"]
    assert set(cfg["reduced"]) == set(json.loads(CONFIG.read_text())["reduced"])
