"""Compressed decode of a DeepSeek-V3-family decoder: latent attention and
expert-parallel MoE layers, with the held experts' projections served
through one grouped seam call each (``kernels.ops.dequant_matmul_group``).

The grouped seam against per-weight ``dequant_matmul_auto`` calls, on the
interpret and host routes; the kernel route's residency; rows a layer
routes to no held expert; the span tree and the assignment counter of a
decode off the store; a prompt consumed in chunks against one consumed a
position a forward, for both block families, and the chunks' filler
positions counted nowhere; and the spec's catalog payload.
"""

import numpy as np
import pytest

import repro.launch.compressed_serve as cs
from repro.core import CompressedModel, StorageEngine
from repro.kernels import ops
from repro.obs.metrics import default_registry
from repro.obs.trace import recent_traces

RNG = np.random.default_rng(15)
SPEC = cs.DeepseekV3Spec(
    d_model=64, n_heads=4, n_layers=3, vocab_size=96, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, first_k_dense=1,
    n_experts=16, held_experts=(0, 1, 2, 3), top_k=4,
    routed_scaling_factor=2.5, norm_topk_prob=True)
D_FF, MOE_FF = 96, 32


def deepseek_tensors(spec: cs.DeepseekV3Spec, seed: int) -> dict:
    """Seeded weights of ``spec``, HF deepseek_v3 names, (in, out)."""
    rng = np.random.default_rng(seed)
    d, h = spec.d_model, spec.n_heads
    qk = spec.qk_nope_head_dim + spec.qk_rope_head_dim

    def w(*shape):
        return rng.normal(0.0, 0.1, shape).astype(np.float32)

    def swiglu(pre, width):
        return {pre + "gate_proj.weight": w(d, width),
                pre + "up_proj.weight": w(d, width),
                pre + "down_proj.weight": w(width, d)}

    t = {"model.embed_tokens.weight": w(spec.vocab_size, d)}
    for i in range(spec.n_layers):
        pre = f"model.layers.{i}."
        att = pre + "self_attn."
        t[pre + "input_layernorm.weight"] = np.ones(d, np.float32)
        t[att + "q_proj.weight"] = w(d, h * qk)
        t[att + "kv_a_proj_with_mqa.weight"] = w(
            d, spec.kv_lora_rank + spec.qk_rope_head_dim)
        t[att + "kv_a_layernorm.weight"] = np.ones(spec.kv_lora_rank,
                                                   np.float32)
        t[att + "kv_b_proj.weight"] = w(
            spec.kv_lora_rank, h * (spec.qk_nope_head_dim + spec.v_head_dim))
        t[att + "o_proj.weight"] = w(h * spec.v_head_dim, d)
        t[pre + "post_attention_layernorm.weight"] = np.ones(d, np.float32)
        if i < spec.first_k_dense:
            t.update(swiglu(pre + "mlp.", D_FF))
            continue
        t[pre + "mlp.gate.weight"] = w(d, spec.n_experts)
        t[pre + "mlp.gate.e_score_correction_bias"] = w(spec.n_experts)
        for e in spec.held_experts:
            t.update(swiglu(f"{pre}mlp.experts.{e}.", MOE_FF))
        t.update(swiglu(pre + "mlp.shared_experts.", 2 * MOE_FF))
    t["model.norm.weight"] = np.ones(d, np.float32)
    t["lm_head.weight"] = w(d, spec.vocab_size)
    return t


@pytest.fixture
def moe_engine(tmp_path):
    eng = StorageEngine(tmp_path)
    eng.save_model("moe", cs.decoder_architecture(SPEC),
                   deepseek_tensors(SPEC, seed=4))
    yield eng
    eng.close()


def _group_operands(packed: bool, n_w: int = 3, k: int = 72, n: int = 40):
    ops_, flags = [], []
    for i in range(n_w):
        base = RNG.integers(-128, 128, (k, n)).astype(np.int8)
        if packed:
            delta = ops.pack_int4(RNG.integers(0, 16, (k, n)).astype(np.uint8))
            ops_.append((base, 0.02 + i * 1e-3, -3.0, delta, 5e-4, 8.0))
        else:
            delta = RNG.integers(-128, 128, (k, n)).astype(np.int8)
            ops_.append((base, 0.013, -11.0 + i, delta, 3.1e-4, -64.0))
        flags.append(packed)
    return ops_, flags


@pytest.mark.parametrize("per_weight", [False, True])
@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("force", ["kernel", "numpy"])
def test_group_seam_equals_per_weight_calls(force, packed, per_weight):
    """Interpret and host routes: the grouped call returns exactly what a
    ``dequant_matmul_auto`` call per weight returns, for a shared
    activation block and for one block a weight."""
    operands, flags = _group_operands(packed)
    x = RNG.normal(0, 1, ((3, 5, 72) if per_weight else (5, 72))).astype(
        np.float32)
    got = ops.dequant_matmul_group(x, operands, flags, force=force,
                                   scratch={}, scratches=[{}, {}, {}])
    want = np.stack([
        ops.dequant_matmul_auto(x[i] if per_weight else x, *o, packed=p,
                                force=force, scratch={})
        for i, (o, p) in enumerate(zip(operands, flags))])
    assert got.shape == (3, 5, 40) and got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_group_seam_gate_counts_and_staging():
    """The gate is the group's elements; the kernel route stages every
    weight's codes once in the group's scratch, counts a launch and a
    staged or reused per weight, and keeps no host copy."""
    reg = default_registry()

    def count(name, labels):
        return reg.sample_value(name, labels) or 0

    operands, flags = _group_operands(False)
    x = RNG.normal(0, 1, (4, 72)).astype(np.float32)
    host_before = count("neurstore_kernel_calls_total",
                        {"kernel": "dequant_matmul", "route": "host"})
    ops.dequant_matmul_group(x, operands, flags)  # CPU: declined
    assert count("neurstore_kernel_calls_total",
                 {"kernel": "dequant_matmul", "route": "host"}) == host_before + 3
    scratch, scratches = {}, [{}, {}, {}]
    res = {e: count("neurstore_operand_residency_total",
                    {"kernel": "dequant_matmul", "event": e})
           for e in ("staged", "reused")}
    first = ops.dequant_matmul_group(x, operands, flags, force="kernel",
                                     scratch=scratch, scratches=scratches)
    staged = scratch["device"]
    again = ops.dequant_matmul_group(x, operands, flags, force="kernel",
                                     scratch=scratch, scratches=scratches)
    assert scratch["device"] is staged and len(staged) == 3
    assert staged[0][0].shape == (128, 128)
    assert scratches == [{}, {}, {}]  # no pre-scaled float32 copies
    np.testing.assert_array_equal(first, again)
    for event in ("staged", "reused"):
        assert count("neurstore_operand_residency_total",
                     {"kernel": "dequant_matmul", "event": event}) \
            == res[event] + 3


def test_group_seam_rejects_mixed_shapes():
    operands, flags = _group_operands(False, n_w=2)
    small = _group_operands(False, n_w=1, k=72, n=24)[0]
    with pytest.raises(ValueError):
        ops.dequant_matmul_group(np.zeros((1, 72), np.float32),
                                 operands + small, flags + [False])


def test_compressed_moe_decode_kernel_route_keeps_no_host_copy(
        moe_engine, monkeypatch):
    """A decode with every call forced onto the kernel: nothing is
    materialized, the held experts run grouped, their weights keep no
    ``scratch["cpu"]``, and each (layer, projection) group holds its
    staged codes."""
    from repro.core.loader import LoadedModel

    def no_materialize(self):
        raise AssertionError("materialize() during compressed serving")

    monkeypatch.setattr(LoadedModel, "materialize", no_materialize)
    lm = moe_engine.load_model("moe", bits=8)
    model = CompressedModel(lm, force="kernel")
    cs.greedy_decode(model, SPEC, np.array([[1, 2], [3, 4]]), 2)
    experts = [model.weight(n) for n in model.kernel_served if ".experts." in n]
    assert len(experts) == 3 * len(SPEC.held_experts) * (
        SPEC.n_layers - SPEC.first_k_dense)
    assert all("cpu" not in w.scratch for w in experts)
    assert len(model._groups) == 3 * (SPEC.n_layers - SPEC.first_k_dense)
    assert all(len(g["device"]) == len(SPEC.held_experts)
               for g in model._groups.values())
    model.close()
    assert all(g == {} for g in model._groups.values())


class FloatProvider:
    """Exact float32 weights behind the provider interface."""

    def __init__(self, tensors):
        self.t = tensors

    def matmul(self, x, name):
        return np.asarray(x, np.float32) @ self.t[name]

    def expert_matmul(self, x, names, rows=None):
        return np.stack([(x[i] if x.ndim == 3 else x) @ self.t[n]
                         for i, n in enumerate(names)])

    def gather_rows(self, name, ids):
        return self.t[name][np.asarray(ids)]

    def vector(self, name):
        return self.t[name]


def test_rows_routed_to_no_held_expert_get_exactly_the_shared_experts():
    """The held experts run over every row; a row routed to none of them
    is combined with weight 0 throughout, so the layer gives it exactly
    the shared experts' output."""
    tensors = deepseek_tensors(SPEC, seed=5)
    p = FloatProvider(tensors)
    x = RNG.normal(0, 1, (64, SPEC.d_model)).astype(np.float32)
    routing: list = []
    out = cs._moe_block(p, 1, x, SPEC, routing)
    ids, _ = routing[0]
    xn = cs._rms_norm(x, tensors["model.layers.1.post_attention_layernorm.weight"],
                      SPEC.norm_eps)
    shared = cs._swiglu(p, xn, "model.layers.1.mlp.shared_experts.")
    none = ~np.isin(ids, SPEC.held_experts).any(axis=1)
    assert 0 < none.sum() < len(none)
    np.testing.assert_array_equal(out[none], shared[none])
    assert not np.array_equal(out[~none], shared[~none])


def test_moe_decode_spans_and_assignment_counter(moe_engine):
    """One ``forward`` a prompt chunk and a later token, > ``mla`` a layer
    (its seam calls under it), ``route`` and ``experts`` a MoE layer; the
    router's matmul under ``route``, three grouped calls under
    ``experts``. Every token's top-k choice is counted as held or
    absent."""
    reg = default_registry()

    def count(placement):
        return reg.sample_value("neurstore_moe_assignments_total",
                                {"placement": placement}) or 0

    before = {p: count(p) for p in ("held", "absent")}
    lm = moe_engine.load_model("moe", bits=8)
    model = CompressedModel(lm)
    prompt = np.array([[1, 2, 3], [4, 5, 6]])
    tokens, routing = cs.greedy_decode(model, SPEC, prompt, 2,
                                       return_routing=True)
    gen = recent_traces()[-1]
    model.close()
    assert gen.name == "generate"
    forwards = gen.children
    n_moe = SPEC.n_layers - SPEC.first_k_dense
    # The 3-position prompt is one chunk, then one forward for the
    # second token.
    assert len(forwards) == 1 + 2 - 1
    assert [f.attrs for f in forwards] == [
        {"positions": 3, "padded": cs.PREFILL_ROWS // 2 - 3},
        {"positions": 1, "padded": 0}]
    for f in forwards:
        names = [c.name for c in f.children]
        assert names.count("mla") == SPEC.n_layers
        assert names.count("route") == names.count("experts") == n_moe
        for c in f.children:
            inner = [s.name for s in c.children]
            if c.name == "mla":
                assert inner == ["dequant_matmul"] * 4
            elif c.name == "route":
                assert inner == ["dequant_matmul"]
            elif c.name == "experts":
                assert inner == ["dequant_matmul_group"] * 3
                assert all(s.attrs["experts"] == len(SPEC.held_experts)
                           and len(s.attrs["routed_rows"]) == s.attrs["experts"]
                           for s in c.children)
    ids = routing["ids"]
    assert ids.shape == (n_moe, 2, 3 - 1 + 2, SPEC.top_k)
    assert routing["scores"].shape == (n_moe, 2, 3 - 1 + 2, SPEC.n_experts)
    held = int(np.isin(ids, SPEC.held_experts).sum())
    assert count("held") - before["held"] == held
    assert count("absent") - before["absent"] == ids.size - held


LLAMA = cs.DecoderSpec(d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      n_layers=2, vocab_size=96)
CHUNK = 4


@pytest.fixture(scope="module")
def two_decoders(tmp_path_factory):
    """A stored decoder of each block family, under its spec's kind."""
    eng = StorageEngine(tmp_path_factory.mktemp("decoders"))
    cs.save_decoder(eng, LLAMA.kind, LLAMA, seed=3)
    eng.save_model(SPEC.kind, cs.decoder_architecture(SPEC),
                   deepseek_tensors(SPEC, seed=4))
    yield eng
    eng.close()


@pytest.mark.parametrize("p", [1, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK + 1])
@pytest.mark.parametrize("source", ["compressed", "materialized"])
@pytest.mark.parametrize("spec", [LLAMA, SPEC], ids=["gqa", "mla_moe"])
def test_chunked_prefill_equals_one_position_prefill(two_decoders, spec,
                                                     source, p, monkeypatch):
    """A prompt consumed in chunks of CHUNK positions (the last padded)
    gives the tokens, logits and routing of the same prompt consumed one
    position a forward, at every prompt length about the chunk's."""
    lm = two_decoders.load_model(spec.kind, bits=8)
    provider = (CompressedModel(lm, force="numpy") if source == "compressed"
                else cs.MaterializedProvider(lm))
    prompt = np.random.default_rng(p).integers(0, spec.vocab_size, (2, p))
    moe = spec is SPEC

    def decode(rows):
        monkeypatch.setattr(cs, "PREFILL_ROWS", rows)
        return cs.greedy_decode(provider, spec, prompt, 3, return_logits=True,
                                return_routing=moe)

    chunked, single = decode(2 * CHUNK), decode(1)
    provider.close()
    np.testing.assert_array_equal(chunked[0], single[0])
    np.testing.assert_allclose(chunked[1], single[1], rtol=0, atol=1e-5)
    if moe:
        n_moe = SPEC.n_layers - SPEC.first_k_dense
        for key, width in (("ids", SPEC.top_k), ("scores", SPEC.n_experts)):
            assert chunked[2][key].shape == single[2][key].shape == (
                n_moe, 2, p - 1 + 3, width)
        np.testing.assert_array_equal(chunked[2]["ids"], single[2]["ids"])
        # Scores of order 1 in float32: a few ulps of reassociation.
        np.testing.assert_allclose(chunked[2]["scores"], single[2]["scores"],
                                   rtol=0, atol=1e-6)


def test_chunk_filler_is_neither_routed_nor_counted(two_decoders, monkeypatch):
    """The filler that pads a prompt's last chunk reaches no assignment
    count and no expert's ``routed_rows``; the position counter splits a
    request into its prompt, its fed-back tokens and the filler."""
    reg = default_registry()

    def count(name, labels):
        return reg.sample_value(name, labels) or 0

    def counts():
        return ({ph: count("neurstore_decode_positions_total", {"phase": ph})
                 for ph in ("prefill", "decode", "padding")},
                {pl: count("neurstore_moe_assignments_total", {"placement": pl})
                 for pl in ("held", "absent")})

    monkeypatch.setattr(cs, "PREFILL_ROWS", 2 * CHUNK)
    b, p, steps = 2, CHUNK + 1, 3
    prompt = np.arange(b * p).reshape(b, p) % SPEC.vocab_size
    lm = two_decoders.load_model(SPEC.kind, bits=8)
    model = CompressedModel(lm)
    positions, assignments = counts()
    _, routing = cs.greedy_decode(model, SPEC, prompt, steps,
                                  return_routing=True)
    gen = recent_traces()[-1]
    model.close()
    after_positions, after_assignments = counts()
    assert {ph: after_positions[ph] - positions[ph] for ph in positions} == {
        "prefill": b * p, "decode": b * (steps - 1),
        "padding": b * (-(-p // CHUNK) * CHUNK - p)}
    ids = routing["ids"]  # (L_moe, b, p - 1 + steps, top_k): real positions
    held = int(np.isin(ids, SPEC.held_experts).sum())
    assert after_assignments["held"] - assignments["held"] == held
    assert after_assignments["absent"] - assignments["absent"] == \
        ids.size - held
    # The second chunk holds position CHUNK and CHUNK - 1 filler positions
    # a sequence: its experts see only position CHUNK's rows routed.
    padded = gen.children[1]
    assert padded.attrs == {"positions": 1, "padded": CHUNK - 1}
    layers = [c for c in padded.children if c.name == "experts"]
    assert len(layers) == ids.shape[0]
    for layer, chosen in zip(layers, ids[:, :, CHUNK]):
        want = [int((chosen == e).any(axis=1).sum()) for e in SPEC.held_experts]
        for call in layer.children:
            assert call.attrs["m"] == b * CHUNK
            assert call.attrs["routed_rows"] == want


def test_return_routing_needs_moe_layers():
    with pytest.raises(ValueError):
        cs.greedy_decode(FloatProvider({}), cs.DecoderSpec(), np.zeros((1, 1)),
                         1, return_routing=True)


def test_architecture_payload_round_trips_each_spec():
    llama = cs.DecoderSpec(d_model=64, n_heads=4, n_kv_heads=2)
    assert cs.decoder_architecture(llama) == {
        "kind": "llama3_decoder", "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 512, "n_layers": 2, "vocab_size": 512,
        "rope_theta": 10000.0, "norm_eps": 1e-5}
    for spec in (llama, SPEC):
        arch = cs.decoder_architecture(spec)
        # A catalog stores JSON: the held experts come back as a list.
        arch = {k: list(v) if isinstance(v, tuple) else v
                for k, v in arch.items()}
        assert cs.spec_from_architecture(arch) == spec
