"""Greedy decode of a stored fine-tune, straight from its compressed frames.

Traffic parameters (``bench/traffic/<mix>.json``):

- ``batch``: sequences per request; one request's sequences share its
  lengths, as a scheduler that groups requests by length would batch them;
- ``prompt_median``, ``output_median``, ``sigma``: each request's prompt
  and output lengths are drawn from the seed, independently, from
  log-normals with these medians (in the source's tokens) and this
  log-standard deviation;
- ``scale``: both lengths are divided by it, so that a window holds tens
  of requests; ``prompt_max`` and ``output_max`` cap them after scaling;
- ``bits``: the delta bits the model is loaded at (8: int8 deltas, 4:
  nibble-packed int4 deltas);
- ``ft_rel_std``: the fine-tune's perturbation (see ``ingest``).

Set-up makes the base and one fine-tune on the device from the seed,
saves both through ``NeurStore.save``, loads the fine-tune compressed
(``load_model(bits=...)``, ``CompressedModel``) and serves one warm
request. The window serves requests back to back, each with lengths and
prompt ids drawn from the seed, and closes when the last request that
started inside it has finished. The program consumes a prompt one
position per forward, as it generates, so a prompt token costs what a
generated one does; the window's rate counts both. The check runs the
plain float32 reference over every finished request's prompt and served
tokens, from weights it makes again from the seed, and reads how far a
served token's logit lies below the reference's best.
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager

import numpy as np

from bench.harness import weights
from bench.harness.cell import Check, Window
from bench.harness.roofline import dequant_matmul
from bench.reference import llama_decoder as ref

#: Widest gap, in logits, by which a served token may lie below the
#: reference's best token (see PERF.md for the readings it was set from).
GAP_LIMIT = 0.014
KERNELS = ("dequant_matmul", "dequant_matmul_int4")


@dataclasses.dataclass
class State:
    cell: object
    spec: object
    store: object
    lm: object
    model: object
    provider: object
    rng: np.random.Generator


def decoder_spec(config: dict):
    from repro.launch.compressed_serve import DecoderSpec

    return DecoderSpec(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"])


#: The reference runs each request padded at its end to a multiple of
#: this many positions (causal, so padding changes no earlier logit), so
#: that a few programs serve every length.
REF_BUCKET = 64


def lengths(rng: np.random.Generator, traffic: dict) -> tuple[int, int]:
    """``(prompt, output)`` lengths of one request, drawn from the seed."""
    z = rng.standard_normal(2)
    out = []
    for key, z_i, low in (("prompt", z[0], 1), ("output", z[1], 1)):
        median = traffic[f"{key}_median"] / traffic["scale"]
        n = round(median * float(np.exp(traffic["sigma"] * z_i)))
        out.append(int(min(max(n, low), traffic[f"{key}_max"])))
    return out[0], out[1]


def request(rng: np.random.Generator, cell) -> tuple[np.ndarray, int]:
    """``(prompt ids (batch, P), output length)`` of the next request."""
    p, o = lengths(rng, cell.traffic)
    ids = rng.integers(0, cell.config["vocab_size"],
                       (cell.traffic["batch"], p), dtype=np.int64)
    return ids, o


def setup(cell) -> State:
    from repro.core.compressed import CompressedModel
    from repro.launch.compressed_serve import decoder_architecture, greedy_decode
    from repro.store import NeurStore, SaveRequest

    tr, s = cell.traffic, cell.config["store"]
    spec = decoder_spec(cell.config)
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
    # whatever the lengths, and the host path compiles nothing.
    warm = rng.integers(0, cell.config["vocab_size"], (tr["batch"], 2))
    greedy_decode(model, spec, warm, 2)
    return State(cell, spec, store, lm, model, model, rng)


def window(st: State, seconds: float, mark=None) -> Window:
    from repro.launch.compressed_serve import greedy_decode

    done, errors = [], []
    attempted = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        attempted += 1
        prompt, steps = request(st.rng, st.cell)
        try:
            if mark:
                with mark("bench.request"):
                    tokens = greedy_decode(st.provider, st.spec, prompt, steps)
            else:
                tokens = greedy_decode(st.provider, st.spec, prompt, steps)
        except Exception as exc:  # noqa: BLE001 — counted, not fatal
            errors.append(repr(exc))
            continue
        done.append((prompt, np.asarray(tokens)))
    t1 = time.perf_counter()
    data = {"requests": done,
            "tokens": sum(p.size + t.size for p, t in done),
            "forwards": sum(p.shape[1] - 1 + t.shape[1] for p, t in done)}
    return Window(t0, t1, attempted, len(errors), errors, data)


def end_to_end(st: State, win: Window) -> dict:
    """Prompt tokens consumed and tokens generated, over the window."""
    if not win.data["requests"]:
        return {}
    return {"decode_tok_s": win.data["tokens"] / win.seconds}


class TimedProvider:
    """The model under a benchmark-side span: each ``matmul`` is timed on
    the host clock, marked on the profiler's, and its logical shape
    recorded."""

    def __init__(self, model, calls: list, mark):
        self.model = model
        self.calls = calls
        self.mark = mark

    def matmul(self, x, name):
        w = self.model.weight(name)
        t = time.perf_counter()
        with self.mark("bench.matmul"):
            y = self.model.matmul(x, name)
        seconds = time.perf_counter() - t
        m = int(np.shape(x)[0])
        o, nbytes = dequant_matmul(m, w.k, w.n, 0.5 if w.packed else 1.0)
        self.calls.append({"kernel": "dequant_matmul", "m": m, "k": w.k,
                           "n": w.n, "packed": w.packed, "seconds": seconds,
                           "eligible": w.k * w.n >= self.model.min_elems,
                           "ops": o, "bytes": nbytes})
        return y

    def gather_rows(self, name, ids):
        return self.model.gather_rows(name, ids)

    def vector(self, name):
        return self.model.vector(name)


def kernel_launches() -> float:
    """Launches of the dequant kernels so far (the program's counter)."""
    from repro.obs.metrics import default_registry

    reg = default_registry()
    return sum(reg.sample_value("neurstore_kernel_calls_total",
                                {"kernel": k, "route": r}) or 0
               for k in KERNELS for r in ("tpu", "interpret"))


@contextmanager
def recorder(st: State):
    """Each call is marked offloaded where the program's launch counter
    grew by exactly the calls its size gate admits; where the counts
    disagree no call is, and the kernel's roofline reads nothing."""
    from bench.harness.cell import mark

    calls: list[dict] = []
    st.provider = TimedProvider(st.model, calls, mark)
    before = kernel_launches()
    try:
        yield calls
    finally:
        st.provider = st.model
        launched = kernel_launches() - before
        admitted = sum(c["eligible"] for c in calls)
        for c in calls:
            c["offloaded"] = c["eligible"] and launched == admitted


def release(st: State) -> None:
    st.lm.close()
    st.store.close()
    st.model = st.provider = st.lm = st.store = None


def reference_params(cell) -> dict:
    """The fine-tune's float32 weights, made again from the seed."""
    base, (ft,) = weights.make_models_on_device(
        cell.config, cell.seed, 1, cell.traffic["ft_rel_std"])
    del base
    return ft


def reference_logits(params: dict, config: dict, prompt, tokens,
                     dtype=None) -> np.ndarray:
    """The reference's logits ``(B, T, V)`` at the positions that chose
    the ``T`` served tokens."""
    ids, start = ref.served_positions(prompt, tokens)
    pad = -ids.shape[1] % REF_BUCKET
    ids = np.pad(ids, ((0, 0), (0, pad)))
    kw = {} if dtype is None else {"dtype": dtype}
    return ref.forward(params, config, ids, **kw)[:, start:start + tokens.shape[1]]


def check(st: State, win: Window) -> list[Check]:
    """Every finished request, against the float32 reference."""
    done = win.data["requests"]
    if not done:
        return []
    params = reference_params(st.cell)
    widest = 0.0
    for prompt, tokens in done:
        logits = reference_logits(params, st.cell.config, prompt, tokens)
        widest = max(widest, float(ref.logit_gaps(logits, tokens).max()))
    return [Check("served_logit_gap", widest, GAP_LIMIT)]


def control(st: State, win: Window) -> list[Check]:
    """The reference in the program's place, one precision lower
    (bfloat16): at each position of the same prompts and served tokens,
    the gap of the token it puts first."""
    import jax.numpy as jnp

    params = reference_params(st.cell)
    widest = 0.0
    for prompt, tokens in win.data["requests"]:
        exact = reference_logits(params, st.cell.config, prompt, tokens)
        low = reference_logits(params, st.cell.config, prompt, tokens,
                               jnp.bfloat16)
        widest = max(widest, float(ref.logit_gaps(exact, low.argmax(-1)).max()))
    return [Check("served_logit_gap", widest, GAP_LIMIT)]
