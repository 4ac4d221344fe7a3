"""Compressed-domain serving benchmark: decode off the store vs materialize.

Measures a full serving *session* — ``load_model(bits=...)`` → provider
construction → greedy decode — for the same stored llama3-shaped decoder
through two providers:

* **compressed** — :class:`repro.core.CompressedModel`: every large
  matmul consumes int8 base codes + quantized deltas through the
  ``dequant_matmul_auto`` seam; the float weight is never materialized;
* **materialized** — ``LoadedModel.materialize()`` first, then plain
  float32 gemms (the materialize-then-serve baseline).

Each session opens a **fresh** ``StorageEngine`` on the same on-disk
store, so neither provider inherits the other's decoded buffer-pool
payloads (the warm-pool variant was measured and biases the comparison).
Sessions are interleaved compressed/materialized, best-of-N; jax backend
discovery is triggered once up front so plugin init is not charged to
whichever session runs first.

Two phases: **smoke** (tiny decoder, short decode — the CI scale) and
**full** (512-wide, 4 layers). A full run records both; ``--smoke``
records only the smoke phase. Each phase also runs one ``bits=4``
session pair to report the int4-packed bytes-per-weight (1.5 vs 2.0)
and check compressed/materialized token parity at that precision.

Gates (``benchmarks/perf_gate.py``): per phase, ``bytes_ratio``
(compressed ÷ materialized operand traffic: the ``operand_bytes`` of the
compressed session's seam spans over the materialized provider's own
tally of float32 weights and gathered rows) strictly < 1.0, and
``throughput_ratio`` (compressed ÷ materialized session tokens/s) ≥ 0.8
— on CPU the decomposed gemm folds to a single combined-operand gemm in
steady state, and the compressed session skips the up-front float64
dequantization of every weight, so losing 20 % end-to-end is a real
regression, not runner noise.

Run: ``PYTHONPATH=src python benchmarks/compressed_serve_bench.py
[--smoke]``; writes ``BENCH_compressed_serve.json``. Or
``python -m benchmarks.run compressed_serve``.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from repro.core import CompressedModel, StorageEngine
from repro.launch.compressed_serve import (
    DecoderSpec,
    MaterializedProvider,
    greedy_decode,
    save_decoder,
)
from repro.obs.trace import recent_traces

# Bumped whenever the JSON layout changes (parsed by benchmarks/perf_gate.py).
SCHEMA_VERSION = 2

SMOKE_SPEC = DecoderSpec(d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                         n_layers=2, vocab_size=256)
FULL_SPEC = DecoderSpec(d_model=512, n_heads=8, n_kv_heads=4, d_ff=1024,
                        n_layers=4, vocab_size=2048)
PROMPT = ((1, 7, 42),)


def _seam_tally() -> dict:
    """Matmul calls and operand bytes of the newest ``generate`` request,
    read off its ``dequant_matmul*`` seam spans."""
    gen = next(r for r in reversed(recent_traces()) if r.name == "generate")
    seam = [s for s in gen.walk() if s.name.startswith("dequant_matmul")]
    return {"matmul_calls": len(seam),
            "bytes_moved": sum(s.attrs["operand_bytes"] for s in seam)}


def _session(root: str, spec: DecoderSpec, kind: str, steps: int,
             bits: int = 8) -> dict:
    """One cold serving session: fresh engine, load → provider → decode."""
    prompt = np.asarray(PROMPT)
    engine = StorageEngine(root)
    try:
        t0 = time.perf_counter()
        lm = engine.load_model("decoder", bits=bits)
        provider = (CompressedModel(lm) if kind == "compressed"
                    else MaterializedProvider(lm))
        setup_s = time.perf_counter() - t0
        tokens = greedy_decode(provider, spec, prompt, steps)
        total_s = time.perf_counter() - t0
        counters = (_seam_tally() if kind == "compressed"
                    else dict(provider.counters))
        provider.close()
    finally:
        engine.close()
    return {
        "setup_s": setup_s,
        "decode_s": total_s - setup_s,
        "total_s": total_s,
        "tokens_per_s": steps / total_s if total_s else float("inf"),
        "bytes_moved": counters["bytes_moved"],
        "matmul_calls": counters["matmul_calls"],
        "tokens": tokens,
    }


def _phase(spec: DecoderSpec, steps: int, reps: int) -> dict:
    with tempfile.TemporaryDirectory() as root:
        engine = StorageEngine(root)
        save_decoder(engine, "decoder", spec, seed=0)
        engine.close()

        # Interleaved best-of-N: allocator/page-cache drift hits both
        # providers equally instead of biasing whichever runs first.
        c_reps, m_reps = [], []
        for _ in range(reps):
            c_reps.append(_session(root, spec, "compressed", steps))
            m_reps.append(_session(root, spec, "materialized", steps))
        best_c = max(c_reps, key=lambda r: r["tokens_per_s"])
        best_m = max(m_reps, key=lambda r: r["tokens_per_s"])
        if not all((r["tokens"] == best_m["tokens"]).all() for r in c_reps):
            raise AssertionError("compressed decode diverged from materialized")

        # One bits=4 pair: flexible loading (top-4 delta bit-planes) gives
        # the int4-packed kernel layout — report its traffic + parity.
        c4 = _session(root, spec, "compressed", steps, bits=4)
        m4 = _session(root, spec, "materialized", steps, bits=4)

    phase = {
        "spec": {"d_model": spec.d_model, "n_layers": spec.n_layers,
                 "d_ff": spec.d_ff, "vocab_size": spec.vocab_size},
        "steps": steps,
        "reps": reps,
        "compressed": {k: v for k, v in best_c.items() if k != "tokens"},
        "materialized": {k: v for k, v in best_m.items() if k != "tokens"},
        "int4": {
            "bytes_moved": c4["bytes_moved"],
            "bytes_ratio_vs_materialized": c4["bytes_moved"] / m4["bytes_moved"],
            "tokens_match": bool((c4["tokens"] == m4["tokens"]).all()),
        },
        "bytes_ratio": best_c["bytes_moved"] / best_m["bytes_moved"],
        "throughput_ratio": (best_c["tokens_per_s"] / best_m["tokens_per_s"]),
        "all_reps": {
            "compressed_tokens_per_s": [r["tokens_per_s"] for r in c_reps],
            "materialized_tokens_per_s": [r["tokens_per_s"] for r in m_reps],
        },
    }
    return phase


def run_bench(smoke: bool = False, reps: int = 5,
              smoke_steps: int = 8, full_steps: int = 16) -> dict:
    # Trigger jax plugin discovery before any timed session — the seam's
    # _on_tpu() probe would otherwise charge backend init (~tens of ms)
    # to the first compressed session.
    import jax

    jax.default_backend()

    phases = {"smoke": _phase(SMOKE_SPEC, smoke_steps, reps)}
    if not smoke:
        phases["full"] = _phase(FULL_SPEC, full_steps, reps)
    return {
        "schema_version": SCHEMA_VERSION,
        "mode": "smoke" if smoke else "full",
        "config": {"reps": reps, "smoke_steps": smoke_steps,
                   "full_steps": full_steps, "prompt_len": len(PROMPT[0])},
        "compressed_serve": {"phases": phases},
    }


def run(csv, smoke: bool = False):
    """Runner entry point (quick scale, CSV convention)."""
    res = run_bench(smoke=True, reps=3 if smoke else 5)
    ph = res["compressed_serve"]["phases"]["smoke"]
    csv.add("compressed_serve/tokens_per_s",
            1e6 / ph["compressed"]["tokens_per_s"],
            f"throughput_ratio={ph['throughput_ratio']:.3f}")
    csv.add("compressed_serve/bytes_ratio", ph["bytes_ratio"] * 1e6,
            f"int4_ratio={ph['int4']['bytes_ratio_vs_materialized']:.3f}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: smoke phase only, 3 reps")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_compressed_serve.json"))
    args = ap.parse_args()
    if args.smoke:
        args.reps = 3
    res = run_bench(smoke=args.smoke, reps=args.reps)
    res["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    for name, ph in res["compressed_serve"]["phases"].items():
        print(f"{name}: compressed {ph['compressed']['tokens_per_s']:.1f} "
              f"tok/s vs materialized {ph['materialized']['tokens_per_s']:.1f} "
              f"(ratio {ph['throughput_ratio']:.3f}); "
              f"bytes ratio {ph['bytes_ratio']:.3f}, "
              f"int4 {ph['int4']['bytes_ratio_vs_materialized']:.3f}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
