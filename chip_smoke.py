"""Chip smoke test: the store's main path on one TPU, at internlm2-1.8b width.

Drives the paper's loop once through the entry points a user calls:

1. builds a seeded base decoder at internlm2-1.8b's published widths
   (depth cut to 2 layers) and a fine-tune of it;
2. saves the base through ``NeurStore.save`` and the fine-tune through
   ``StoreClient`` against an in-process ``ModelStoreServer``, then
   downloads the fine-tune once and checks it against the embedded load;
3. checks that every tensor of the fine-tune was stored as a delta
   against the base tensor it came from (the save EXPLAIN rows);
4. decodes the fine-tune on compressed weights (``CompressedModel``) at
   ``bits=8`` and ``bits=4``, and compares the logits with the host
   float32 forward over the same handle (``MaterializedProvider``);
5. checks that ``dequant_matmul``, ``dequant_matmul_int4`` and
   ``quantized_l2`` each ran compiled on the TPU.

Every phase raises on failure, and the process then exits non-zero. It
refuses to run without a TPU: there is no CPU branch. Findings go to
stdout; the last line is one JSON object naming the device.

    python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402

from repro.configs.internlm2_1_8b import CONFIG  # noqa: E402
from repro.core.compressed import CompressedModel  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.compressed_serve import (  # noqa: E402
    DecoderSpec, MaterializedProvider, decoder_architecture, greedy_decode,
    init_decoder_tensors)
from repro.obs.metrics import default_registry  # noqa: E402
from repro.server import ModelStoreServer, StoreClient  # noqa: E402
from repro.server.quota import tenant_model_name  # noqa: E402
from repro.store import NeurStore, SaveRequest  # noqa: E402

#: Depth is the only cut: 2 of the published 24 layers fit the run's
#: time and host memory; every width is the published one.
N_LAYERS = 2
#: Fine-tune perturbation, relative to each matrix's RMS: small enough
#: that delta range stays inside the store's default tau.
FT_REL_STD = 1e-3
BATCH, PROMPT, STEPS = 2, 4, 4
#: Bound on max|logit - ref| / max|ref|. Both sides decode the same
#: quantized weights, so only float32 rounding separates them: ~1e-6
#: through two layers. A zero-point off by one shifts logits by ~1e-2,
#: and operands rounded to bf16 in the matmul by ~3e-3.
LOGIT_TOL = 1e-4
KERNELS = ("dequant_matmul", "dequant_matmul_int4", "quantized_l2")
TENANT = "default"


def require_tpu() -> dict:
    """The device this run measures; exits non-zero off the TPU."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {platform!r}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def internlm2_spec() -> DecoderSpec:
    return DecoderSpec(
        d_model=CONFIG.d_model, n_heads=CONFIG.n_heads,
        n_kv_heads=CONFIG.n_kv_heads, d_ff=CONFIG.d_ff, n_layers=N_LAYERS,
        vocab_size=CONFIG.vocab_size, rope_theta=CONFIG.rope_theta,
        norm_eps=CONFIG.norm_eps)


def fine_tune(base: dict, seed: int) -> dict:
    """Base plus a seeded perturbation of every matrix (vectors kept)."""
    rng = np.random.default_rng(seed + 1)
    out = {}
    for name, w in base.items():
        if w.ndim < 2:
            out[name] = w
            continue
        std = FT_REL_STD * float(np.sqrt(np.mean(np.square(w, dtype=np.float64))))
        noise = rng.standard_normal(w.shape, dtype=np.float32)
        noise *= np.float32(std)
        out[name] = w + noise
    return out


class CompileClock:
    """Sums backend-compile time (cache reads included) over the run."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def kernel_calls() -> dict:
    reg = default_registry()
    return {(k, r): int(reg.sample_value("neurstore_kernel_calls_total",
                                         {"kernel": k, "route": r}) or 0)
            for k in KERNELS for r in ("tpu", "host")}


def check_dedup(base_report, ft_report) -> int:
    """Every fine-tune tensor is a delta against its own base's vertex."""
    if ft_report.n_new_bases:
        raise AssertionError(f"fine-tune made {ft_report.n_new_bases} new bases")
    base_vid = {row["tensor"]: (row["dim"], row["vertex_id"])
                for row in base_report.explain}
    for row in ft_report.explain:
        if row["outcome"] != "delta":
            raise AssertionError(f"{row['tensor']}: stored as {row['outcome']}")
        if (row["dim"], row["vertex_id"]) != base_vid[row["tensor"]]:
            raise AssertionError(
                f"{row['tensor']}: delta against vertex {row['vertex_id']}, "
                f"its base is {base_vid[row['tensor']]}")
    return len(ft_report.explain)


def check_download(client: StoreClient, store: NeurStore, name: str) -> int:
    """The served download equals the embedded reconstruction, bit for bit."""
    with client.load(name) as remote, \
            store.load(tenant_model_name(TENANT, name)) as local:
        want = local.materialize()
        got = remote.materialize()
        if list(got) != list(want):
            raise AssertionError("downloaded tensor names differ")
        for t, arr in want.items():
            if not np.array_equal(got[t], arr):
                raise AssertionError(f"downloaded {t} differs from load()")
        return sum(a.nbytes for a in want.values())


def decode_and_compare(store: NeurStore, name: str, spec: DecoderSpec,
                       bits: int, prompt: np.ndarray) -> None:
    """Compressed-domain greedy decode vs the host float32 reference."""
    lm = store.engine.load_model(tenant_model_name(TENANT, name), bits=bits)
    try:
        t0 = time.perf_counter()
        tokens, logits = greedy_decode(CompressedModel(lm), spec, prompt,
                                       STEPS, return_logits=True)
        seconds = time.perf_counter() - t0
        ref_tokens, ref_logits = greedy_decode(MaterializedProvider(lm), spec,
                                               prompt, STEPS, return_logits=True)
    finally:
        lm.close()
    if not np.all(np.isfinite(logits)):
        raise AssertionError(f"bits={bits}: non-finite logits")
    if logits.shape != (BATCH, STEPS, spec.vocab_size):
        raise AssertionError(f"bits={bits}: logits shape {logits.shape}")
    err = float(np.max(np.abs(logits - ref_logits)) / np.max(np.abs(ref_logits)))
    agree = float(np.mean(tokens == ref_tokens))
    print(f"decode bits={bits}: rel logit err {err!r} (tol {LOGIT_TOL}), "
          f"token agreement {agree!r}, {seconds!r} s on compressed weights")
    if not err <= LOGIT_TOL:
        raise AssertionError(f"bits={bits}: rel logit err {err} > {LOGIT_TOL}")


def run(spec: DecoderSpec, seed: int, root: str) -> None:
    """Every phase of the smoke; raises on the first failure."""
    t0 = time.perf_counter()
    base = init_decoder_tensors(spec, seed)
    ft = fine_tune(base, seed)
    n_params = sum(w.size for w in base.values())
    print(f"models: 2 x {n_params} params from seed {seed}, "
          f"{time.perf_counter() - t0!r} s to build")

    arch = decoder_architecture(spec)
    store = NeurStore.open(root, pool_bytes=8 << 30)
    try:
        server = ModelStoreServer(store.engine, port=0).start()
        try:
            t0 = time.perf_counter()
            base_report = store.save(SaveRequest("base", base, architecture=arch))
            print(f"save base (NeurStore.save): {time.perf_counter() - t0!r} s "
                  f"wall, {base_report.n_new_bases} new bases, "
                  f"{base_report.page_bytes} page bytes")
            del base
            with StoreClient(server.host, server.port, tenant=TENANT,
                             timeout=1200.0) as client:
                t0 = time.perf_counter()
                ft_report = client.save(SaveRequest("ft", ft, architecture=arch))
                print(f"save fine-tune (StoreClient): "
                      f"{time.perf_counter() - t0!r} s wall, "
                      f"{ft_report.n_new_bases} new bases, "
                      f"{ft_report.page_bytes} page bytes")
                del ft
                n = check_dedup(base_report, ft_report)
                print(f"dedup: {n}/{n} fine-tune tensors stored as deltas "
                      "against their base")
                t0 = time.perf_counter()
                nbytes = check_download(client, store, "ft")
                print(f"download (StoreClient.load): {nbytes} bytes match "
                      f"load(), {time.perf_counter() - t0!r} s")
        finally:
            server.stop()
        prompt = np.random.default_rng(seed + 2).integers(
            0, spec.vocab_size, (BATCH, PROMPT))
        for bits in (8, 4):
            decode_and_compare(store, "ft", spec, bits, prompt)
    finally:
        store.close()

    calls = kernel_calls()
    print("kernel calls: " + ", ".join(
        f"{k} tpu={calls[k, 'tpu']} host={calls[k, 'host']}" for k in KERNELS))
    missing = [k for k in KERNELS if calls[k, "tpu"] == 0]
    if missing:
        raise AssertionError(f"no compiled TPU call of {missing}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = require_tpu()
    cache = enable_compile_cache()
    clock = CompileClock()
    spec = internlm2_spec()
    print(f"device: {device['kind']} x{device['count']}; compile cache {cache}")
    print(f"config: {CONFIG.name} d_model={spec.d_model} heads={spec.n_heads} "
          f"kv_heads={spec.n_kv_heads} head_dim={spec.head_dim} "
          f"d_ff={spec.d_ff} vocab={spec.vocab_size}; reduced: n_layers "
          f"{CONFIG.n_layers} -> {spec.n_layers} (the only reduction)")
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        run(spec, args.seed, root)
    print(f"compile: {clock.programs} programs, {clock.seconds!r} s "
          f"(persistent-cache hits {clock.cache_hits}); "
          f"total {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
