"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json``. Set-up (weights, saves, warm-up and
compilation) is timed as ``setup_s``; then the window runs for
``--seconds``; then the check compares the window's answers with the
plain reference. The last lines of stderr are the numbers compared with
their limits; the last line of stdout is the result as one JSON object.
With ``--trace 1`` the window runs under the profiler and the line holds
the per-layer metrics instead of the end-to-end ones.

Exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR.parent), str(BENCH_DIR.parent / "src")]

from bench.harness import cell as harness  # noqa: E402
from bench.harness.device import require_tpu  # noqa: E402

#: Persistent compilation cache, at a fixed path inside the checkout
#: (the path is part of the cache's key). JAX_COMPILATION_CACHE_DIR,
#: where set, takes its place.
CACHE_DIR = BENCH_DIR / ".jax_cache"


def enable_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # Cache every program, however quick to compile: a run after the
    # first compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCount:
    """Backend compilations in this process (persistent-cache hits too)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __call__(self) -> int:
        return self.n


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    bench = harness.load_benchmark()
    entry, _, _ = harness.resolve(bench, args.workload)
    device = require_tpu(entry["chips"])
    enable_compile_cache()
    workdir = Path(tempfile.mkdtemp(prefix="neurstore_bench_"))
    try:
        line = harness.run(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), workdir, device,
                           compiles=CompileCount())
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    harness.print_line(line)


if __name__ == "__main__":
    main()
