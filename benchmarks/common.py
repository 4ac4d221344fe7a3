"""Benchmark harness utilities: CSV emission per the repo convention."""

from __future__ import annotations

import time

from repro.launch.compile_cache import enable_compile_cache

# Benchmarks are chip entry points: every compile goes to the one
# persistent cache, set before the first compile.
enable_compile_cache()


class Csv:
    """Collects ``name,us_per_call,derived`` rows (one per measurement)."""

    def __init__(self):
        self.rows: list[tuple[str, float, str]] = []

    def add(self, name: str, us_per_call: float, derived: str = ""):
        self.rows.append((name, us_per_call, derived))

    def emit(self):
        for name, us, derived in self.rows:
            print(f"{name},{us:.1f},{derived}")


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, (time.perf_counter() - t0)
