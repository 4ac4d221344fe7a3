"""Record the trace fixture of the reducer's test, on a TPU.

    python3 bench/tests/fixtures/record_trace.py OUT.xplane.pb

Runs one probe distance block (``quantized_l2``) and one int4
compressed matmul (``dequant_matmul_int4``) through the program's
dispatch seams inside the benchmark's traced window, copies the trace
to ``OUT`` and prints each plane and line with its first events, so the
names the reducer matches can be read off.
"""

from __future__ import annotations

import glob
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> None:
    import numpy as np
    from jax.profiler import ProfileData

    from bench.harness.device import require_tpu
    from bench.harness.xplane import Profile
    from repro.kernels import ops

    print(require_tpu(1))
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 255, (8, 1 << 19), dtype=np.uint8)
    scales = np.full(8, 1e-3, np.float32)
    zps = np.full(8, 128.0, np.float32)
    mids = np.zeros(8, np.float32)
    query = rng.standard_normal((1, 1 << 19)).astype(np.float32)
    base = rng.integers(-128, 127, (2048, 2048), dtype=np.int8)
    delta = ops.pack_int4(rng.integers(0, 15, (2048, 2048)).astype(np.uint8))
    x = rng.standard_normal((8, 2048)).astype(np.float32)

    def work():
        ops.quantized_l2_auto(query, codes, scales, zps, mids, min_elems=0)
        ops.dequant_matmul_auto(x, base, 1e-3, 0.0, delta, 1e-5, 7.0,
                                packed=True, min_elems=0)

    work()  # compile outside the trace
    with tempfile.TemporaryDirectory() as d:
        with Profile(Path(d)):
            work()
        (src,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        shutil.copyfile(src, out)
    for plane in ProfileData.from_file(out).planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines))
        for line in lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for e in events[:6]:
                stats = {k: str(v)[:200] for k, v in dict(e.stats).items()}
                print("    ", repr(e.name), e.start_ns, e.duration_ns, stats)


if __name__ == "__main__":
    main(sys.argv[1])
