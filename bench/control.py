"""Read a cell's control beside the program, on several seeds, in one process.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> [<n> ...]

For each seed: the cell's set-up, a window of ``--seconds`` at the cell's
own load, the check's readings of the program, and the same readings of
the control (the plain reference in the program's place, one precision
lower). One JSON line per seed. This is how a limit's two readings are
taken on the chip; benchmark runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR.parent), str(BENCH_DIR.parent / "src")]

from bench.harness import cell as harness  # noqa: E402
from bench.harness.device import require_tpu  # noqa: E402
from bench.run import enable_compile_cache  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    entry, _, _ = harness.resolve(bench, args.workload)
    device = require_tpu(entry["chips"])
    enable_compile_cache()
    for seed in args.seeds:
        workdir = Path(tempfile.mkdtemp(prefix="neurstore_control_"))
        try:
            line = harness.run(bench, args.workload, seed, args.seconds,
                               False, workdir, device, control=True)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        print(json.dumps({"seed": seed, "attempted": line["attempted"],
                          "metrics": line["metrics"],
                          "program": line["checks"],
                          "control": line["control"]}), flush=True)


if __name__ == "__main__":
    main()
