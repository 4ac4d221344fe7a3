"""Tiny copies of the benchmark's configurations, for CPU rehearsals.

Every width shrinks by the map in the configuration's ``tiny_widths``
and every other number stays, so a rehearsal drives the same drivers,
the same program paths and the same checks as a chip run, in seconds.
These sizes are for tests only.
"""

from __future__ import annotations

import copy
import json
from pathlib import Path

from bench.harness import cell

SCALARS = ("hidden_size", "intermediate_size", "vocab_size", "head_dim")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def shrink(config: dict) -> dict:
    sizes = {int(k): v for k, v in config["tiny_widths"].items()}
    out = copy.deepcopy(config)
    for key in SCALARS:
        if key in out:
            out[key] = sizes.get(out[key], out[key])

    def fix(entry):
        if isinstance(entry, dict):
            entry["tensors"] = [fix(e) for e in entry["tensors"]]
            return entry
        name, shape, init = entry
        return [name, [sizes.get(s, s) for s in shape], init]

    out["tensors"] = [fix(e) for e in out["tensors"]]
    return out


def tiny_benchmark(tmp: Path) -> dict:
    """``BENCHMARK.json`` with each configuration file swapped for its
    tiny copy under ``tmp``."""
    bench = cell.load_benchmark()
    for c in bench["configs"]:
        cfg = shrink(json.loads((cell.ROOT / c["file"]).read_text()))
        path = Path(tmp) / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    return bench
