"""Each cell's control comes out not correct: the plain reference in the
program's place, one precision lower, read as the check reads the
program (CPU)."""

from __future__ import annotations

import json

import pytest

from bench.harness import cell
from bench.tests.tiny import CPU, shrink, tiny_benchmark

#: Decode's control needs logits of a realistic size to show: at the
#: rehearsal's hidden size of 64 bfloat16 rounding moves them too little.
#: A quarter of internlm2-1.8b's widths (hidden 512) runs in seconds; its
#: matrices are drawn at 0.05 where the configuration says 0.02, so that
#: bfloat16's relative rounding moves the logits by what it does at full
#: width, while the program's error, a fixed tolerance per weight, does not
#: grow with them.
DECODE_WIDTHS = {"2048": 512, "8192": 2048, "1024": 256, "11568": 2048,
                 "128": 32}
DECODE_INIT = 0.05


@pytest.mark.parametrize("workload", ["ingest.hubert-xlarge"])
def test_control_fails_where_the_program_passes(workload, tmp_path):
    bench = tiny_benchmark(tmp_path)
    line = cell.run(bench, workload, 2**34 + 1, 0.3, False, tmp_path / "run",
                    CPU, control=True)
    assert line["correct"]
    assert any(c["value"] > c["limit"] for c in line["control"].values())


def test_decode_control_fails_where_the_program_passes(tmp_path):
    bench = cell.load_benchmark()
    for c in bench["configs"]:
        cfg = json.loads((cell.ROOT / c["file"]).read_text())
        if cfg["name"] == "internlm2-1.8b":
            cfg["tiny_widths"] = DECODE_WIDTHS
            cfg["initializer_range"] = DECODE_INIT
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(shrink(cfg)))
        c["file"] = str(path)
    line = cell.run(bench, "decode.internlm2-1.8b", 2**34 + 2, 0.3, False,
                    tmp_path / "run", CPU, control=True)
    assert line["correct"], line["checks"]
    gap = line["control"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
