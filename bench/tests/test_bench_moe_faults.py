"""The MoE decode cell comes out not correct where its timed path is
broken underneath, and its control comes out not correct where the
program passes.

Each fault case drives the whole cell on the CPU, with the chip look
skipped, at the configuration's tiny widths cut to the dense layer and
one MoE layer, after planting one fault in the program where its answer
is produced: the router choosing without its correction bias, one held
expert's output dropped, half of a batch left out. Matrices are drawn
at 0.05 where the configuration says 0.02: at the tiny widths and 0.02
a routed expert moves the logits by less than the check's limit, as it
does not at full width, while the program's error, a truncation of each
stored weight, does not grow with the weights (the unfaulted run reads
a gap of 0 there).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench.harness import cell
from bench.tests.test_bench_moe import CONFIG, tiny_config
from bench.tests.tiny import CPU, shrink

CELL = "decode_moe.moonlight-16b-a3b"


def moe_benchmark(tmp, cfg: dict) -> dict:
    """``BENCHMARK.json`` with the Moonlight configuration swapped for
    ``cfg``."""
    bench = cell.load_benchmark()
    path = tmp / "moonlight-16b-a3b.json"
    path.write_text(json.dumps(cfg))
    for c in bench["configs"]:
        if c["name"] == "moonlight-16b-a3b":
            c["file"] = str(path)
    return bench


def _route_without_bias(monkeypatch):
    """The router chooses its experts on the unbiased scores."""
    import repro.launch.compressed_serve as cs

    inner = cs._route

    class NoBias:
        def __init__(self, provider):
            self.provider = provider

        def matmul(self, x, name):
            return self.provider.matmul(x, name)

        def vector(self, name):
            v = self.provider.vector(name)
            return np.zeros_like(v) if name.endswith("correction_bias") else v

    monkeypatch.setattr(cs, "_route", lambda provider, pre, xn, spec:
                        inner(NoBias(provider), pre, xn, spec))


def _drop_held_expert(monkeypatch):
    """The first held expert's output is lost in the grouped call."""
    from repro.core.compressed import CompressedModel

    inner = CompressedModel.expert_matmul

    def dropped(self, x, names, rows=None):
        y = inner(self, x, names, rows=rows)
        if names[0].endswith("down_proj.weight"):
            y[0] = 0.0
        return y

    monkeypatch.setattr(CompressedModel, "expert_matmul", dropped)


def _decode_half_batch(monkeypatch):
    """Half of the batch left out: its rows repeat the other half's."""
    import repro.launch.compressed_serve as cs

    inner = cs.greedy_decode

    def half(provider, spec, prompt, steps, **kw):
        b = prompt.shape[0] // 2
        out = inner(provider, spec, prompt[:b], steps, **kw)
        if not kw.get("return_routing"):
            return np.concatenate([out, out], axis=0)
        tokens, routing = out
        return (np.concatenate([tokens, tokens], axis=0),
                {k: np.concatenate([v, v], axis=1) for k, v in routing.items()})

    monkeypatch.setattr(cs, "greedy_decode", half)


FAULTS = [_route_without_bias, _drop_held_expert, _decode_half_batch]
FAULT_INIT = 0.05
#: Its first request generates 23 tokens after a 21-token prompt, so that
#: the one request a loaded machine may finish inside the window carries
#: a dropped expert into the served tokens (a first request that
#: generates one token can leave every argmax standing).
FAULT_SEED = 2**32 + 27


def fault_config() -> dict:
    cfg = json.loads(CONFIG.read_text())
    cfg["initializer_range"] = FAULT_INIT
    return tiny_config(2, shrink(cfg))


@pytest.mark.parametrize("fault", FAULTS,
                         ids=[f.__name__.strip("_") for f in FAULTS])
def test_broken_moe_decode_is_not_correct(fault, monkeypatch, tmp_path):
    bench = moe_benchmark(tmp_path, fault_config())
    fault(monkeypatch)
    line = cell.run(bench, CELL, FAULT_SEED, 0.3, False, tmp_path / "run",
                    CPU)
    # Caught by the comparison, not by a crash of the run.
    assert line["failed"] == 0 and line["checks"], line
    assert line["correct"] is False, line["checks"]


#: The control needs logits and router scores of a realistic size to
#: show: at the tiny widths bfloat16 rounding moves them too little. A
#: quarter of the published widths (hidden 512) at two layers runs in
#: seconds; matrices are drawn at 0.05 where the configuration says
#: 0.02, so that bfloat16's relative rounding moves the logits and the
#: scores by what it does at full width, while the program's error, a
#: truncation of each stored weight, does not grow with them.
QUARTER = {"2048": 512, "11264": 2816, "3072": 768, "576": 144, "512": 128,
           "4096": 1024, "1408": 352, "2816": 704, "20480": 2048}


def test_moe_control_fails_where_the_program_passes(tmp_path):
    cfg = json.loads(CONFIG.read_text())
    cfg["tiny_widths"] = QUARTER
    cfg["initializer_range"] = 0.05
    cfg = tiny_config(2, shrink(cfg))
    bench = moe_benchmark(tmp_path, cfg)
    line = cell.run(bench, CELL, 2**34 + 5, 0.3, False, tmp_path / "run",
                    CPU, control=True)
    assert line["correct"], line["checks"]
    control = line["control"]
    assert any(control[name]["value"] > control[name]["limit"]
               for name in line["checks"]), control
