"""Each cell's set-up, a short window and its check, at tiny widths on
the CPU, through the harness with its look for a chip skipped."""

from __future__ import annotations

import pytest

from bench.harness import cell
from bench.tests.tiny import CPU, tiny_benchmark

WORKLOADS = [w["name"] for w in cell.load_benchmark()["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_checks_on_the_cpu(workload, trace, tmp_path):
    bench = tiny_benchmark(tmp_path)
    line = cell.run(bench, workload, 2**33 + 3, 0.5, bool(trace),
                    tmp_path / "run", CPU)
    assert line["correct"], line
    assert line["attempted"] >= 1 and line["failed"] == 0
    wanted = {m["name"] for m in cell.metrics_for(bench, workload, bool(trace))}
    if trace:
        # No device here: what reads the trace's device ops reads nothing,
        # the spans still read.
        assert line["device"]["busy_s"] == 0.0
        assert set(line["metrics"]) <= wanted
        assert "breakdown" in line
    else:
        assert set(line["metrics"]) == wanted
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
