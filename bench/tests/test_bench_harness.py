"""The benchmark's own pieces on the CPU: names, arithmetic, reductions."""

from __future__ import annotations

import json
import math
import re
from pathlib import Path

import numpy as np
import pytest

from bench.harness import cell, device, roofline, spans, weights, xplane
from bench.reference.tensor_store import PlainStore, bound_ratio

BENCH = cell.load_benchmark()
FIXTURE = Path(__file__).parent / "fixtures" / "v5e_kernels.xplane.pb"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DRIVER_API = ("setup", "window", "end_to_end", "recorder", "release", "check")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_pieces_load_by_name(workload):
    entry, config, traffic = cell.resolve(BENCH, workload)
    assert config["name"] == entry["config"]
    driver = cell.load_module(cell.BENCH_DIR / "drivers"
                              / f"{traffic['driver']}.py")
    for fn in DRIVER_API:
        assert callable(getattr(driver, fn)), fn
    per_layer = cell.metrics_for(BENCH, workload, trace=True)
    e2e = cell.metrics_for(BENCH, workload, trace=False)
    assert per_layer and "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    for m in per_layer:
        reader = cell.load_module(cell.reader_path(m["name"]))
        assert callable(reader.read)
        assert m["moves"] in {e["name"] for e in e2e}


@pytest.mark.parametrize("name,reader", [
    ("device_idle.ingest", "device_idle.py"),
    ("step_mfu.decode", "step_mfu.py"),
    ("dequant_matmul_roofline.decode", "kernel_roofline.py"),
    ("some_kernel_roofline", "kernel_roofline.py"),
])
def test_readers_are_found_by_family(name, reader):
    assert cell.reader_path(name) == cell.BENCH_DIR / "metrics" / reader


def test_benchmark_file_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"] for c in BENCH["configs"]}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in layer for layer in layers)
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/") and NAME.match(c["name"])
        data = json.loads((cell.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(data["reduced"])
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["name"])


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        cell.resolve(BENCH, "no-such-cell")


def test_peaks_are_keyed_by_device_kind():
    v5e = device.peaks("TPU v5 lite")
    assert v5e == {"bf16_flops": 197e12, "int8_ops": 393e12,
                   "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="TPU v99"):
        device.peaks("TPU v99")


def test_run_refuses_the_cpu():
    with pytest.raises(device.NoChip):
        device.require_tpu(1)


def test_roofline_arithmetic_from_logical_shapes():
    peak = device.peaks("TPU v5 lite")
    ops, nbytes = roofline.quantized_l2(1, 8, 1_638_400)
    assert ops == 2 * 8 * 1_638_400
    assert nbytes == 8 * 1_638_400 + 12 * 8 + 4 * 1_638_400 + 4 * 8
    t, bound = roofline.least_seconds(ops, nbytes, peak)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    ops, nbytes = roofline.dequant_matmul(8, 2048, 8192, 0.5)
    assert ops == 2 * 8 * 2048 * 8192
    assert nbytes == 2048 * 8192 * 1.5 + 4 * 8 * 2048 + 4 * 8 * 8192
    ops, nbytes = roofline.dequant_matmul(4096, 4096, 4096, 1.0)
    t, bound = roofline.least_seconds(ops, nbytes, peak)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)


def test_layer_shares_from_calls_and_trace():
    from bench.harness.metrics_ctx import LayerContext

    ev = [xplane.Event("dequant_matmul_pallas.1 custom-call", 0, 2_000_000),
          xplane.Event("copy.1 copy", 2_000_000, 3_000_000)]
    summary = xplane.summarize(xplane.Trace({"/device:TPU:0": ev}, []),
                               xplane.Event("bench.window", 0, 10**9), [])
    calls = [{"ops": 2.0 * 8 * 2048 * 8192,
              "bytes": roofline.dequant_matmul(8, 2048, 8192, 1.0)[1]}]
    ctx = LayerContext(win=None, calls=calls, summary=summary,
                       peak=device.peaks("TPU v5 lite"), state=None)
    least = calls[0]["bytes"] / 819e9
    share = ctx.roofline_share(lambda op: op.startswith("dequant_matmul"),
                               calls)
    assert share == pytest.approx(100 * least / 2e-3)
    assert ctx.peak_share(calls) == pytest.approx(100 * calls[0]["ops"] / 197e12)
    assert ctx.device_idle() == pytest.approx(99.7)
    assert ctx.roofline_share(lambda op: op.startswith("quantized_l2"),
                              calls) is None


def test_interval_reductions():
    ev = [xplane.Event("a", 0, 10), xplane.Event("b", 5, 20),
          xplane.Event("a", 30, 40)]
    assert xplane.busy_ns(ev, 0, 50) == 30
    assert xplane.busy_ns(ev, 8, 35) == 17
    assert xplane.gaps(ev, 0, 50) == [(20, 30), (40, 50)]
    assert xplane.op_seconds(ev, 0, 50) == {"a": 20e-9, "b": 15e-9}
    segs = [(15, 35, "outer", 1), (22, 28, "inner", 2)]
    idle = xplane.attribute([(20, 30), (40, 50)], segs)
    assert idle == pytest.approx({"outer": 4e-9, "inner": 6e-9,
                                  "(no host span)": 10e-9})


def test_reducer_on_a_recorded_chip_trace():
    trace = xplane.read_xspace(FIXTURE)
    (ops,) = trace.devices.values()
    marks = {e.name: e for e in trace.host}
    window = marks["bench.window"]
    summary = xplane.summarize(trace, window, [])
    busy = xplane.union(xplane.clip([(e.start, e.end) for e in ops],
                                    window.start, window.end))
    assert summary.busy_s == pytest.approx(sum(e - s for s, e in busy) / 1e9)
    assert 0 < summary.busy_s < summary.window_s
    l2_s, l2_n = summary.kernel_seconds(
        lambda op: op == "quantized_l2_pallas.1 custom-call")
    dq_s, dq_n = summary.kernel_seconds(
        lambda op: op == "dequant_matmul_int4_pallas.1 custom-call")
    assert (l2_n, dq_n) == (1, 1)
    assert l2_s + dq_s <= summary.busy_s * (1 + 1e-9)


def test_op_names_come_from_the_hlo_text():
    text = ("%quantized_l2_pallas.1 = f32[8,1]{1,0:T(8,128)S(1)} custom-call("
            "f32[1,1638400]{1,0:T(1,128)S(1)} %bitcast.8), custom_call_target=")
    assert xplane.op_name(text) == "quantized_l2_pallas.1 custom-call"
    text = "%copy.5 = f32[8,1]{1,0:T(8,128)S(1)} copy(f32[8,1] %quantized_l2_pallas.1)"
    assert xplane.op_name(text) == "copy.5 copy"
    assert xplane.op_name("plain") == "plain"


class _Span:
    def __init__(self, name, start, end, children=(), trace_id="t"):
        self.name, self.start, self.end = name, start, end
        self.children = list(children)
        self.trace_id = trace_id
        self.attrs = {}

    def elapsed(self):
        return self.end - self.start


def test_span_scoping_and_self_time():
    save = _Span("engine.save", 0, 10, [_Span("probe", 1, 4),
                                         _Span("quantize", 4, 9)])
    load = _Span("engine.load", 20, 25, [_Span("probe", 20, 21)])
    root = _Span("http.request", 0, 30, [save, load])
    assert spans.total([root], "probe", under="engine.save") == 3
    assert spans.total([root], "probe") == 4
    assert spans.self_seconds(save) == 2
    assert spans.window_roots([root, _Span("x", 40, 41)], 0, 35) == [root]
    segs = xplane.span_segments([root], lambda t: int(t))
    assert (1, 4, "engine.save/probe", 3) in segs
    assert (0, 1, "http.request/engine.save", 2) in segs


def test_tensor_lists_at_published_widths():
    hub = json.loads((cell.BENCH_DIR / "configs" / "hubert-xlarge.json")
                     .read_text())
    assert weights.n_params(hub) == 2 * 19_677_440
    lm = json.loads((cell.BENCH_DIR / "configs" / "internlm2-1.8b.json")
                    .read_text())
    specs = {n: s for n, s, _ in weights.tensor_specs(lm)}
    assert specs["model.layers.1.self_attn.k_proj.weight"] == (2048, 1024)
    assert specs["lm_head.weight"] == (2048, 92_544 // 8)


def test_models_repeat_from_the_seed():
    cfg = {"initializer_range": 0.02, "layers": 1, "tensors": [
        {"repeat": "layers", "tensors": [["l{i}.w", [16, 8], "normal"],
                                         ["l{i}.b", [8], "zeros"]]}]}
    base, (ft,) = weights.make_models(cfg, 2**33 + 1, 1, 1e-3)
    again, _ = weights.make_models(cfg, 2**33 + 1, 1, 1e-3)
    other, _ = weights.make_models(cfg, 2**33 + 2, 1, 1e-3)
    assert np.array_equal(base["l0.w"], again["l0.w"])
    assert not np.array_equal(base["l0.w"], other["l0.w"])
    assert np.array_equal(ft["l0.b"], base["l0.b"])
    rel = np.std(ft["l0.w"] - base["l0.w"]) / np.sqrt(np.mean(base["l0.w"] ** 2))
    assert 5e-4 < rel < 2e-3


@pytest.mark.parametrize("seed", [2**33 + 1, 2**40 + 7])
def test_request_lengths_repeat_from_the_seed(seed):
    decode = cell.load_module(cell.BENCH_DIR / "drivers" / "decode.py")
    _, _, traffic = cell.resolve(BENCH, "decode.internlm2-1.8b")

    def draw(s):
        rng = np.random.default_rng([s, 11])
        return np.array([decode.lengths(rng, traffic) for _ in range(4000)])

    lengths = draw(seed)
    assert np.array_equal(lengths, draw(seed))
    assert not np.array_equal(lengths, draw(seed + 1))
    p, o = lengths[:, 0], lengths[:, 1]
    assert p.min() >= 1 and p.max() <= traffic["prompt_max"]
    assert o.min() >= 1 and o.max() <= traffic["output_max"]
    # Medians as the source's, scaled; prompts much longer than answers.
    scale = traffic["scale"]
    assert np.median(p) == pytest.approx(traffic["prompt_median"] / scale, rel=0.1)
    assert np.median(o) == pytest.approx(traffic["output_median"] / scale, rel=0.25)
    assert p.sum() > 5 * o.sum()


def test_plain_store_and_its_control():
    rng = np.random.default_rng(0)
    sent = {"w": rng.normal(0, 0.02, (64, 64)).astype(np.float32)}
    p = 2.0 ** -24
    exact = PlainStore()
    exact.save("m", sent)
    assert bound_ratio(exact.load("m"), sent, p) == 0.0
    import ml_dtypes

    control = PlainStore(ml_dtypes.bfloat16)
    control.save("m", sent)
    assert bound_ratio(control.load("m"), sent, p) > 100.0
    assert bound_ratio({}, sent, p) == math.inf
