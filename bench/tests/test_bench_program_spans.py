"""The readers of the program's seam, decode and save spans, on span
trees built by hand: their values, their scoping, and ``None`` where
the spans they read are absent."""

from __future__ import annotations

import pytest

from bench.harness import cell
from bench.harness.cell import Window
from bench.harness.metrics_ctx import LayerContext


class Span:
    """The part of ``repro.obs.trace.Span`` the readers use."""

    def __init__(self, name, seconds, *children, start=0.0):
        self.name = name
        self.children = list(children)
        self.attrs = {}
        self.start = start
        self.end = start + seconds

    def elapsed(self):
        return self.end - self.start

    def walk(self):
        yield self
        for child in self.children:
            yield from child.walk()


def decode_roots():
    """Two forwards of one request, one kernel-routed call each and a
    host-route call in the first; a forward outside any request."""
    req = Span("generate", 0.2,
               Span("forward", 0.100,
                    Span("dequant_matmul", 0.085,
                         Span("upload", 0.030), Span("wait", 0.050)),
                    Span("dequant_matmul", 0.005)),
               Span("forward", 0.060,
                    Span("dequant_matmul_int4", 0.052,
                         Span("upload", 0.020), Span("wait", 0.030)),
                    Span("upload", 0.004)))  # not a seam call's
    stray = Span("other", 1.0,
                 Span("forward", 1.0,
                      Span("dequant_matmul", 1.0, Span("upload", 1.0),
                           Span("wait", 1.0))))
    return [req, stray]


def ingest_roots():
    """One save over HTTP, its probe with a launched distance block, and
    spans of the same names outside ``engine.save``."""
    save = Span("http.request", 9.0, Span(
        "engine.save", 8.0,
        Span("probe", 5.0,
             Span("quantized_l2", 4.0, Span("upload", 1.0),
                  Span("upload", 0.5), Span("wait", 1.5),
                  Span("upload", 0.5), Span("wait", 0.5)),
             Span("delta", 0.5)),
        Span("quantize", 2.0, Span("encode", 0.5), Span("encode", 0.25))))
    load = Span("http.request", 1.0, Span(
        "engine.load", 1.0, Span("catalog", 0.1),
        Span("quantized_l2", 1.0, Span("upload", 1.0), Span("wait", 1.0)),
        Span("encode", 1.0)))
    return [save, load]


def context(roots, logical_bytes=0):
    win = Window(0.0, 1.0, 1, 0, [], {"logical_bytes": logical_bytes})
    ctx = LayerContext(win=win, calls=None, summary=None, peak=None,
                       state=None)
    ctx.roots = roots
    return ctx


def read(name, ctx):
    return cell.load_module(cell.reader_path(name)).read(ctx, name)


NEW = {
    # (upload 30 + 20 ms) over 2 forwards; the stray tree is not read.
    "operand_upload_ms_per_forward.decode": (decode_roots, 0, 25.0),
    "kernel_wait_ms_per_forward.decode": (decode_roots, 0, 40.0),
    # 160 ms of forwards less 130 ms of seam upload and wait, over 2.
    "host_math_ms_per_forward.decode": (decode_roots, 0, 15.0),
    # Per GB of 2 GB saved; the load's spans are not read.
    "probe_upload_s_per_GB.ingest": (ingest_roots, 2e9, 1.0),
    "probe_wait_s_per_GB.ingest": (ingest_roots, 2e9, 1.0),
    "encode_s_per_GB.ingest": (ingest_roots, 2e9, 0.375),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_values_and_scoping(name):
    roots, nbytes, want = NEW[name]
    assert read(name, context(roots(), nbytes)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_without_its_spans(name):
    assert read(name, context([], 2e9)) is None
    # The parent program's trees: no seam, decode or encode spans.
    old = [Span("generate", 1.0), Span("engine.save", 1.0,
                                       Span("probe", 0.5),
                                       Span("quantize", 0.5))]
    assert read(name, context(old, 2e9)) is None


def test_host_route_decode_reads_host_math_alone():
    """Where every call stays on the host (the CPU), nothing is uploaded
    or waited for, and a forward is all host math."""
    roots = [Span("generate", 0.1,
                  Span("forward", 0.04, Span("dequant_matmul", 0.03)),
                  Span("forward", 0.06, Span("dequant_matmul", 0.05)))]
    ctx = context(roots)
    assert read("operand_upload_ms_per_forward.decode", ctx) is None
    assert read("kernel_wait_ms_per_forward.decode", ctx) is None
    assert read("host_math_ms_per_forward.decode", ctx) == pytest.approx(50.0)


def test_new_metrics_are_declared_for_their_cell():
    per_layer = {m["name"]: m for m in cell.load_benchmark()["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        cell_name = ("decode.internlm2-1.8b" if name.endswith(".decode")
                     else "ingest.hubert-xlarge")
        assert m["source"] == "program_span" and m["workloads"] == [cell_name]
        assert cell.reader_path(name).name == name.split(".")[0] + ".py"
