"""Routed experts of one forward: time in ``experts`` spans (one a MoE
layer: its grouped seam calls over the held experts and the weighted
combine) per ``forward`` span of the window's requests, in ms. ``None``
where the program opens no ``experts`` span."""

from bench.metrics.mla_ms_per_forward import span_ms_per_forward


def read(ctx, name):
    return span_ms_per_forward(ctx, "experts")
