"""Routing of one forward: time in ``route`` spans (one a MoE layer: the
router's matmul, its scores, the top-k choice and the weights) per
``forward`` span of the window's requests, in ms. ``None`` where the
program opens no ``route`` span."""

from bench.metrics.mla_ms_per_forward import span_ms_per_forward


def read(ctx, name):
    return span_ms_per_forward(ctx, "route")
