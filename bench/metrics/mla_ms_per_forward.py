"""Latent attention of one forward: time in ``mla`` spans (one a layer:
its norms, projections, rope, cache writes and attention, seam calls
included) under the window's ``generate`` requests, per ``forward``
span, in ms. ``None`` where the program opens no ``mla`` span."""

from bench.harness.spans import walk_under


def span_ms_per_forward(ctx, span: str):
    """Time in spans called ``span`` inside the window's ``forward``
    spans (under ``generate``), per forward, in ms; ``None`` where there
    are none."""
    forwards = [f for r in ctx.roots
                for f in walk_under(r, "forward", under="generate")]
    seconds = sum(s.elapsed() for f in forwards for s in f.walk()
                  if s.name == span)
    return 1e3 * seconds / len(forwards) if seconds > 0 else None


def read(ctx, name):
    return span_ms_per_forward(ctx, "mla")
