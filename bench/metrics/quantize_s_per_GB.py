"""Delta quantization of a save: self time of ``quantize`` under
``engine.save``, per GB saved."""

from bench.harness.spans import total


def read(ctx, name):
    seconds = total(ctx.roots, "quantize", under="engine.save", own=True)
    return ctx.per_gb(seconds) if seconds > 0 else None
