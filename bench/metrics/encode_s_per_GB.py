"""Bit-packing of a save's deltas: time in ``encode`` spans (one a
tensor, around ``encode_payload``) under ``engine.save``, per GB saved.
The self time of ``quantize`` is then the delta quantizer alone."""

from bench.harness.spans import total


def read(ctx, name):
    seconds = total(ctx.roots, "encode", under="engine.save")
    return ctx.per_gb(seconds) if seconds > 0 else None
