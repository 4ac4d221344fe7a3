"""Operand upload of the index probe: time in ``upload`` spans under the
``quantized_l2`` spans of ``engine.save`` (the hoisted code block, then
each query row, up to the kernel's dispatch), per GB saved. ``None``
where no distance block took a kernel route."""

from bench.harness.spans import walk_under


def read(ctx, name):
    seconds = sum(c.elapsed() for r in ctx.roots
                  for s in walk_under(r, "quantized_l2", under="engine.save")
                  for c in s.children if c.name == "upload")
    return ctx.per_gb(seconds) if seconds > 0 else None
