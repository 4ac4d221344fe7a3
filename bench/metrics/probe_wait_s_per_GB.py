"""Kernel wait of the index probe: time in ``wait`` spans under the
``quantized_l2`` spans of ``engine.save`` (from each query row's launch
until its distances are in host memory), per GB saved. ``None`` where
no distance block took a kernel route."""

from bench.harness.spans import walk_under


def read(ctx, name):
    seconds = sum(c.elapsed() for r in ctx.roots
                  for s in walk_under(r, "quantized_l2", under="engine.save")
                  for c in s.children if c.name == "wait")
    return ctx.per_gb(seconds) if seconds > 0 else None
