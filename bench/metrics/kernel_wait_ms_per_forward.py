"""Kernel wait of one forward: time in ``wait`` spans under the
``dequant_matmul*`` seam spans (from the kernel's dispatch until its
result is in host memory), per ``forward`` span of the window's
``generate`` requests, in ms. ``None`` where no call took a kernel
route."""

from bench.harness.spans import walk_under


def read(ctx, name):
    forwards = [f for r in ctx.roots
                for f in walk_under(r, "forward", under="generate")]
    seconds = sum(c.elapsed() for f in forwards for s in f.walk()
                  if s.name.startswith("dequant_matmul")
                  for c in s.children if c.name == "wait")
    return 1e3 * seconds / len(forwards) if seconds > 0 else None
