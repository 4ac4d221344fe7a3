"""Operand upload of one forward: time in ``upload`` spans under the
``dequant_matmul*`` seam spans (operand conversion, padding and
host-to-device transfer, up to the kernel's dispatch), per ``forward``
span of the window's ``generate`` requests, in ms. ``None`` where no
call took a kernel route."""

from bench.harness.spans import walk_under


def read(ctx, name):
    forwards = [f for r in ctx.roots
                for f in walk_under(r, "forward", under="generate")]
    seconds = sum(c.elapsed() for f in forwards for s in f.walk()
                  if s.name.startswith("dequant_matmul")
                  for c in s.children if c.name == "upload")
    return 1e3 * seconds / len(forwards) if seconds > 0 else None
