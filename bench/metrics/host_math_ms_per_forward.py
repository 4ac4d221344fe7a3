"""Host math of one forward: a ``forward`` span's time less its seam
calls' ``upload`` and ``wait`` spans (host attention, norms, rope,
embedding gather, argmax, and the matmuls the seam keeps on the host),
averaged over the window's ``forward`` spans, in ms."""

from bench.harness.spans import walk_under


def read(ctx, name):
    forwards = [f for r in ctx.roots
                for f in walk_under(r, "forward", under="generate")]
    if not forwards:
        return None
    seconds = sum(f.elapsed() for f in forwards) - sum(
        c.elapsed() for f in forwards for s in f.walk()
        if s.name.startswith("dequant_matmul")
        for c in s.children if c.name in ("upload", "wait"))
    return 1e3 * seconds / len(forwards)
