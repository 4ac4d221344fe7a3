"""Index probe of a save: self time of ``probe`` under ``engine.save``
(``engine.load`` opens a ``probe`` of its own), per GB saved."""

from bench.harness.spans import total


def read(ctx, name):
    seconds = total(ctx.roots, "probe", under="engine.save", own=True)
    return ctx.per_gb(seconds) if seconds > 0 else None
