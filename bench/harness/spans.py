"""Reductions over the program's span trees (``repro.obs.trace``).

The program keeps completed root spans in a ring. A run takes the roots
that started inside its window; the per-layer readers then ask for
spans by name, scoped under an ancestor where a name is ambiguous:
``engine.load`` opens a span called ``probe`` too, so the index probe
of a save is ``probe`` under ``engine.save`` and nothing else.
"""

from __future__ import annotations

from collections.abc import Iterable, Iterator


def window_roots(roots: Iterable, t0: float, t1: float) -> list:
    """Closed root spans that started in ``[t0, t1]`` (perf_counter s)."""
    return [s for s in roots
            if s.end is not None and t0 <= s.start <= t1]


def walk_under(span, name: str, under: str | None = None,
               _inside: bool = False) -> Iterator:
    """Spans called ``name`` in ``span``'s tree, below an ``under`` span
    when one is given."""
    inside = _inside or under is None or span.name == under
    if span.name == name and (_inside or under is None):
        yield span
    for child in span.children:
        yield from walk_under(child, name, under, inside)


def self_seconds(span) -> float:
    """Elapsed time less the part its children cover."""
    return span.elapsed() - sum(c.elapsed() for c in span.children)


def total(roots: Iterable, name: str, under: str | None = None,
          own: bool = False) -> float:
    """Seconds in spans called ``name`` (only their self time if ``own``)."""
    fn = self_seconds if own else (lambda s: s.elapsed())
    return sum(fn(s) for r in roots for s in walk_under(r, name, under))


def by_trace(roots: Iterable) -> dict[str, list]:
    """Roots grouped by trace id: a client request and the server's tree
    for it share one."""
    out: dict[str, list] = {}
    for r in roots:
        out.setdefault(r.trace_id, []).append(r)
    return out
