"""From the profiler's trace to device busy time, kernel time and idle gaps.

A traced window runs inside :class:`Profile`: the JAX profiler with the
Python tracer off, and one host annotation, ``bench.window``, around the
window. That annotation fixes the window on the trace's clock, and the
host clock reading taken as it opens maps the program's own spans onto
the same clock, so each idle gap of the device can be named by what the
host was doing in it.

Device work is the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. Busy time is the union of those intervals
inside the window, averaged over the devices used.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import time
from pathlib import Path

WINDOW_MARK = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: ``%name.1 = <type> opcode(operands...)``: an XLA op event's name is
#: its HLO instruction text; the instruction's own name and opcode are
#: what a kernel is known by (operands may name another kernel).
_HLO = re.compile(r"^%?([\w.\-]+) = .*?\b([a-z][a-z\-]*)\(")


def op_name(text: str) -> str:
    """``"quantized_l2_pallas.1 custom-call"`` from an op's HLO text."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns, trace clock
    end: int


@dataclasses.dataclass
class Trace:
    """Device op events per device, and host annotation events."""

    devices: dict[str, list[Event]]
    host: list[Event]


def read_xspace(path: str | os.PathLike) -> Trace:
    """The events a reduction needs, from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Event(op_name(e.name), int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                            for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [Event(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns))
                         for e in line.events
                         if e.name.startswith("bench.")]
    return Trace(devices, host)


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merged, sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(events: list[Event], lo: int, hi: int) -> int:
    """Length of the union of ``events`` inside ``[lo, hi]``."""
    return sum(e - s for s, e in
               union(clip([(ev.start, ev.end) for ev in events], lo, hi)))


def gaps(events: list[Event], lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of ``[lo, hi]``: its complement of the busy union."""
    out, cur = [], lo
    for s, e in union(clip([(ev.start, ev.end) for ev in events], lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def op_seconds(events: list[Event], lo: int, hi: int,
               match=None) -> dict[str, float]:
    """Device seconds by op name inside the window (optionally filtered)."""
    out: dict[str, float] = {}
    for ev in events:
        if ev.end <= lo or ev.start >= hi or (match and not match(ev.name)):
            continue
        d = (min(ev.end, hi) - max(ev.start, lo)) / 1e9
        out[ev.name] = out.get(ev.name, 0.0) + d
    return out


def attribute(idle: list[tuple[int, int]],
              segments: list[tuple[int, int, str, int]]) -> dict[str, float]:
    """Idle seconds by what the host was doing.

    ``segments`` are ``(start, end, label, depth)`` host intervals; where
    several cover one instant the deepest names it. Idle time that no
    segment covers is ``"(no host span)"``.
    """
    points = []
    for i, (s, e, _, _) in enumerate(segments):
        points += [(s, 1, i), (e, 0, i)]
    for s, e in idle:
        points += [(s, 3, -1), (e, 2, -1)]
    points.sort()
    active: set[int] = set()
    in_gap = 0
    out: dict[str, float] = {}
    prev = None
    for t, kind, i in points:
        if prev is not None and t > prev and in_gap:
            if active:
                best = max(active, key=lambda j: (segments[j][3],
                                                  segments[j][0]))
                label = segments[best][2]
            else:
                label = "(no host span)"
            out[label] = out.get(label, 0.0) + (t - prev) / 1e9
        prev = t
        if kind == 1:
            active.add(i)
        elif kind == 0:
            active.discard(i)
        elif kind == 3:
            in_gap += 1
        else:
            in_gap -= 1
    return out


def span_segments(roots, to_ns) -> list[tuple[int, int, str, int]]:
    """Self intervals of the program's spans, on the trace clock.

    A span's label is its parent's name and its own; its depth is its
    depth in the tree, so the innermost span names an instant.
    """
    out = []

    def walk(span, parent: str, depth: int):
        kids = sorted(span.children, key=lambda c: c.start)
        cur = span.start
        label = f"{parent}/{span.name}" if parent else span.name
        for c in kids:
            if c.start > cur:
                out.append((to_ns(cur), to_ns(c.start), label, depth))
            cur = max(cur, c.end if c.end is not None else c.start)
            walk(c, span.name, depth + 1)
        if span.end is not None and span.end > cur:
            out.append((to_ns(cur), to_ns(span.end), label, depth))

    for r in roots:
        walk(r, "", 1)
    return out


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


@dataclasses.dataclass
class Summary:
    """What the per-layer readers and the result line take from a trace."""

    busy_s: float
    window_s: float
    top_ops: list
    idle_by_host: list
    trace: Trace
    lo: int
    hi: int

    def kernel_seconds(self, match) -> tuple[float, int]:
        """Device seconds and launches of ops whose :func:`op_name`
        ``match`` accepts, summed over devices."""
        secs, n = 0.0, 0
        for events in self.trace.devices.values():
            for ev in events:
                if ev.end > self.lo and ev.start < self.hi and match(ev.name):
                    secs += (min(ev.end, self.hi) - max(ev.start, self.lo)) / 1e9
                    n += 1
        return secs, n


def summarize(trace: Trace, window: Event, segments) -> Summary:
    lo, hi = window.start, window.end
    devs = list(trace.devices.values()) or [[]]
    busy = sum(busy_ns(evs, lo, hi) for evs in devs) / len(devs) / 1e9
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    for evs in devs:
        for k, v in op_seconds(evs, lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v / len(devs)
        for k, v in attribute(gaps(evs, lo, hi), segments).items():
            idle[k] = idle.get(k, 0.0) + v / len(devs)
    return Summary(busy, (hi - lo) / 1e9, top(ops), top(idle), trace, lo, hi)


class Profile:
    """Context manager: trace the block, then :meth:`summary` reduces it."""

    def __init__(self, log_dir: Path):
        self.log_dir = Path(log_dir)
        self._mark = None
        self.anchor_pc = None

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()
        self.anchor_pc = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self, win) -> Summary:
        from repro.obs.trace import recent_traces

        from .spans import window_roots

        files = sorted(glob.glob(str(self.log_dir / "**" / "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        trace = read_xspace(files[-1])
        window = max((e for e in trace.host if e.name == WINDOW_MARK),
                     key=lambda e: e.end - e.start)
        anchor_ns, pc0 = window.start, self.anchor_pc

        def to_ns(pc: float) -> int:
            return int(anchor_ns + (pc - pc0) * 1e9)

        segments = [(e.start, e.end, e.name, 0) for e in trace.host
                    if e.name != WINDOW_MARK]
        segments += span_segments(window_roots(recent_traces(), win.t0,
                                               win.t1), to_ns)
        return summarize(trace, window, segments)
