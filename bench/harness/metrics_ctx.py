"""What a per-layer reader is handed after a traced window."""

from __future__ import annotations

import dataclasses
from functools import cached_property

from . import roofline
from .spans import window_roots

GB = 1e9


@dataclasses.dataclass
class LayerContext:
    """``win``: the driver's window; ``calls``: kernel calls the driver's
    recorder saw, each with its logical shape, ``ops``, ``bytes`` and
    whether it was ``offloaded`` to the chip; ``summary``:
    the trace reduction; ``peak``: the chip's peaks (``None`` off the
    chip); ``state``: the driver's own state."""

    win: object
    calls: list | None
    summary: object
    peak: dict | None
    state: object

    @cached_property
    def roots(self) -> list:
        """The program's root spans that started inside the window."""
        from repro.obs.trace import recent_traces

        return window_roots(recent_traces(), self.win.t0, self.win.t1)

    def per_gb(self, seconds: float) -> float | None:
        """``seconds`` per GB of the window's logical bytes."""
        nbytes = self.win.data.get("logical_bytes", 0)
        return seconds / (nbytes / GB) if nbytes else None

    def roofline_share(self, kernel_match, calls: list) -> float | None:
        """Least time of ``calls`` over the device time of the ops that
        ``kernel_match`` accepts, in %; ``None`` where either is missing."""
        if self.peak is None or not calls:
            return None
        secs, n = self.summary.kernel_seconds(kernel_match)
        if secs <= 0.0:
            return None
        least = sum(roofline.least_seconds(c["ops"], c["bytes"], self.peak)[0]
                    for c in calls)
        return 100.0 * least / secs

    def peak_share(self, calls: list) -> float | None:
        """Operations of ``calls`` over the traced window, over the bf16
        peak, in %; ``None`` where there are none or no peak."""
        if self.peak is None or not calls or self.summary.window_s <= 0:
            return None
        ops = sum(c["ops"] for c in calls)
        return 100.0 * ops / self.summary.window_s / self.peak["bf16_flops"]

    def device_idle(self) -> float | None:
        s = self.summary
        if s.window_s <= 0:
            return None
        return 100.0 * (1.0 - s.busy_s / s.window_s)
