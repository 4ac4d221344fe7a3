"""The device a run measures: its name, its peaks and its memory."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> dict:
    """``{"platform", "kind", "count"}`` of the chips JAX sees.

    Raises :class:`NoChip` (a ``SystemExit`` with a message, so the
    process exits non-zero and prints no result) off the TPU or when
    fewer than ``chips`` devices are visible.
    """
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or jax.default_backend() != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"bench: cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peaks(kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``kind``; unknown kinds are an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in jax.devices()]
    peaks_seen = [p for p in peaks_seen if p is not None]
    return max(peaks_seen) if peaks_seen else None
