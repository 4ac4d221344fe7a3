"""Plain reference of a tensor store, and the comparison with it.

The reference keeps every tensor it is given as it was given: a save
followed by a load returns the same float32 numbers. The store under
test promises each reconstructed element within its tolerance ``p`` of
the float32 it was sent, plus the rounding of the float32 result (half
a unit in the last place). :func:`bound_ratio` reads how much of that
promise a reconstruction uses: at most 1 where it is kept.

``PlainStore(dtype=bfloat16)`` is the control: the same reference kept
one precision lower, which breaks the promise by orders of magnitude.
"""

from __future__ import annotations

import numpy as np


class PlainStore:
    """Tensors by model name, kept in ``dtype`` and read back as float32."""

    def __init__(self, dtype=np.float32):
        self.dtype = dtype
        self._models: dict[str, dict[str, np.ndarray]] = {}

    def save(self, name: str, tensors: dict) -> None:
        self._models[name] = {k: np.asarray(v, np.float32).astype(self.dtype)
                              for k, v in tensors.items()}

    def load(self, name: str) -> dict[str, np.ndarray]:
        return {k: v.astype(np.float32) for k, v in self._models[name].items()}


def bound_ratio(got: dict, sent: dict, p: float) -> float:
    """max over elements of ``|got - sent| / (p + ulp(float32)/2)``.

    ``inf`` where a tensor is missing, extra or of another shape, or a
    value is not finite.
    """
    if list(got) != list(sent):
        return float("inf")
    worst = 0.0
    for name, want in sent.items():
        want = np.asarray(want, np.float32)
        have = np.asarray(got[name])
        if have.shape != want.shape or have.dtype != np.float32:
            return float("inf")
        err = np.abs(have.astype(np.float64) - want.astype(np.float64))
        ulp = np.spacing(np.maximum(np.abs(have), np.abs(want)))
        ratio = err / (p + 0.5 * ulp.astype(np.float64))
        top = float(ratio.max()) if ratio.size else 0.0
        if not np.isfinite(top):
            return float("inf")
        worst = max(worst, top)
    return worst
