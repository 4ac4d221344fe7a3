"""Compressed-domain runtime adapter: serve matmuls straight off a snapshot.

:class:`CompressedModel` maps :meth:`LoadedModel.compressed_params` output
(int8-recentred base codes + quantized deltas, including the int4-packed
flexible-loading form at ``bits=4``) directly into the layout the fused
``dequant_matmul`` kernels expect — the full-precision weight is never
materialized. The handle's buffer-pool frame stays pinned for the life of
the serving session (snapshot semantics, see ``docs/concurrency.md``), so
repeated decode steps read codes zero-copy from the pool.

Operand-normalization details the kernels don't know about live here:

* **constant base** (``base_scale == 0``): the stored codes are all zero
  (recentred: −128) and the value lives in ``base_mid``. The kernel
  formula ``(c − bz)·bs`` reproduces it exactly with ``bz = −129``,
  ``bs = mid``.
* **zero-bit delta** (``nbit == 0``, range ≤ 2p): bin-centre dequant
  ``(q − dz + 0.5)·ds`` must yield ``delta_mid``; with all-zero codes
  that is ``dz = code_value, ds = 2·mid``.
* **int4 packing**: deltas at ``nbit <= 4`` with even K pack two unsigned
  nibble codes per byte (``kernels.ops.pack_int4`` layout) — 1.5 HBM
  bytes per weight element on TPU instead of 2.0.

Each matmul is a span of the seam (``dequant_matmul`` or
``dequant_matmul_int4``, see ``kernels.ops.dequant_matmul_auto``) that
carries its route, logical shape and operand bytes.

On the kernel route a weight's codes are put on the device on its first
matmul and stay there, in the tensor's ``scratch``, until
:meth:`CompressedModel.close`: the pinned snapshot cannot change them,
and the cache belongs to one tensor object, so it never serves another
snapshot's codes. :meth:`CompressedModel.expert_matmul` serves a group
of same-shape weights (one projection of a MoE layer's held experts)
through ``kernels.ops.dequant_matmul_group``: one round trip for the
group, its staged codes kept in a scratch of the group's own, and on the
host route each weight's own ``scratch``, as a per-weight call keeps it.
"""

from __future__ import annotations

import math

import numpy as np

from ..kernels.ops import (
    KERNEL_DISPATCH_MIN_ELEMS,
    dequant_matmul_auto,
    dequant_matmul_group,
    pack_int4,
)
from .loader import KernelNotReady, LoadedModel

__all__ = ["CompressedModel", "CompressedTensor", "KernelNotReady"]


class CompressedTensor:
    """One weight's kernel-ready operands, built once per serving session.

    ``scratch`` is the seam's per-weight cache: the host route's
    pre-scaled float32 weight, or the kernel route's device-resident
    codes. :meth:`CompressedModel.close` empties it."""

    __slots__ = ("name", "shape", "k", "n", "packed", "base", "delta",
                 "base_scale", "base_zp", "delta_scale", "delta_zp",
                 "operand_nbytes", "scratch")

    def __init__(self, name: str, entry: dict):
        shape = entry["shape"]
        if len(shape) < 2:
            raise ValueError(
                f"tensor {name!r}: matmul weights need >= 2 dims, got {shape}")
        self.name = name
        self.shape = tuple(shape)
        self.k = shape[0]
        self.n = int(math.prod(shape[1:]))
        self.base = entry["base_codes"].reshape(self.k, self.n)
        if entry["base_scale"] == 0.0:
            self.base_scale = float(entry["base_mid"])
            self.base_zp = -129.0
        else:
            self.base_scale = float(entry["base_scale"])
            self.base_zp = float(entry["base_zp"])
        nbit = entry["nbit"]
        self.packed = bool(nbit <= 4 and self.k % 2 == 0)
        if self.packed:
            # Unsigned nibble codes + unsigned zero-point (int4 kernel form).
            self.delta = pack_int4(entry["qdelta"].reshape(self.k, self.n))
            if nbit == 0:
                self.delta_scale = 2.0 * float(entry["delta_mid"])
                self.delta_zp = 0.0
            else:
                self.delta_scale = float(entry["delta_scale"])
                self.delta_zp = float(entry["delta_zp"])
        else:
            self.delta = entry["qdelta_i8"].reshape(self.k, self.n)
            if nbit == 0:
                self.delta_scale = 2.0 * float(entry["delta_mid"])
                self.delta_zp = -128.0
            else:
                self.delta_scale = float(entry["delta_scale"])
                self.delta_zp = float(entry["delta_zp_i8"])
        self.operand_nbytes = self.base.nbytes + self.delta.nbytes
        self.scratch: dict = {}


class CompressedModel:
    """Serve a :class:`LoadedModel` without materializing float weights.

    ``matmul(x, name)`` routes through ``kernels.ops.dequant_matmul_auto``
    (Pallas on TPU, decomposed gemm on CPU), whose operands stay cached
    per weight — on the device on the kernel route — for the life of the
    session; ``close()`` gives them back with the snapshot.
    ``gather_rows`` dequantizes only the requested embedding rows;
    ``vector`` reconstructs small tensors (norm gains) via
    ``tensor(name)``. Requires a kernel-ready
    handle — open it with ``load_model(name, bits=8)`` (or ``bits=4``);
    full-precision handles raise :class:`KernelNotReady` on first use.
    """

    def __init__(self, lm: LoadedModel, *,
                 min_elems: int = KERNEL_DISPATCH_MIN_ELEMS,
                 force: str | None = None):
        self.lm = lm
        self.params = lm.compressed_params()
        self.min_elems = min_elems
        self.force = force
        self._weights: dict[str, CompressedTensor] = {}
        self._groups: dict[tuple[str, ...], dict] = {}
        self._vectors: dict[str, np.ndarray] = {}
        #: Names whose bytes were served through the kernel seam — the
        #: zero-materialize acceptance test asserts ``materialize()`` /
        #: ``tensor()`` are never called for these.
        self.kernel_served: set[str] = set()

    # ------------------------------------------------------------- weights
    def weight(self, name: str) -> CompressedTensor:
        w = self._weights.get(name)
        if w is None:
            entry = self.params.kernel_operands(name)
            w = self._weights[name] = CompressedTensor(name, entry)
            self.kernel_served.add(name)
        return w

    def matmul(self, x: np.ndarray, name: str) -> np.ndarray:
        """``x @ dq(weight)`` on compressed operands; (M, K) → (M, N)."""
        w = self.weight(name)
        return dequant_matmul_auto(
            x, w.base, w.base_scale, w.base_zp, w.delta, w.delta_scale,
            w.delta_zp, packed=w.packed, min_elems=self.min_elems,
            force=self.force, scratch=w.scratch)

    def expert_matmul(self, x: np.ndarray, names, rows=None) -> np.ndarray:
        """``x @ dq(weight)`` for each weight of a same-shape group, in
        one seam call; ``x`` (M, K) shared or (E, M, K) one block per
        weight, returns (E, M, N). ``rows`` (how many of the M rows the
        caller routes to each weight) is recorded on the seam's span."""
        names = tuple(names)
        ws = [self.weight(n) for n in names]
        return dequant_matmul_group(
            x, [(w.base, w.base_scale, w.base_zp, w.delta, w.delta_scale,
                 w.delta_zp) for w in ws], [w.packed for w in ws],
            min_elems=self.min_elems, force=self.force,
            scratch=self._groups.setdefault(names, {}),
            scratches=[w.scratch for w in ws], rows=rows)

    def bytes_per_weight(self, name: str) -> float:
        """Kernel-operand bytes per weight element (2.0 int8, 1.5 int4)."""
        w = self.weight(name)
        return w.operand_nbytes / (w.k * w.n)

    # ------------------------------------------------- row-wise access
    def gather_rows(self, name: str, ids: np.ndarray) -> np.ndarray:
        """Dequantize only the gathered rows (compressed-domain embedding
        lookup) — never the full (V, d) table."""
        entry = self.params[name]
        ids = np.asarray(ids)
        codes = entry["base_codes"].reshape(entry["shape"][0], -1)[ids]
        if entry["base_scale"] == 0.0:
            base = np.full(codes.shape, float(entry["base_mid"]), np.float32)
        else:
            base = ((codes.astype(np.float32) - entry["base_zp"])
                    * entry["base_scale"])
        q = entry["qdelta"].reshape(entry["shape"][0], -1)[ids]
        nbit = entry["nbit"]
        if nbit == 0:
            delta = np.full(q.shape, float(entry["delta_mid"]), np.float32)
        else:
            delta = ((q.astype(np.float32) - entry["delta_zp"] + 0.5)
                     * entry["delta_scale"])
        self.kernel_served.add(name)
        return (base + delta).astype(np.float32)

    def vector(self, name: str) -> np.ndarray:
        """Small tensors (norm gains, biases): full reconstruct, cached."""
        v = self._vectors.get(name)
        if v is None:
            v = self._vectors[name] = self.lm.tensor(name)
        return v

    # ------------------------------------------------------------ lifecycle
    def close(self) -> None:
        """Drop every weight's cached operands (device memory included),
        then release the snapshot."""
        for w in self._weights.values():
            w.scratch.clear()
        for group in self._groups.values():
            group.clear()
        self.lm.close()
