"""Closed-loop ingest: one client saves fine-tunes of one base, back to back.

Traffic parameters (``bench/traffic/<mix>.json``):

- ``finetunes``: how many distinct fine-tunes set-up makes from the seed;
  the window saves them in turn, each under a new name;
- ``ft_rel_std``: each fine-tune's perturbation, relative to the RMS of
  the matrix it perturbs (vectors are kept).

Set-up makes the base and the fine-tunes on the device in one call,
starts the HTTP front door on a store in the run's directory, saves the
base and one warm fine-tune. The window is the saves; it closes when
the last save that started inside it has finished. The check reopens
the store from disk and compares every tensor of every acknowledged
save with what was sent, to the store's error bound, and every EXPLAIN
row with a delta against that tensor's own base vertex.
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager

import numpy as np

from bench.harness import weights
from bench.harness.cell import Check, Window
from bench.harness.store import catalog_name, open_store, save_request, serve
from bench.harness.roofline import quantized_l2
from bench.reference.tensor_store import PlainStore, bound_ratio


@dataclasses.dataclass
class State:
    cell: object
    fts: list
    store: object
    server: object
    client: object
    base_rows: dict
    root: str


def setup(cell) -> State:
    tr = cell.traffic
    base, fts = weights.make_models(cell.config, cell.seed, tr["finetunes"],
                                    tr["ft_rel_std"])
    root = str(cell.workdir / "store")
    store = open_store(cell.config, root)
    server, client = serve(cell.config, store)
    report = client.save(save_request(cell.config, "base", base))
    client.save(save_request(cell.config, "warm", fts[-1]))
    rows = {r["tensor"]: (r["dim"], r["vertex_id"]) for r in report.explain}
    return State(cell, fts, store, server, client, rows, root)


def window(st: State, seconds: float, mark=None) -> Window:
    saves, errors = [], []
    attempted = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        i = attempted
        attempted += 1
        name = f"ft{i}"
        ft = i % len(st.fts)
        try:
            if mark:
                with mark("bench.save"):
                    rep = st.client.save(save_request(st.cell.config, name,
                                                      st.fts[ft]))
            else:
                rep = st.client.save(save_request(st.cell.config, name,
                                                  st.fts[ft]))
        except Exception as exc:  # noqa: BLE001 — counted, not fatal
            errors.append(f"{name}: {exc!r}")
            continue
        saves.append((name, ft, rep))
    t1 = time.perf_counter()
    data = {"saves": saves,
            "logical_bytes": sum(r.original_bytes for _, _, r in saves),
            "page_bytes": sum(r.page_bytes for _, _, r in saves)}
    return Window(t0, t1, attempted, len(errors), errors, data)


def end_to_end(st: State, win: Window) -> dict:
    d = win.data
    if not d["saves"]:
        return {}
    return {"save_MBps": d["logical_bytes"] / 1e6 / win.seconds,
            "stored_ratio": d["page_bytes"] / d["logical_bytes"]}


@contextmanager
def recorder(st: State):
    """Record the logical shape of every distance block the probe offers
    to the chip; ops and bytes are reckoned from those, unpadded."""
    from repro.kernels import ops

    calls: list[dict] = []
    inner = ops.quantized_l2_auto

    def recording(queries, codes, *args, **kwargs):
        out = inner(queries, codes, *args, **kwargs)
        b = int(np.atleast_2d(queries).shape[0])
        n, d = (int(x) for x in np.shape(codes))
        o, nbytes = quantized_l2(b, n, d)
        calls.append({"kernel": "quantized_l2", "offloaded": out is not None,
                      "b": b, "n": n, "d": d, "ops": o, "bytes": nbytes})
        return out

    ops.quantized_l2_auto = recording
    try:
        yield calls
    finally:
        ops.quantized_l2_auto = inner


def release(st: State) -> None:
    st.client.close()
    st.server.stop()
    st.store.close()


def control(st: State, win: Window) -> list[Check]:
    """The reference in the program's place, one precision lower
    (bfloat16), read as the check reads the program."""
    import ml_dtypes

    p = st.cell.config["store"]["tolerance"]
    plain = PlainStore(ml_dtypes.bfloat16)
    worst = 0.0
    for name, ft, _ in win.data["saves"]:
        plain.save(name, st.fts[ft])
        worst = max(worst, bound_ratio(plain.load(name), st.fts[ft], p))
    return [Check("recon_err_over_bound", worst, 1.0)]


def check(st: State, win: Window) -> list[Check]:
    """Read every acknowledged save back from the reopened store."""
    saves = win.data["saves"]
    p = st.cell.config["store"]["tolerance"]
    worst, off_base = 0.0, 0
    store = open_store(st.cell.config, st.root)
    try:
        for name, ft, _ in saves:
            full = catalog_name(name)
            with store.load(full) as handle:
                got = handle.materialize()
            worst = max(worst, bound_ratio(got, st.fts[ft], p))
            rows = store.explain(full)["explain"]
            off_base += len(st.fts[ft]) - len(rows)
            for row in rows:
                if (row["outcome"] != "delta" or (row["dim"], row["vertex_id"])
                        != st.base_rows[row["tensor"]]):
                    off_base += 1
    finally:
        store.close()
    if not saves:
        return []
    return [Check("recon_err_over_bound", worst, 1.0),
            Check("rows_not_delta_on_own_base", float(off_base), 0.0)]
