"""A run whose timed path is broken underneath comes out not correct.

Each case drives a whole cell on the CPU at tiny widths, with the chip
look skipped, after planting one fault in the program where its answer
is produced: a value altered, or half of a batch left out.
"""

from __future__ import annotations

import numpy as np
import pytest

from bench.harness import cell
from bench.tests.tiny import CPU, tiny_benchmark


def _alter_saved_codes(monkeypatch):
    """A save's delta codes off by one in one place: its answer altered."""
    import repro.core.engine as engine

    inner = engine.quantize_delta

    def altered(delta, p):
        q, meta = inner(delta, p)
        if meta.nbit:
            q = q.copy()
            q.flat[0] = q.flat[0] + 1 if q.flat[0] == 0 else q.flat[0] - 1
        return q, meta

    monkeypatch.setattr(engine, "quantize_delta", altered)


def _save_half(monkeypatch):
    """The server stores half of each model's tensors."""
    from repro.core.engine import StorageEngine

    inner = StorageEngine.save_model

    def half(self, name, architecture, tensors, *args, **kwargs):
        keep = dict(list(tensors.items())[: max(1, len(tensors) // 2)])
        if name.endswith(("/base", "base")):
            keep = tensors
        return inner(self, name, architecture, keep, *args, **kwargs)

    monkeypatch.setattr(StorageEngine, "save_model", half)


def _alter_token(monkeypatch):
    """One served token changed where the decode loop produces it."""
    import repro.launch.compressed_serve as cs

    inner = cs.greedy_decode

    def altered(provider, spec, prompt, steps, **kw):
        tokens = np.array(inner(provider, spec, prompt, steps, **kw))
        tokens[0, -1] = (tokens[0, -1] + 1) % spec.vocab_size
        return tokens

    monkeypatch.setattr(cs, "greedy_decode", altered)


def _decode_half_batch(monkeypatch):
    """Half of the batch left out: its rows repeat the other half's."""
    import repro.launch.compressed_serve as cs

    inner = cs.greedy_decode

    def half(provider, spec, prompt, steps, **kw):
        b = prompt.shape[0] // 2
        tokens = np.asarray(inner(provider, spec, prompt[:b], steps, **kw))
        return np.concatenate([tokens, tokens], axis=0)

    monkeypatch.setattr(cs, "greedy_decode", half)



FAULTS = [
    ("ingest.hubert-xlarge", _alter_saved_codes),
    ("ingest.hubert-xlarge", _save_half),
    ("decode.internlm2-1.8b", _alter_token),
    ("decode.internlm2-1.8b", _decode_half_batch),
]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__.strip('_')}" for w, f in FAULTS])
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch,
                                          tmp_path):
    bench = tiny_benchmark(tmp_path)
    fault(monkeypatch)
    line = cell.run(bench, workload, 2**32 + 9, 0.3, False,
                    tmp_path / "run", CPU)
    # Caught by the comparison, not by a crash of the run.
    assert line["failed"] == 0 and line["checks"], line
    assert line["correct"] is False, line["checks"]
