"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the configuration's sizes, its source
  and its store settings;
- ``bench/traffic/<traffic>.json``: the mix's parameters, with
  ``"driver"`` naming the generator in ``bench/drivers/<driver>.py``
  that sets the cell up, drives the window and checks its answers;
- ``bench/metrics/<family>.py``: the reader of every per-layer metric
  whose name starts ``<family>`` (``device_idle.ingest`` and
  ``device_idle.decode`` share ``device_idle.py``); a ``<kernel>_roofline``
  family without a file of its own is read by ``kernel_roofline.py``.
  ``read(ctx, name)`` returns a number, or ``None`` when it finds nothing.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path

from . import device as _device

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: ok when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Cell:
    """What a driver is given: the cell's entries and its run's inputs."""

    name: str
    config: dict
    traffic: dict
    seed: int
    workdir: Path
    chips: int = 1


@dataclasses.dataclass
class Window:
    """What a driver's window did: operations attempted and failed, the
    host-clock span from its start to the end of its last operation,
    and whatever its metrics and check need."""

    t0: float
    t1: float
    attempted: int
    failed: int
    errors: list
    data: dict

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def load_module(path: Path):
    """Import one file by path (names may hold dots, as metric names do)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def resolve(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """``(cell entry, config, traffic)`` of a workload, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[entry["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{entry['traffic']}.json").read_text())
    return entry, config, traffic


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end ones untraced (one
    without ``workloads`` belongs to every cell), its per-layer ones,
    each listing its cells, traced."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])]
    return [m for m in bench["per_layer"] if workload in m["workloads"]]


def reader_path(name: str) -> Path:
    """The file that reads the per-layer metric ``name``."""
    family = name.split(".")[0]
    path = BENCH_DIR / "metrics" / f"{family}.py"
    if not path.exists() and family.endswith("_roofline"):
        path = BENCH_DIR / "metrics" / "kernel_roofline.py"
    return path


@contextmanager
def mark(name: str):
    """A host span on the profiler's clock (cheap when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def run(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
        workdir: Path, device: dict, trace_dir: Path | None = None,
        compiles=None, control: bool = False) -> dict:
    """Set up, measure, check; returns the result line as a dict.

    ``device`` is what :func:`bench.harness.device.require_tpu` found;
    tests pass their own to drive a run on the CPU. ``compiles`` counts
    compilations so far; the line reports those inside the window.
    ``control`` adds the readings of the cell's control (``control``
    key): the reference one precision lower, in the program's place.
    Benchmark runs never ask for it.
    """
    entry, config, traffic = resolve(bench, workload)
    driver = load_module(BENCH_DIR / "drivers" / f"{traffic['driver']}.py")
    cell = Cell(workload, config, traffic, seed, workdir, entry["chips"])
    wanted = metrics_for(bench, workload, trace)

    t_setup = time.perf_counter()
    state = driver.setup(cell)
    setup_s = time.perf_counter() - t_setup

    recorder = driver.recorder(state) if trace else nullcontext()
    profile = None
    if trace:
        from . import xplane
        profile = xplane.Profile(trace_dir or workdir / "trace")
    n_compiled = compiles() if compiles else 0
    with (profile or nullcontext()), recorder as calls:
        win = driver.window(state, seconds, mark if trace else None)
    n_compiled = (compiles() - n_compiled) if compiles else None
    memory = _device.memory_peak_bytes()

    metrics: dict[str, dict] = {}
    out_device = dict(device, memory_peak_bytes=memory)
    breakdown = None
    if trace:
        from .metrics_ctx import LayerContext
        summary = profile.summary(win)
        ctx = LayerContext(win=win, calls=calls, summary=summary,
                           peak=_peak(device), state=state)
        for m in wanted:
            value = load_module(reader_path(m["name"])).read(ctx, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out_device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.top_ops,
                     "idle_gaps": summary.idle_by_host}
    else:
        values = dict(driver.end_to_end(state, win), setup_s=setup_s)
        for m in wanted:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    driver.release(state)
    checks = driver.check(state, win)
    correct = (win.failed == 0 and bool(checks)
               and all(c.ok for c in checks))
    line = {"correct": correct, "attempted": win.attempted,
            "failed": win.failed, "metrics": metrics, "device": out_device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if control:
        line["control"] = {c.name: {"value": c.value, "limit": c.limit}
                           for c in driver.control(state, win)}
    line["window_compiles"] = n_compiled
    line["errors"] = win.errors[:5]
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return line


def _peak(device: dict) -> dict | None:
    try:
        return _device.peaks(device["kind"])
    except KeyError:
        if device.get("platform") == "tpu":
            raise
        return None  # a CPU rehearsal: no peaks, no shares of them


def print_line(line: dict) -> None:
    """Checks as the last lines of stderr, the result as stdout's last."""
    for name, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
