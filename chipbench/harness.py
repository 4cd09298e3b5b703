"""The data-driven harness: one cell of ``BENCHMARK.json``, run once.

Nothing here names a cell, a configuration, a traffic mix or a metric.  A
cell is found by name in the benchmark file; from it the harness loads

* the configuration file the benchmark names (``configs/<name>.json``),
  whose ``entry`` names the driver in ``entries/<entry>.py``;
* the traffic file ``traffic/<traffic>.json``;
* for a traced run, one reader ``metrics/<metric>.py`` per per-layer metric
  that lists the cell (or, without a ``workloads`` key, per metric whose
  ``moves`` the cell reports).

So a later cell, configuration or metric is new files plus entries in
``BENCHMARK.json``.  An entry module has ``run(ctx) -> Outcome``; a metric
reader has ``read(run) -> float | None`` and returns None where it finds
nothing to read, and the metric is then left out of the line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str):
    """Import a file by path (metric and entry names may hold dots)."""
    name = "chipbench_" + os.path.basename(path)[:-3].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str

    def entry(self):
        return load_module(os.path.join(self.bench_dir, "entries",
                                        self.config["entry"] + ".py"))

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py"))


def _reports(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    """Whether ``cell`` reports ``metric``: the cells it lists, or, where it
    lists none, every cell (end-to-end) or every cell that reports the
    end-to-end metric it moves (per-layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def resolve(spec: dict, cell_name: str, root: str,
            bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``cell_name`` of the benchmark file ``spec``; configuration
    files are relative to ``root`` (the checkout), traffic files are
    ``<bench_dir>/traffic/<traffic>.json``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r}; known: {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"] if _reports(m, cell_name, [])]
    names = [m["name"] for m in e2e]
    layer = [m for m in spec["per_layer"] if _reports(m, cell_name, names)]
    return Cell(cell_name, config, traffic, int(w["chips"]), e2e, layer,
                bench_dir)


class Record:
    """What per-layer readers read: attributes set by the entry (counters,
    host-clock spans, sizes), plus ``trace``, the reduced profiler trace of
    the window or None, and ``peaks``, the device's row of the peak
    table."""

    def __init__(self, **fields):
        self.trace = None
        self.peaks = None
        self.notes = {}
        self.__dict__.update(fields)


@dataclasses.dataclass
class Outcome:
    """What an entry hands back.  ``metrics`` holds every end-to-end value
    the entry measured; ``run`` is the record per-layer readers read;
    ``checks`` maps a short name to ``(value, limit)``: a run is correct iff
    every value is at most its limit."""

    metrics: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, tuple]
    memory_peak_bytes: int
    run: object
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)


class CompileCounter:
    """Counts XLA backend compiles and their seconds (jax.monitoring)."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


@dataclasses.dataclass
class Context:
    """What an entry gets: the cell, the run's arguments and the tools."""

    cell: Cell
    seed: int
    seconds: float
    tracer: object
    compiles: CompileCounter
    log: Callable[[str], None]
    t0: float          # process start: set-up runs from here to the window


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and jax.default_backend() != "tpu":
        raise SystemExit(f"chipbench: JAX backend is "
                         f"{jax.default_backend()!r}; this benchmark runs "
                         f"on a TPU only")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks(device: dict, require_tpu: bool) -> Optional[dict]:
    """The device's row of the peak table (an unknown TPU kind is an error;
    off the chip there is none)."""
    from chipbench import roofline

    if require_tpu:
        return roofline.peaks_for(device["kind"])
    return None


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks, default=0))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             out_dir: str, t0: Optional[float] = None,
             require_tpu: bool = True,
             log: Callable[[str], None] = None) -> dict:
    """Run the cell once and return its result line as a dict."""
    from chipbench import tracing

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    device = device_info(cell.chips, require_tpu)
    device_peaks = peaks(device, require_tpu)
    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds),
                  tracer=tracing.Tracer(bool(trace),
                                        os.path.join(out_dir, "trace")),
                  compiles=CompileCounter(), log=log,
                  t0=time.perf_counter() if t0 is None else t0)
    outcome = cell.entry().run(ctx)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    result = {"correct": all(v <= lim for v, lim in outcome.checks.values()),
              "attempted": int(outcome.attempted),
              "failed": int(outcome.failed)}
    metrics: Dict[str, dict] = {}
    notes = dict(outcome.notes)
    if trace:
        if not ctx.tracer.path:
            raise RuntimeError("the profiler wrote no trace of the window")
        readers = {m["name"]: cell.reader(m["name"]) for m in cell.per_layer}
        layers = {r.LAYER_FILE: tracing.load_layer(cell.bench_dir,
                                                   r.LAYER_FILE)
                  for r in readers.values() if hasattr(r, "LAYER_FILE")}
        t_reduce = time.perf_counter()
        summary = tracing.summarize(ctx.tracer.path, layers)
        notes["trace_reduce_s"] = time.perf_counter() - t_reduce
        run = outcome.run
        run.trace = summary
        run.peaks = device_peaks
        for m in cell.per_layer:
            value = readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        notes.update(run.notes)
    else:
        for m in cell.end_to_end:
            if m["name"] in outcome.metrics:
                metrics[m["name"]] = {"value": float(outcome.metrics[
                    m["name"]]), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["notes"] = notes
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in outcome.checks.items()}
    return result
