"""Profiler capture and the reduction from a trace to the benchmark's
device numbers: busy time (the union of the intervals in which an operation
ran on a device), the idle share, kernel time per layer, and a breakdown of
the busiest operations and of the idle gaps by what the host was doing.

The window is the benchmark's own host span ``bench.window``; device events
are clipped to it.  A layer's kernel time is the summed device duration of
the operations whose names match one of the layer's patterns, kept in
``chipbench/layers/<layer>.json``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class DeviceOps:
    """One device's operations: names and [start, end) in ns.

    On a TPU an operation's name is its HLO instruction
    (``%pair_verdict.1 = s32[...] custom-call(...)``; a Pallas kernel is a
    custom-call named after the jitted function that calls it), and
    ``module`` is the jitted program it ran in."""

    names: List[str]
    start: np.ndarray
    end: np.ndarray
    module: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    """What the reduction reads from one trace file."""

    window: Tuple[float, float]            # ns, the bench.window span
    devices: List[DeviceOps]
    host_spans: List[Tuple[str, float, float]]   # (name, start, end) ns


def load(path: str, window_span: str = WINDOW_SPAN) -> Trace:
    """Read an ``.xplane.pb`` file written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = [], [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            ops = lines.get(OPS_LINE, [])
            mods = sorted(lines.get(MODULES_LINE, []),
                          key=lambda e: e.start_ns)
            mod_start = np.array([e.start_ns for e in mods], float)
            dev = DeviceOps([e.name for e in ops],
                            np.array([e.start_ns for e in ops], float),
                            np.array([e.start_ns + e.duration_ns
                                      for e in ops], float))
            at = np.searchsorted(mod_start, dev.start, side="right") - 1
            dev.module = [mods[i].name.split("(")[0] if i >= 0 else ""
                          for i in at]
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == window_span:
                        window = span[1:]
                    elif ev.duration_ns > 0:
                        host.append(span)
    if window is None:
        raise ValueError(f"{path}: no {window_span!r} span in the trace")
    return Trace(window=window, devices=devices, host_spans=host)


def _clip(dev: DeviceOps, window) -> DeviceOps:
    lo, hi = window
    s = np.clip(dev.start, lo, hi)
    e = np.clip(dev.end, lo, hi)
    keep = e > s
    module = dev.module or [""] * len(dev.names)
    return DeviceOps([n for n, k in zip(dev.names, keep) if k], s[keep],
                     e[keep], [m for m, k in zip(module, keep) if k])


def _leaves(dev: DeviceOps) -> np.ndarray:
    """Mask of the operations that hold no other: a ``while`` or a
    conditional spans the operations of its body on the same line."""
    order = np.lexsort((-dev.end, dev.start))     # an enclosing op first
    s, e = dev.start[order], dev.end[order]
    parent = np.zeros(len(s), bool)
    parent[:-1] = s[1:] < e[:-1]
    leaf = np.empty(len(s), bool)
    leaf[order] = ~parent
    return leaf


def short_name(name: str, module: str = "") -> str:
    """``module/%op`` from an operation's HLO text."""
    op = name.split(" = ")[0]
    return f"{module}/{op}" if module else op


def union(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Merged [start, end) intervals, int[K, 2] sorted by start."""
    if len(start) == 0:
        return np.zeros((0, 2))
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    ends = np.append(reach[idx[1:] - 1], reach[-1])
    return np.stack([s[idx], ends], axis=1)


def busy_seconds(trace: Trace) -> float:
    """Busy time averaged over the devices that ran an operation."""
    per = []
    for dev in trace.devices:
        d = _clip(dev, trace.window)
        if len(d.start):
            iv = union(d.start, d.end)
            per.append(float((iv[:, 1] - iv[:, 0]).sum()) * 1e-9)
    return float(np.mean(per)) if per else 0.0


def window_seconds(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) * 1e-9


def layer_seconds(trace: Trace, patterns: Sequence[str]) -> float:
    """Summed device time of the operations (leaves only) matching any
    pattern, averaged over the devices that ran an operation."""
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    per = []
    for dev in trace.devices:
        d = _clip(dev, trace.window)
        if not len(d.start):
            continue
        hit = np.array([bool(rx.search(n)) for n in d.names], bool)
        hit &= _leaves(d)
        per.append(float((d.end - d.start)[hit].sum()) * 1e-9)
    return float(np.mean(per)) if per else 0.0


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operations (leaves, as ``module/%op``) with the most device
    time summed over devices, as ``[name, seconds]``."""
    total: Dict[str, float] = {}
    for dev in trace.devices:
        d = _clip(dev, trace.window)
        leaf = _leaves(d) if len(d.start) else []
        for n, m, s, e, ok in zip(d.names, d.module, d.start, d.end, leaf):
            if ok:
                key = short_name(n, m)
                total[key] = total.get(key, 0.0) + (e - s) * 1e-9
    return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """Idle time of the first busy device, grouped by the innermost host
    span open at each gap's midpoint, as ``[span name, seconds]`` for the
    ``k`` largest groups (``"(no host span)"`` where none was open)."""
    lo, hi = trace.window
    devs = [_clip(d, trace.window) for d in trace.devices]
    devs = [d for d in devs if len(d.start)]
    if devs:
        iv = union(devs[0].start, devs[0].end)
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
    else:
        edges = np.array([[lo, hi]])
    gaps = edges[edges[:, 1] > edges[:, 0]]
    spans = sorted(trace.host_spans, key=lambda s: s[2] - s[1])
    starts = np.array([s[1] for s in spans])
    ends = np.array([s[2] for s in spans])
    total: Dict[str, float] = {}
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        inside = np.flatnonzero((starts <= mid) & (ends > mid)) if len(
            spans) else []
        name = spans[inside[0]][0] if len(inside) else "(no host span)"
        total[name] = total.get(name, 0.0) + (g1 - g0) * 1e-9
    return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]


def load_layer(bench_dir: str, layer: str) -> List[str]:
    """The name patterns of a layer's kernels."""
    with open(os.path.join(bench_dir, "layers", f"{layer}.json")) as f:
        return list(json.load(f)["patterns"])


class Tracer:
    """Opens the benchmark's window span, and with ``enabled`` records a
    profiler trace of the window into ``out_dir``."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.out_dir = out_dir
        self.path: Optional[str] = None

    @contextlib.contextmanager
    def window(self):
        import jax

        if self.enabled:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans: TraceMe only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            if self.enabled:
                jax.profiler.stop_trace()
                found = glob.glob(os.path.join(
                    self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
                self.path = found[0] if found else None

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)


def summarize(path: str, layers: Dict[str, Iterable[str]]) -> dict:
    """Everything the harness takes from one trace."""
    tr = load(path)
    return {
        "busy_s": busy_seconds(tr),
        "window_s": window_seconds(tr),
        "layers": {name: layer_seconds(tr, pats)
                   for name, pats in layers.items()},
        "device_ops": top_ops(tr),
        "idle_gaps": idle_gaps(tr),
    }
