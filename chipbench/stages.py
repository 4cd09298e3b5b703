"""The program's own names in a trace: device time per join stage and idle
time per host span of the join.

The program names six device stages with ``jax.named_scope`` inside its
jitted steps (``repro.tracing``); a device operation's ``tf_op``, read by
``xplane.py``, holds them.  A fused operation carries the ``tf_op`` of its
root instruction, so a fusion is credited to the stage of its root op; an
operation whose ``tf_op`` names no stage (an asynchronous copy, or a
reduction lowered outside the caller's scope) counts as ``"(unscoped)"``.
The join's host spans (``join``, ``join.*``, ``prepare.*``) share the
profiler's clock with the device, so each idle gap goes to the innermost of
them open at its midpoint, or to ``"(none)"``.

The harness reduces a trace with ``tracing.summarize`` and hands readers
the summary, not the file; :func:`of` finds the run's trace file (the
newest under ``chipbench_out/``) and checks that its window is the one the
harness reduced before reading it.  A trace of a program without these
names reads as absent, and the readers then return None.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Optional, Sequence

import numpy as np

from chipbench import tracing, xplane

#: The program's stage vocabulary (``repro.tracing.STAGES``).
STAGES = ("expand", "dedup", "window", "verdict", "compact", "verify")
UNSCOPED = "(unscoped)"
NO_SPAN = "(none)"
PROGRAM_SPAN = re.compile(r"^(join|join\..+|prepare\..+)$")
TRACES = os.path.join("chipbench_out", "*", "trace", "plugins", "profile",
                      "*", "*.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def stage_of(tf_op: str) -> str:
    """The innermost stage named in ``tf_op``, or ``"(unscoped)"``."""
    for part in reversed(tf_op.split("/")):
        if part.rstrip(":") in STAGES:
            return part.rstrip(":")
    return UNSCOPED


def scopes(trace: tracing.Trace, tf_ops: Dict[str, str]) -> Dict[str, float]:
    """Device seconds per stage: leaf operations clipped to the window,
    averaged over the devices that ran an operation."""
    per = []
    for dev in trace.devices:
        d = tracing._clip(dev, trace.window)
        if not len(d.start):
            continue
        total: Dict[str, float] = {}
        for name, dur, leaf in zip(d.names, d.end - d.start,
                                   tracing._leaves(d)):
            if leaf:
                stage = stage_of(tf_ops.get(name, ""))
                total[stage] = total.get(stage, 0.0) + dur * 1e-9
        per.append(total)
    keys = sorted({k for t in per for k in t})
    return {k: float(np.mean([t.get(k, 0.0) for t in per])) for k in keys}


def program_idle(trace: tracing.Trace) -> Dict[str, float]:
    """Idle seconds of the first busy device per innermost program span
    (``join``, ``join.*``, ``prepare.*``) open at each gap's midpoint, with
    ``"(none)"`` where none is open."""
    spans = [s for s in trace.host_spans if PROGRAM_SPAN.match(s[0])]
    only = dataclasses.replace(trace, host_spans=spans)
    gaps = tracing.idle_gaps(only, k=len(spans) + 1)
    return {NO_SPAN if name == "(no host span)" else name: t
            for name, t in gaps}


def reduce(path: str) -> dict:
    """``window_s``, ``scopes`` and ``program_idle`` of one trace file."""
    tr = tracing.load(path)
    return {"window_s": tracing.window_seconds(tr),
            "scopes": scopes(tr, xplane.device_tf_ops(path)),
            "program_idle": program_idle(tr)}


def find(window_s: float, root: str = ROOT) -> Optional[dict]:
    """The reduction of the newest trace under ``root`` whose window lasts
    ``window_s`` exactly (the harness's summary of the same file)."""
    paths = sorted(glob.glob(os.path.join(root, TRACES)),
                   key=os.path.getmtime, reverse=True)
    for path in paths:
        try:
            tr = tracing.load(path)
        except ValueError:          # no window span: not a benchmark trace
            continue
        if tracing.window_seconds(tr) == window_s:
            return reduce(path)
    return None


def of(run, root: str = ROOT) -> Optional[dict]:
    """The run's reduction (cached on the run, and copied to its notes), or
    None where the run was not traced or its trace names no stage and no
    program span."""
    if run.trace is None:
        return None
    if not hasattr(run, "stages"):
        found = find(run.trace["window_s"], root)
        if found is not None and (set(found["scopes"]) - {UNSCOPED}
                                  or set(found["program_idle"]) - {NO_SPAN}):
            run.notes["scopes"] = found["scopes"]
            run.notes["program_idle"] = found["program_idle"]
        else:
            found = None
        run.stages = found
    return run.stages


def seconds_per_join(run, names: Sequence[str]) -> Optional[float]:
    """Device seconds of the stages ``names`` per join of the window, or
    None where the trace names no stage."""
    found = of(run)
    if found is None or not set(found["scopes"]) - {UNSCOPED}:
        return None
    joins = getattr(run, "joins", 0)
    if not joins:
        return None
    return sum(found["scopes"].get(n, 0.0) for n in names) / joins
