"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

On a TPU the trace holds, per chip, a plane ``/device:TPU:<n>`` whose line
``XLA Modules`` has one event per execution of a compiled program (named
``jit_<function>(<fingerprint>)``) and whose line ``XLA Ops`` has one event
per HLO operation. The host plane ``/host:CPU`` has the benchmark's
``TraceAnnotation`` spans on the line of the Python thread, and runtime
lines whose ``CompleteCallbacks`` events carry the ``run_id`` of the
program execution they complete.

Device and host timestamps come from different clocks. The offset is
estimated as the smallest lag from a program's end on the device to the
host's completion callback for that same ``run_id``, which bounds it from
above; an execution cannot complete on the host before it ends.

From those it computes, inside the benchmark's ``bench.traced_window``
span: the device busy time (the union of program intervals, averaged over
chips), the device time and count of each program, the operations that
took most device time, and the idle gaps, each attributed to the innermost
benchmark span open on the host at the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.traced_window"
SPAN_PREFIX = "bench."
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def program_name(event_name: str) -> str:
    """``jit_split_grads(123)`` -> ``jit_split_grads``."""
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def _stats(event) -> Dict[str, object]:
    return {k: v for k, v in event.stats}


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def read(path: str) -> Dict[str, object]:
    """Raw events of the trace at ``path``: per device plane its program
    and op events, the host spans, and the clock offset (ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = []
    spans: List[Tuple[str, float, float]] = []
    completions: Dict[int, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((program_name(ev.name), ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        _stats(ev).get("run_id")))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append((op_name(ev.name), ev.start_ns,
                                    ev.duration_ns))
            devices.append({"name": plane.name, "modules": modules,
                            "ops": ops})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif ev.name == "CompleteCallbacks":
                        rid = _stats(ev).get("run_id")
                        if rid is not None:
                            completions.setdefault(int(rid), ev.start_ns)
    lags = [completions[int(rid)] - end
            for dev in devices for _, _, end, rid in dev["modules"]
            if rid is not None and int(rid) in completions]
    offset = min(lags) if lags else 0.0
    return {"devices": devices, "spans": spans, "offset_ns": offset}


def _innermost(spans, t: float) -> str:
    best: Optional[Tuple[float, str]] = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t <= e:
            if best is None or e - s < best[0]:
                best = (e - s, name)
    return best[1] if best else "outside benchmark spans"


def reduce(trace_dir: str, top: int = 10) -> Dict[str, object]:
    """The traced window's numbers; times in seconds."""
    raw = read(find_xplane(trace_dir))
    windows = [(s, e) for n, s, e in raw["spans"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]
    off = raw["offset_ns"]
    devices = raw["devices"]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy_total = 0.0
    programs: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"seconds": 0.0, "count": 0})
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, List[float]] = defaultdict(list)
    for dev in devices:
        intervals = []
        for name, s, e, _ in dev["modules"]:
            s, e = s + off, e + off
            if e <= lo or s >= hi:
                continue
            intervals.append((s, e))
            # an execution across an edge of the slice counts by the
            # share of it inside, so that time per execution holds
            inside = min(e, hi) - max(s, lo)
            p = programs[name]
            p["seconds"] += inside * 1e-9
            p["count"] += inside / (e - s) if e > s else 1
        busy = merge(clip(intervals, lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for name, s, d in dev["ops"]:
            if lo <= s + off < hi:
                ops[name] += d * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2], strict=True):
            if g1 > g0:
                gaps[_innermost(raw["spans"], 0.5 * (g0 + g1))].append(
                    (g1 - g0) * 1e-9)
    n = len(devices)
    window_s = (hi - lo) * 1e-9
    busy_s = busy_total * 1e-9 / n
    idle = sorted(((f"{k} (gaps {len(v)}, longest {max(v)!r} s)", sum(v) / n)
                   for k, v in gaps.items()), key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "programs": {k: {"seconds": v["seconds"] / n, "count": v["count"] / n}
                     for k, v in programs.items()},
        "device_ops": sorted(((k, v / n) for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": idle[:top],
    }


def program_seconds(reduced: Dict[str, object], prefix: str
                    ) -> Tuple[float, float]:
    """Device seconds and executions of the programs named ``prefix*``."""
    secs = count = 0.0
    for name, p in reduced["programs"].items():
        if name.startswith(prefix):
            secs += p["seconds"]
            count += p["count"]
    return secs, count
