#!/usr/bin/env python3
"""Put the split step's device time and the chip's idle gaps down to a stage.

    python3 chipbench/stages.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace-dir DIR] [--out FILE]

runs one traced run of a split cell as ``run.py --trace 1`` does, keeps the
trace, and prints one JSON line: the reduction below, the stage metrics
(``metrics``) beside the benchmark's own per-layer metrics read from the
same trace (``benchmark``), and the run's ``correct``.

The program names its stages twice (``repro.core.splitting``): host spans
(``sl.*`` ``TraceAnnotation``s) around each part of a device's round, and
``jax.named_scope``s inside ``split_grads``. A span is on the host plane of
the trace. A scope is not on the device's op events, whose names are the HLO
instruction without metadata; it is in the compiled program's text, as the
``op_name`` of each instruction, so the map from instruction to stage comes
from ``split_grads.lower(...).compile().as_text()`` and is joined to the
trace by instruction name. The backward pass carries the scope inside
``transpose(...)``.

Beside ``trace.reduce``, inside the ``bench.traced_window`` slice:

- ``clock``: the host-to-device offset and how many program executions
  matched a host completion callback; none matching is an error, since
  every host time would then sit on the wrong device time;
- ``spans``: per ``bench.``/``sl.`` span name its seconds, its self
  seconds (less the part its child spans cover) and its count, an
  occurrence across an edge of the slice counting by its share inside;
- ``idle_gaps``: each gap between programs, by the innermost ``bench.`` or
  ``sl.`` span open on the host at its middle;
- ``programs``: per program its executions and seconds, and the seconds
  of its outermost op events (an op event inside another op event of the
  same line, as a loop's body inside the loop, is not counted again);
- ``stages``: the scoped program's outermost op seconds by stage and
  direction, and the op names the map does not hold.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace  # noqa: E402

SPAN_PREFIXES = ("bench.", "sl.")
SCOPED_PROGRAM = "jit_split_grads"
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"sl\.[A-Za-z0-9_]+")
_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')

Span = Tuple[str, float, float]


# ---------------------------------------------------------------------------
# Reading
# ---------------------------------------------------------------------------


def read(path: str) -> Dict[str, object]:
    """Raw events of the trace at ``path``: per device plane its program
    executions ``(program, start, end, run_id)`` and op events
    ``(op, start, end)`` of the ``XLA Ops`` line, the host's ``bench.`` and
    ``sl.`` spans per line, and the host's completion time per run_id."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = []
    spans: List[List[Span]] = []
    completions: Dict[int, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        rid = trace._stats(ev).get("run_id")
                        modules.append((trace.program_name(ev.name),
                                        ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        None if rid is None else int(rid)))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append((trace.op_name(ev.name), ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
            devices.append({"name": plane.name, "modules": modules,
                            "ops": ops})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                mine = []
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        mine.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
                    elif ev.name == "CompleteCallbacks":
                        rid = trace._stats(ev).get("run_id")
                        if rid is not None:
                            completions.setdefault(int(rid), ev.start_ns)
                if mine:
                    spans.append(mine)
    return {"devices": devices, "spans": spans, "completions": completions}


def clock(raw: Dict[str, object]) -> Dict[str, float]:
    """Host time minus device time, in ns: the smallest lag from a
    program's end on the device to the host's completion callback of the
    same run_id, which bounds it from above. Raises when no run_id
    matches."""
    lags = [raw["completions"][rid] - end
            for dev in raw["devices"] for _, _, end, rid in dev["modules"]
            if rid is not None and rid in raw["completions"]]
    if not lags:
        raise ValueError("no program execution matches a host completion "
                         "callback: host and device clocks cannot be joined")
    return {"offset_ns": float(min(lags)), "matched": len(lags)}


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name`` metadata ('' where it has none),
    over every computation of a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            meta = _OP_NAME.search(line, m.end())
            out[m.group(1)] = (re.sub(r"\\(.)", r"\1", meta.group(1))
                               if meta else "")
    return out


def stage(op_name: str) -> Tuple[str, str]:
    """``(scope, direction)`` of an op: the last ``sl.*`` scope its name
    holds, backward (``bwd``) when a ``transpose(`` encloses that scope;
    ``unscoped`` when it holds none."""
    found = list(_SCOPE.finditer(op_name))
    if not found:
        return UNSCOPED, "fwd"
    last = found[-1]
    depth: List[bool] = []   # open parentheses: is each a transpose(
    for m in re.finditer(r"transpose\(|\(|\)", op_name[:last.start()]):
        if m.group() == ")":
            if depth:
                depth.pop()
        else:
            depth.append(m.group() != "(")
    return last.group(), "bwd" if any(depth) else "fwd"


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------


def _share(s: float, e: float, lo: float, hi: float) -> float:
    """The share of [s, e] inside [lo, hi]."""
    if e <= s:
        return 1.0 if lo <= s < hi else 0.0
    return max(min(e, hi) - max(s, lo), 0.0) / (e - s)


def outermost(ops: Sequence[Tuple[str, float, float]]
              ) -> List[Tuple[str, float, float]]:
    """The op events not contained in another op event of the list."""
    out: List[Tuple[str, float, float]] = []
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        if out and op[1] >= out[-1][1] and op[2] <= out[-1][2]:
            continue
        out.append(op)
    return out


def span_totals(lines: Iterable[Sequence[Span]], lo: float, hi: float
                ) -> Dict[str, Dict[str, float]]:
    """Per span name, inside [lo, hi]: seconds, self seconds (less the
    union of the spans of the same line that it contains) and the count,
    each occurrence by its share inside."""
    out: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"seconds": 0.0, "self_seconds": 0.0, "count": 0.0})
    for line in lines:
        order = sorted(line, key=lambda sp: (sp[1], -sp[2]))
        for i, (name, s, e) in enumerate(order):
            share = _share(s, e, lo, hi)
            if share == 0.0:
                continue
            children = []
            j = i + 1
            while j < len(order) and order[j][1] <= e:
                if order[j][2] <= e:
                    children.append(order[j][1:])
                j += 1
            own = trace.clip([(s, e)], lo, hi)
            cover = trace.merge(trace.clip(children, lo, hi))
            t = out[name]
            t["seconds"] += sum(b - a for a, b in own) * 1e-9
            t["self_seconds"] += (sum(b - a for a, b in own)
                                  - sum(b - a for a, b in cover)) * 1e-9
            t["count"] += share
    return dict(out)


def attribute(lines: Iterable[Sequence[Span]], times: Sequence[float]
              ) -> List[str]:
    """For each time, the shortest span (other than the traced window) open
    on the host then: with spans that nest, the innermost."""
    spans = sorted((sp for line in lines for sp in line
                    if sp[0] != trace.WINDOW_SPAN), key=lambda sp: sp[1])
    names: List[Optional[str]] = [None] * len(times)
    open_: List[Span] = []
    i = 0
    for k in sorted(range(len(times)), key=lambda k: times[k]):
        t = times[k]
        while i < len(spans) and spans[i][1] <= t:
            open_.append(spans[i])
            i += 1
        open_ = [sp for sp in open_ if sp[2] >= t]
        best = min(open_, key=lambda sp: sp[2] - sp[1], default=None)
        names[k] = best[0] if best else "outside benchmark spans"
    return names


def reduce(raw: Dict[str, object], names: Dict[str, str],
           scoped: str = SCOPED_PROGRAM, top: int = 12) -> Dict[str, object]:
    """The traced slice's stages; times in seconds. ``names`` maps the
    instructions of program ``scoped`` to their ``op_name``s."""
    windows = [(s, e) for line in raw["spans"] for n, s, e in line
               if n == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {trace.WINDOW_SPAN} span in the trace")
    if not raw["devices"]:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = windows[0]
    clk = clock(raw)
    off = clk["offset_ns"]
    n_dev = len(raw["devices"])
    programs: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0.0, "seconds": 0.0, "op_seconds": 0.0})
    ops: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    stages: Dict[str, float] = defaultdict(float)
    unmapped: Dict[str, float] = defaultdict(float)
    gap_mid, gap_len = [], []
    busy_ns = 0.0
    for dev in raw["devices"]:
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        intervals = []
        for name, s, e, _ in modules:
            share = _share(s + off, e + off, lo, hi)
            if share == 0.0:
                continue
            intervals.append((s + off, e + off))
            p = programs[name]
            p["count"] += share
            p["seconds"] += (min(e + off, hi) - max(s + off, lo)) * 1e-9
        for op, s, e in outermost(dev["ops"]):
            inside = (min(e + off, hi) - max(s + off, lo)) * 1e-9
            if inside <= 0:
                continue
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= modules[k][2]:
                continue
            prog = modules[k][0]
            programs[prog]["op_seconds"] += inside
            ops[prog][op] += inside
            if prog == scoped:
                if op in names:
                    scope, way = stage(names[op])
                    stages[scope if scope == UNSCOPED else f"{scope}.{way}"] \
                        += inside
                else:
                    unmapped[op] += inside
        busy = trace.merge(trace.clip(intervals, lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2], strict=True):
            if g1 > g0:
                gap_mid.append(0.5 * (g0 + g1))
                gap_len.append((g1 - g0) * 1e-9)
    gaps: Dict[str, List[float]] = defaultdict(list)
    for name, length in zip(attribute(raw["spans"], gap_mid), gap_len,
                            strict=True):
        gaps[name].append(length)
    return {
        "clock": clk,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_dev,
        "idle_s": sum(gap_len) / n_dev,
        "spans": span_totals(raw["spans"], lo, hi),
        "idle_gaps": sorted(([k, sum(v) / n_dev, len(v), max(v)]
                             for k, v in gaps.items()),
                            key=lambda g: -g[1]),
        "programs": {k: {f: x / n_dev for f, x in v.items()}
                     for k, v in programs.items()},
        "program_ops": {k: sorted(((op, s / n_dev) for op, s in v.items()),
                                  key=lambda kv: -kv[1])[:top]
                        for k, v in ops.items()},
        "stages": {k: v / n_dev for k, v in stages.items()},
        "unmapped": {k: v / n_dev for k, v in unmapped.items()},
    }


# ---------------------------------------------------------------------------
# The stage metrics
# ---------------------------------------------------------------------------


def _per(total: Optional[float], count: Optional[float]) -> Optional[float]:
    return 1e3 * total / count if total is not None and count else None


def metrics(red: Dict[str, object], scoped: str = SCOPED_PROGRAM
            ) -> Dict[str, Optional[float]]:
    """The stage metrics, ms unless named otherwise; None where the trace
    holds nothing to read (no ``sl.`` span, no scoped op)."""
    spans, st = red["spans"], red["stages"]
    prog = red["programs"].get(scoped)
    runs = prog["count"] if prog else 0.0

    def span(name: str) -> Tuple[Optional[float], float]:
        t = spans.get(name)
        return (t["seconds"], t["count"]) if t else (None, 0.0)

    def device(*keys: str) -> Optional[float]:
        if not st or not any(k in st for k in keys):
            return None
        return _per(sum(st.get(k, 0.0) for k in keys), runs)

    opt, n_opt = span("sl.optimizer")
    split, _ = span("sl.split_lora")
    merge, _ = span("sl.merge_lora")
    _, n_steps = span("sl.dispatch")
    decide, n_decide = span("sl.decide")
    adapters = (None if split is None or merge is None
                else split + merge)
    return {
        "split_server_layers_fwd_ms": device("sl.server_layers.fwd"),
        "split_server_layers_bwd_ms": device("sl.server_layers.bwd"),
        "split_head_ms": device("sl.head.fwd", "sl.head.bwd"),
        "split_link_ms": device("sl.link.fwd", "sl.link.bwd"),
        "split_optimizer_host_ms": _per(opt, n_opt),
        "split_adapters_host_ms": _per(adapters, n_steps),
        "split_decide_host_ms": _per(decide, n_decide),
        "split_programs_per_step": (
            sum(p["count"] for p in red["programs"].values()) / runs
            if runs else None),
    }


def info(red: Dict[str, object], scoped: str = SCOPED_PROGRAM
         ) -> Dict[str, Optional[float]]:
    """What the metrics leave out, and the checks on the attribution: the
    device stage, unscoped ops, and the program's time between its ops (ms
    per execution); the idle seconds on ``sl.`` spans and on
    ``bench.split_run`` as shares of the idle."""
    st = red["stages"]
    prog = red["programs"].get(scoped)
    runs = prog["count"] if prog else 0.0
    idle = red["idle_s"]
    by_name = {g[0]: g[1] for g in red["idle_gaps"]}
    return {
        "split_grads_ms": _per(prog["seconds"], runs) if prog else None,
        "device_stage_ms": _per(st.get("sl.device_stage.fwd", 0.0)
                                + st.get("sl.device_stage.bwd", 0.0), runs),
        "unscoped_ms": _per(st.get(UNSCOPED, 0.0), runs),
        "unmapped_ms": _per(sum(red["unmapped"].values()), runs),
        "between_ops_ms": (_per(prog["seconds"] - prog["op_seconds"], runs)
                           if prog else None),
        "idle_on_sl_spans": (sum(v for k, v in by_name.items()
                                 if k.startswith("sl.")) / idle
                             if idle else None),
        "idle_on_bench_split_run": (by_name.get("bench.split_run", 0.0)
                                    / idle if idle else None),
    }


# ---------------------------------------------------------------------------
# One traced run
# ---------------------------------------------------------------------------


def compiled_text(cell, cfg, cut: int, device) -> str:
    """The compiled text of the split step the runner's tuner runs at
    ``cut``, lowered from shapes on ``device``."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from chipbench.reference.common import key_from_seed
    from repro.core.splitting import split_grads, split_lora
    c, t = cell.config, cell.traffic
    ref = cell.reference
    on = SingleDeviceSharding(device)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on),
            tree)

    key = key_from_seed(0, 1)
    frozen = placed(jax.eval_shape(lambda k: ref.make_frozen(c, k), key))
    lora = jax.eval_shape(lambda k: ref.make_lora(c, k), key)
    lora_dev, lora_srv = placed(jax.eval_shape(
        lambda l: split_lora(l, cut), lora))
    tokens = jax.ShapeDtypeStruct((int(t["mini_batch"]), int(t["seq_len"])),
                                  jax.numpy.int32, sharding=on)
    return split_grads.lower(
        frozen, lora_dev, lora_srv, tokens, tokens, cfg=cfg, cut=cut,
        impl="naive", compress=bool(t["int8_link"])).compile().as_text()


def scope_map(texts: Iterable[str]) -> Dict[str, str]:
    """One instruction map over the programs of several cuts; an
    instruction name that two of them give different ``op_name``s is an
    error, since the trace names the program without its cut."""
    out: Dict[str, str] = {}
    for text in texts:
        for k, v in op_names(text).items():
            if out.setdefault(k, v) != v:
                raise ValueError(f"instruction {k!r} differs between cuts")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir", default=os.path.join(ROOT,
                                                        ".bench_stages"))
    ap.add_argument("--out", help="also write the whole reduction here")
    a = ap.parse_args(argv)
    import shutil
    import jax
    from chipbench import harness
    from chipbench import run as bench
    cell = harness.load_cell(a.workload)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", bench.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = harness.check_devices(cell.chips)
    shutil.rmtree(a.trace_dir, ignore_errors=True)
    t = cell.traffic
    tracer = harness.Tracer(
        True, start_after_s=t["trace_after_frac"] * a.seconds,
        seconds=min(t["trace_seconds"], 0.5 * a.seconds),
        trace_dir=a.trace_dir)
    cfg = bench.program_config(cell)
    args = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=1, control=0)
    out, comparison = cell.runner.run(cell, args, devices,
                                      harness.CompileClock(), tracer, cfg)
    names = scope_map(compiled_text(cell, cfg, cut, devices[0])
                      for cut in out["info"]["cuts"])
    red = reduce(read(trace.find_xplane(a.trace_dir)), names)
    ctx = dict(out["ctx"], trace=trace.reduce(a.trace_dir),
               peak=harness.chip_peaks(devices[0].device_kind))
    line = {"workload": a.workload, "seed": a.seed,
            "correct": comparison.correct,
            "metrics": metrics(red), "info": info(red),
            "benchmark": {k: v["value"] for k, v in
                          harness.read_per_layer(cell, ctx).items()},
            "traced_run": out["metrics"], "clock": red["clock"],
            "window_s": red["window_s"], "busy_s": red["busy_s"],
            "idle_gaps": red["idle_gaps"][:12], "stages": red["stages"],
            "unmapped": sorted(red["unmapped"])[:20]}
    if a.out:
        with open(a.out, "w") as f:
            json.dump(dict(line, reduction=red), f, indent=1)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
