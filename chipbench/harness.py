"""What every cell shares: finding the cell's files by name, the device
checks, set-up and compile accounting, the traced slice, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness reads:

  configs/<config>.json    the configuration as it is run; its
                           ``reference`` key names the plain reference
                           module under ``reference/``;
  traffic/<mix>.json       the mix's parameters; its ``runner`` key names
                           the general runner under ``runners/``;
  limits/<cell>.json       the limit of each number that decides
                           ``correct``, with the readings it was set from;
  metrics/<metric>.py      one reader per per-layer metric.

A later change adds a cell, a mix or a metric by adding such files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROCESS_START = time.perf_counter()


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the file at ``path`` as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def reference(self):
        return load_module(
            os.path.join(HERE, "reference", self.config["reference"] + ".py"),
            "chipbench_reference_" + self.config["reference"])

    @property
    def runner(self):
        return load_module(
            os.path.join(HERE, "runners", self.traffic["runner"] + ".py"),
            "chipbench_runner_" + self.traffic["runner"])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The workload ``name`` of ``BENCHMARK.json`` with its files."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(os.path.join(ROOT,
                                              configs[w["config"]]["file"])),
                traffic=load_json(os.path.join(HERE, "traffic",
                                               w["traffic"] + ".json")),
                limits=load_json(os.path.join(HERE, "limits",
                                              name + ".json")),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def seed_words(seed: int, tag: int = 0) -> int:
    """A 32-bit word drawn from ``seed`` (any size) and a stream ``tag``."""
    return int(np.random.SeedSequence([int(seed) % 2**63, tag]
                                      ).generate_state(1)[0])


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(os.path.join(HERE, "peaks.json"))["chips"]
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(table)})")
    return table[device_kind]


def check_devices(chips: int):
    """The chips this cell needs, or exit non-zero without a result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"the cell needs {chips} chips; JAX found {len(devices)}")
    chip_peaks(devices[0].device_kind)
    return devices[:chips]


class CompileClock:
    """Seconds of XLA compilation (or persistent-cache fetches) and the
    number of compilations in this process."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def span(name: str):
    """A host span in the profiler's trace, opened by the benchmark's own
    files around a call into the program."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Turns the profiler on for ``seconds`` of the window, once."""

    def __init__(self, enabled: bool, start_after_s: float, seconds: float,
                 trace_dir: str):
        self.enabled = enabled
        self.start_after_s = start_after_s
        self.seconds = seconds
        self.trace_dir = trace_dir
        self._state = "idle" if enabled else "off"
        self._t0 = 0.0
        self._window_span = None

    @property
    def active(self) -> bool:
        return self._state == "on"

    def poll(self, elapsed_s: float) -> None:
        """Call between units of work with the window's elapsed seconds."""
        import jax
        if self._state == "idle" and elapsed_s >= self.start_after_s:
            jax.profiler.start_trace(self.trace_dir)
            self._window_span = span("bench.traced_window")
            self._window_span.__enter__()
            self._t0 = time.perf_counter()
            self._state = "on"
        elif self._state == "on" and \
                time.perf_counter() - self._t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax
        if self._state != "on":
            return
        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._state = "done"


def device_info(devices, memory_peak: Optional[int]) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Comparison:
    """Numbers compared against their limits; ``correct`` if each is
    finite and at most its limit."""
    numbers: Dict[str, float] = dataclasses.field(default_factory=dict)
    limits: Dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, name: str, value: float, limit: float) -> None:
        self.numbers[name] = float(value)
        self.limits[name] = float(limit)

    @property
    def correct(self) -> bool:
        return bool(self.numbers) and all(
            np.isfinite(v) and v <= self.limits[k]
            for k, v in self.numbers.items())

    def lines(self) -> List[str]:
        return [f"check {k}: {v!r} limit {self.limits[k]!r}"
                for k, v in self.numbers.items()]

    def as_json(self) -> Dict[str, Dict[str, float]]:
        return {k: {"value": v, "limit": self.limits[k]}
                for k, v in self.numbers.items()}


def read_per_layer(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Each per-layer metric's reader, found by the metric's name; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                          "chipbench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def emit(result: Dict[str, Any], comparison: Comparison) -> None:
    """The checks as the last lines on stderr, the result as the last line
    on stdout, with the checks under a key of their own that comes last."""
    for line in comparison.lines():
        print(line, file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = comparison.as_json()
    print(json.dumps(line), flush=True)


def relative_gap(a: float, b: float, floor: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), floor)


def now() -> float:
    return time.perf_counter()


def elapsed_since_start() -> float:
    return time.perf_counter() - PROCESS_START

