#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic
mix, limits and per-layer readers are files under ``chipbench/`` found by
name (see ``harness.py``). With ``--trace 0`` the result line carries the
cell's end-to-end metrics; with ``--trace 1`` the profiler records a slice
of the window and the line carries the per-layer metrics, ``busy_s``,
``window_s`` and a ``breakdown``. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

``--control 1`` puts the reference, computed one precision lower, in the
program's place for the comparison that decides ``correct``; the
benchmark's own runs never pass it.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness  # noqa: E402

harness.PROCESS_START = START
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def program_config(cell):
    """The program's registry entry with every value the configuration
    file states put in: the file is the configuration as it is run."""
    import dataclasses
    from repro.configs.base import LoRAConfig, get_config
    c = cell.config
    cfg = get_config(c["registry"])
    fields = {f: c[k] for k, f in cell.reference.PROGRAM_FIELDS.items()
              if k in c}
    lora = LoRAConfig(rank=c["lora"]["rank"], alpha=c["lora"]["alpha"],
                      targets=tuple(c["lora"]["targets"]),
                      dtype=c["lora"]["dtype"])
    return dataclasses.replace(cfg, lora=lora, **fields)


def run_cell(args, *, require_tpu=True, cell=None):
    """One run; returns (result dict, Comparison). ``require_tpu=False``
    is for tests on the CPU, which skip the look for a chip."""
    import jax
    cell = cell or harness.load_cell(args.workload)
    if require_tpu:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = (harness.check_devices(cell.chips) if require_tpu
               else jax.devices()[:cell.chips])
    clock = harness.CompileClock()
    t = cell.traffic
    if os.path.isdir(TRACE_DIR):
        shutil.rmtree(TRACE_DIR)
    tracer = harness.Tracer(
        bool(args.trace), start_after_s=t["trace_after_frac"] * args.seconds,
        seconds=min(t["trace_seconds"], 0.5 * args.seconds),
        trace_dir=TRACE_DIR)
    out, comparison = cell.runner.run(cell, args, devices, clock, tracer,
                                      program_config(cell))
    result = {"correct": comparison.correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    device = harness.device_info(devices, out["memory"])
    if args.trace:
        from chipbench import trace
        reduced = trace.reduce(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = dict(out["ctx"], trace=reduced,
                   peak=harness.chip_peaks(devices[0].device_kind))
        result["metrics"] = harness.read_per_layer(cell, ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = {
            m["name"]: {"value": float(out["metrics"][m["name"]]),
                        "unit": m["unit"]}
            for m in cell.end_to_end}
    result["device"] = device
    result["info"] = out.get("info", {})
    return result, comparison


def main():
    args = parse()
    result, comparison = run_cell(args)
    harness.emit(result, comparison)


if __name__ == "__main__":
    main()
