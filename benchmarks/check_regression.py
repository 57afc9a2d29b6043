"""CI perf gate over the BENCH_*.json trajectory.

Validates freshly produced benchmark payloads against their schemas and
compares their ``gates`` (jitted hot-path wall times, seconds) to the
committed baseline at the repo root.  Fails (exit 1) when any gated path is
more than ``--threshold`` times slower than the baseline — by design only
*jitted* hot paths are gated (``tiered_card_grid`` round times, compiled jnp
kernel probes); Pallas interpret-mode times are never emitted as gates
because CPU interpret mode is far too noisy to gate.

    # schema validation only (fails on malformed output)
    python benchmarks/check_regression.py --validate BENCH_kernels.json ...

    # full gate: fresh outputs vs committed baseline
    python benchmarks/check_regression.py \
        --baseline-dir bench_baseline --fresh-dir . --threshold 2.0
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple

# repo root on sys.path so the splint unit registry is importable when this
# runs as `python benchmarks/check_regression.py` from CI
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.splint.units import check_key_units  # noqa: E402

BENCH_FILES = ("BENCH_kernels.json", "BENCH_card_calibration.json",
               "BENCH_fleet_scale.json", "BENCH_churn.json",
               "BENCH_serving.json", "BENCH_hierarchy.json")

# required top-level keys per schema tag; every payload must carry
# "schema", "mode", and a (possibly empty) "gates" dict of positive floats
REQUIRED_KEYS: Dict[str, Tuple[str, ...]] = {
    "bench-kernels/v1": ("probes", "roofline_fit", "latency_tables"),
    "bench-card-calibration/v1": ("dryrun_status", "dryrun_rows", "measured"),
    "bench-fleet-scale/v1": ("scaling", "big_fleet"),
    "bench-churn/v1": ("sweep", "devices", "quorum"),
    "bench-serving/v1": ("sweep", "arch", "engine"),
    "bench-hierarchy/v1": ("sweep", "arch", "rounds"),
}


def validate(path: str) -> List[str]:
    """Return a list of schema errors (empty = valid)."""
    errors = []
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    schema = payload.get("schema")
    if schema not in REQUIRED_KEYS:
        return [f"{path}: unknown schema {schema!r} "
                f"(expected one of {sorted(REQUIRED_KEYS)})"]
    for key in REQUIRED_KEYS[schema] + ("mode", "gates"):
        if key not in payload:
            errors.append(f"{path}: missing required key {key!r}")
    gates = payload.get("gates")
    if not isinstance(gates, dict):
        errors.append(f"{path}: 'gates' must be a dict")
    else:
        for name, val in gates.items():
            if not isinstance(val, (int, float)) or not val > 0 \
                    or val != val or val == float("inf"):
                errors.append(f"{path}: gate {name!r} must be a positive "
                              f"finite number, got {val!r}")
        # gates are wall seconds by contract: every key must carry a
        # time[s] suffix and no alias/mixed unit tokens (splint registry)
        errors += check_key_units(gates.keys(), context=path,
                                  require="time[s]")
    if schema == "bench-kernels/v1" and not errors:
        tables = payload["latency_tables"]
        if not tables:
            errors.append(f"{path}: latency_tables is empty")
        for arch, tab in tables.items():
            if tab.get("schema") != "latency-table/v1":
                errors.append(f"{path}: latency table {arch!r} has bad "
                              f"schema tag {tab.get('schema')!r}")
    if schema == "bench-card-calibration/v1" and not errors:
        if not payload["measured"].get("rows"):
            errors.append(f"{path}: measured.rows is empty — the "
                          "no-dryrun fallback must still calibrate")
    if schema == "bench-churn/v1" and not errors:
        if not payload["sweep"]:
            errors.append(f"{path}: sweep is empty")
        for row in payload["sweep"]:
            frac = row.get("survivor_fraction")
            if not isinstance(frac, (int, float)) or not 0.0 <= frac <= 1.0:
                errors.append(f"{path}: survivor_fraction {frac!r} "
                              "not in [0, 1]")
    if schema == "bench-serving/v1" and not errors:
        sweep = payload["sweep"]
        if not sweep:
            errors.append(f"{path}: sweep is empty")
        for row in sweep:
            if row.get("drained") is not True:
                errors.append(f"{path}: sweep row slots={row.get('slots')} "
                              f"adapters={row.get('adapters')} did not "
                              "drain — throughput numbers are meaningless")
            for key in ("requests_per_s", "tokens_per_sec", "mean_ttft_s"):
                val = row.get(key)
                if not isinstance(val, (int, float)) or not val > 0 \
                        or val != val or val == float("inf"):
                    errors.append(f"{path}: sweep {key} must be a positive "
                                  f"finite number, got {val!r}")
        # the point of the sweep is a slot x adapter grid: require at least
        # two distinct values along each axis
        for axis in ("slots", "adapters"):
            vals = {row.get(axis) for row in sweep}
            if len(vals) < 2:
                errors.append(f"{path}: sweep covers only {sorted(vals)} "
                              f"for {axis!r} (need >= 2 distinct values)")
    if schema == "bench-hierarchy/v1" and not errors:
        sweep = payload["sweep"]
        if not sweep:
            errors.append(f"{path}: sweep is empty")
        for row in sweep:
            for key in ("mean_round_s", "mean_delay_s", "mean_energy_j"):
                val = row.get(key)
                if not isinstance(val, (int, float)) or not val > 0 \
                        or val != val or val == float("inf"):
                    errors.append(f"{path}: sweep {key} must be a positive "
                                  f"finite number, got {val!r}")
        # the point of the sweep is a servers x fleet-size grid: require at
        # least two distinct values along each axis
        for axis in ("servers", "devices"):
            vals = {row.get(axis) for row in sweep}
            if len(vals) < 2:
                errors.append(f"{path}: sweep covers only {sorted(vals)} "
                              f"for {axis!r} (need >= 2 distinct values)")
    return errors


def compare_gates(baseline_path: str, fresh_path: str,
                  threshold: float) -> List[str]:
    """Return regression messages (empty = gate green)."""
    with open(baseline_path) as f:
        base = json.load(f).get("gates", {})
    with open(fresh_path) as f:
        fresh = json.load(f).get("gates", {})
    common = sorted(set(base) & set(fresh))
    if base and fresh and not common:
        return [f"{fresh_path}: no gate keys overlap the baseline "
                f"({sorted(base)[:3]}... vs {sorted(fresh)[:3]}...) — "
                "schema drift?"]
    failures = []
    for name in common:
        ratio = fresh[name] / base[name]
        marker = "FAIL" if ratio > threshold else "ok"
        print(f"  gate {name}: {base[name]:.6g}s -> {fresh[name]:.6g}s "
              f"({ratio:.2f}x) {marker}")
        if ratio > threshold:
            failures.append(f"{fresh_path}: {name} regressed {ratio:.2f}x "
                            f"(> {threshold:.1f}x allowed)")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--validate", nargs="+", metavar="FILE",
                    help="only validate these payloads, no baseline compare")
    ap.add_argument("--baseline-dir", default=".",
                    help="directory holding the committed BENCH_*.json")
    ap.add_argument("--fresh-dir", default=".",
                    help="directory holding the freshly produced BENCH_*.json")
    ap.add_argument("--threshold", type=float, default=2.0,
                    help="fail on > threshold x slowdown of a gated path")
    args = ap.parse_args()

    errors: List[str] = []
    if args.validate:
        for path in args.validate:
            errors += validate(path)
    else:
        for name in BENCH_FILES:
            fresh = os.path.join(args.fresh_dir, name)
            base = os.path.join(args.baseline_dir, name)
            if not os.path.exists(fresh):
                errors.append(f"{fresh}: missing fresh benchmark output")
                continue
            errors += validate(fresh)
            if not os.path.exists(base):
                print(f"  {name}: no committed baseline yet — skipping "
                      "compare (first run)")
                continue
            print(f"{name}:")
            errors += compare_gates(base, fresh, args.threshold)

    if errors:
        print("\nbench gate FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print("bench gate green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
