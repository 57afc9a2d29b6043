"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with ShapeDtypeStruct inputs (no allocation).

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out dryrun.jsonl

Per combo this prints/records: memory_analysis (fits?), cost_analysis
(FLOPs/bytes for §Roofline), and the collective schedule parsed from the
lowered HLO. Failures here are bugs in the sharding config.

The production meshes need 512 devices; ``main`` asks the CPU backend for
them before JAX starts. A caller of ``lower_combo`` sets
``--xla_force_host_platform_device_count`` itself.
"""
import argparse
import dataclasses as _dc
import json
import os
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ARCH_IDS, INPUT_SHAPES, InputShape,
                                ModelConfig, get_config,
                                long_context_variant)
from repro.data.pipeline import batch_specs
from repro.launch import sharding as shd
from repro.launch.analysis import analyze_compiled, model_flops
from repro.launch.mesh import make_production_mesh
from repro.launch.serve import make_prefill_step, make_serve_step
from repro.launch.train import make_train_step
from repro.models import model as model_lib
from repro.optim import adamw, constant_schedule

DRYRUN_ARCHS = tuple(a for a in ARCH_IDS if a != "llama32-1b")


def combo_config(arch: str, shape_name: str) -> Optional[ModelConfig]:
    """Config for (arch, shape) or None if the combo is skipped (DESIGN.md §4)."""
    cfg = get_config(arch)
    if shape_name == "long_500k":
        return long_context_variant(cfg)
    return cfg


def _lower_one(cfg: ModelConfig, shape: InputShape, mesh, *, cut: int,
               unroll: bool, compile_: bool, microbatches: int = 1,
               remat: bool = True):
    """Lower (and optionally compile) one step program. Returns
    (lowered, compiled_or_None)."""
    from repro import shardctx

    params_avals = model_lib.abstract_params(cfg)
    pspecs = shd.param_specs(cfg, params_avals, mesh)
    pshard = shd.to_named(pspecs, mesh)
    params_in = shd.attach(params_avals, pshard)
    bspecs = shd.to_named(shd.batch_specs_for(cfg, mesh, shape.kind,
                                              shape.global_batch, cut), mesh)

    with mesh, shardctx.mesh_ctx(mesh):
        if shape.kind == "train":
            optimizer = adamw(constant_schedule(1e-4))
            opt_avals = jax.eval_shape(optimizer.init, params_avals["lora"])
            opt_specs = shd.opt_state_specs(pspecs["lora"])
            opt_in = shd.attach(opt_avals, shd.to_named(opt_specs, mesh))
            step = make_train_step(cfg, optimizer, cut=cut, unroll=unroll,
                                    microbatches=microbatches, remat=remat)
            batch_avals = batch_specs(cfg, shape, cut)
            batch_in = shd.attach(batch_avals, bspecs)
            lowered = jax.jit(step, donate_argnums=(1, 2)).lower(
                params_in["frozen"], params_in["lora"], opt_in, batch_in)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, unroll=unroll)
            batch_avals = batch_specs(cfg, shape)
            key = "embeds" if cfg.input_mode == "embeds" else "tokens"
            inp = shd.attach({key: batch_avals[key]}, bspecs)[key]
            lowered = jax.jit(step).lower(
                params_in["frozen"], params_in["lora"], inp)
        else:  # decode
            step = make_serve_step(cfg, unroll=unroll)
            cache_avals = jax.eval_shape(
                lambda: model_lib.init_cache(cfg, shape.global_batch,
                                             shape.seq_len))
            cspecs = shd.cache_specs(cfg, cache_avals, mesh,
                                     shape.global_batch)
            cache_in = shd.attach(cache_avals, shd.to_named(cspecs, mesh))
            batch_avals = batch_specs(cfg, shape)
            key = "embeds" if cfg.input_mode == "embeds" else "tokens"
            inp = shd.attach({key: batch_avals[key]}, bspecs)[key]
            t_aval = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(step, donate_argnums=(2,)).lower(
                params_in["frozen"], params_in["lora"], cache_in, inp, t_aval)
        compiled = lowered.compile() if compile_ else None
    return lowered, compiled


def _cost_triple(compiled, chips) -> Dict:
    text = compiled.as_text()
    roof, coll, _mem = analyze_compiled(compiled, text, chips)
    return {"flops": roof.flops, "hbm_bytes": roof.hbm_bytes,
            "collective_bytes": float(coll.total_bytes),
            "counts": coll.counts, "bytes_by_kind": coll.bytes_by_kind}


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                cut: int = 0, compile_: bool = True, unroll: bool = False,
                roofline_probe: bool = True, microbatches: int = 1,
                remat: bool = True, capacity_factor: float = 0.0,
                kv_int8: bool = False) -> Dict:
    """Full-depth lower+compile (sharding proof + memory analysis) plus a
    depth-1/depth-2 unrolled probe pair for exact roofline terms:

      term(L) = term(1) + (L - 1) * (term(2) - term(1))

    XLA's HloCostAnalysis counts a scan body once regardless of trip count,
    so the full-depth scan numbers undercount by ~L x; the probe pair fixes
    that exactly for uniform layer stacks (all assigned archs are uniform).
    """
    cfg = combo_config(arch, shape_name)
    if capacity_factor:
        cfg = _dc.replace(cfg, capacity_factor=capacity_factor)
    if kv_int8:
        cfg = _dc.replace(cfg, kv_cache_dtype="int8")
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rec: Dict = {"arch": arch, "shape": shape_name, "unroll": unroll,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "cut": cut, "microbatches": microbatches, "remat": remat,
                 "capacity_factor": capacity_factor or None,
                 "kv_int8": kv_int8, "ok": False}

    t0 = time.time()
    lowered, compiled = _lower_one(cfg, shape, mesh, cut=cut, unroll=unroll,
                                   compile_=compile_,
                                   microbatches=microbatches, remat=remat)
    rec["lower_compile_s"] = round(time.time() - t0, 1)
    if not compile_:
        rec["ok"] = True
        return rec

    text = compiled.as_text()
    roof_raw, coll_raw, mem = analyze_compiled(compiled, text, chips)
    rec["memory"] = mem
    rec["raw_scan_costs"] = {"flops": roof_raw.flops,
                             "hbm_bytes": roof_raw.hbm_bytes,
                             "collective_bytes": float(coll_raw.total_bytes)}

    n_layers_eff = cfg.n_layers - cut
    if roofline_probe and not unroll and n_layers_eff >= 2:
        t1 = time.time()
        probes = []
        for depth in (1, 2):
            cfg_p = _dc.replace(cfg, n_layers=depth)
            # probes always run microbatches=1: total FLOPs/bytes per step
            # are mb-invariant (only *peak* memory changes, and that comes
            # from the full-depth compile's memory_analysis)
            _, comp_p = _lower_one(cfg_p, shape, mesh, cut=0, unroll=True,
                                   compile_=True, microbatches=1, remat=remat)
            probes.append(_cost_triple(comp_p, chips))
        rec["probe_s"] = round(time.time() - t1, 1)
        p1, p2 = probes
        L = n_layers_eff

        def extrap(key):
            return p1[key] + (L - 1) * (p2[key] - p1[key])

        flops = extrap("flops")
        hbm = extrap("hbm_bytes")
        coll_b = extrap("collective_bytes")
        counts = {k: p1["counts"].get(k, 0)
                  + (L - 1) * (p2["counts"].get(k, 0)
                               - p1["counts"].get(k, 0))
                  for k in set(p1["counts"]) | set(p2["counts"])}
        from repro.launch.analysis import Roofline
        roof = Roofline(flops=flops, hbm_bytes=hbm, collective_bytes=coll_b,
                        chips=chips)
        rec["collectives"] = {"counts": counts, "total_bytes": coll_b,
                              "per_layer_bytes":
                                  p2["collective_bytes"]
                                  - p1["collective_bytes"]}
    else:
        roof = roof_raw
        rec["collectives"] = {"counts": coll_raw.counts,
                              "bytes_by_kind": coll_raw.bytes_by_kind,
                              "total_bytes": float(coll_raw.total_bytes)}

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mf = model_flops(cfg, tokens,
                     "train" if shape.kind == "train" else "inference")
    rec.update({
        "ok": True,
        "roofline": roof.as_dict(),
        "model_flops_global": mf,
        "model_flops_per_chip": mf / chips,
        "useful_flops_ratio": (mf / chips) / roof.flops if roof.flops else None,
        "tokens": tokens,
    })
    return rec


def main() -> None:
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512"]))
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--mesh", choices=["single", "multi", "both"],
                   default="single")
    p.add_argument("--cut", type=int, default=0)
    p.add_argument("--no-compile", action="store_true")
    p.add_argument("--unroll", action="store_true",
                   help="unroll layers for exact cost_analysis FLOPs "
                        "(XLA counts scan bodies once)")
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--capacity-factor", type=float, default=0.0)
    p.add_argument("--kv-int8", action="store_true")
    p.add_argument("--out", default=None, help="append JSONL records here")
    args = p.parse_args()

    combos = []
    archs = list(DRYRUN_ARCHS) if (args.all or args.arch in (None, "all")) \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape in (None, "all")) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    n_fail = 0
    for a, s, mp in combos:
        label = f"{a} x {s} x {'2x16x16' if mp else '16x16'}"
        try:
            rec = lower_combo(a, s, multi_pod=mp, cut=args.cut,
                              compile_=not args.no_compile,
                              unroll=args.unroll,
                              microbatches=args.microbatches,
                              remat=not args.no_remat,
                              capacity_factor=args.capacity_factor,
                              kv_int8=args.kv_int8)
            r = rec.get("roofline", {})
            print(f"[OK]   {label}: lower {rec.get('lower_s')}s "
                  f"compile {rec.get('compile_s', '-')}s "
                  f"dominant={r.get('dominant', '-')} "
                  f"compute={r.get('compute_s', 0):.4g}s "
                  f"memory={r.get('memory_s', 0):.4g}s "
                  f"coll={r.get('collective_s', 0):.4g}s", flush=True)
        except Exception as e:
            n_fail += 1
            rec = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if mp else "16x16", "ok": False,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[FAIL] {label}: {type(e).__name__}: {str(e)[:500]}",
                  flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
