"""Kernel micro-benchmarks + the measured-cost calibration pipeline.

Two layers:

  * the legacy ``bench_*`` functions — wall time of the Pallas kernels
    (interpret mode on CPU — correctness-path timing) vs their pure-jnp
    oracles, plus the analytic TPU-v5e VMEM/roofline numbers each kernel
    is designed against;
  * ``run()`` — the calibration pipeline: timing probes over the kernel
    ladder (``measured_cost.probe_kernels``), the roofline fit, and one
    calibrated ``LatencyTable`` per architecture config, emitted as the
    machine-readable ``BENCH_kernels.json`` the CI bench-trajectory job
    commits/uploads and ``check_regression.py`` gates.

    PYTHONPATH=src python benchmarks/kernel_bench.py [--smoke] \
        [--json BENCH_kernels.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.core.hardware import (TPU_V5E_KIND, chip_peaks,
                                 profile_from_throughput)
from repro.core.measured_cost import (build_latency_tables, fit_roofline,
                                      probe_kernels)

SCHEMA = "bench-kernels/v1"
DEFAULT_TABLE_BATCH = 4     # SimParams.mini_batch — the fleet workload shape
DEFAULT_TABLE_SEQ = 512     # SimParams.seq_len
V5E = chip_peaks(TPU_V5E_KIND)  # the chip the kernels are designed against


def _time(fn: Callable, reps: int = 3) -> float:
    jax.block_until_ready(fn())  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        # splint: ignore[trace-safety] -- timing probe: the sync IS the point
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_lora_matmul() -> Dict:
    from repro.kernels import ops, ref
    m, k, n, r = 512, 512, 512, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (m, k), jnp.float32)
    w = jax.random.normal(keys[1], (k, n), jnp.float32)
    a = jax.random.normal(keys[2], (k, r), jnp.float32)
    b = jax.random.normal(keys[3], (r, n), jnp.float32)
    t_kernel = _time(lambda: ops.lora_matmul(x, w, a, b, 2.0))
    t_ref = _time(lambda: ref.lora_matmul_ref(x, w, a, b, 2.0))
    flops = 2 * m * k * n + 2 * m * k * r + 2 * m * r * n
    # analytic: fused kernel avoids writing/re-reading the (m, r) intermediate
    hbm_saved = 2 * m * r * 4
    return {"name": "lora_matmul_512", "us_interpret": t_kernel,
            "us_jnp_ref": t_ref,
            "tpu_compute_bound_us": flops / V5E.bf16_flops_per_s * 1e6,
            "hbm_bytes_saved_by_fusion": hbm_saved}


def bench_flash_attention() -> Dict:
    from repro.kernels import ops
    from repro.models.attention import chunked_attention
    b, s, hq, hkv, d = 1, 512, 8, 4, 64
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (b, s, hq, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, hkv, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    t_kernel = _time(lambda: ops.flash_attention(q, k, v, block_q=128,
                                                 block_k=128))
    t_ref = _time(lambda: chunked_attention(q, k, v, causal=True, window=0,
                                            q_positions=pos, k_positions=pos))
    score_bytes = b * hq * s * s * 4  # what flash keeps out of HBM
    return {"name": "flash_attention_512", "us_interpret": t_kernel,
            "us_jnp_chunked": t_ref,
            "hbm_bytes_saved_vs_naive": score_bytes}


def bench_ssd_scan() -> Dict:
    from repro.kernels import ops
    from repro.models.mamba import ssd_chunked
    b, l, nh, hp, ns, chunk = 1, 512, 4, 64, 64, 128
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    xt = jax.random.normal(keys[0], (b, l, nh, hp)) * 0.2
    a = -jnp.abs(jax.random.normal(keys[1], (b, l, nh))) * 0.1
    B = jax.random.normal(keys[2], (b, l, ns)) * 0.3
    C = jax.random.normal(keys[3], (b, l, ns)) * 0.3
    t_kernel = _time(lambda: ops.ssd_scan(xt, a, B, C, chunk))
    t_ref = _time(lambda: ssd_chunked(xt, a, B, C, chunk))
    return {"name": "ssd_scan_512", "us_interpret": t_kernel,
            "us_jnp_ref": t_ref,
            "vmem_tile_bytes": chunk * chunk * 4 * 2 + chunk * (hp + 2 * ns) * 4}


def bench_flash_decode() -> Dict:
    from repro.kernels import ops
    from repro.models.attention import naive_attention
    b, s, hq, hkv, d = 2, 1024, 8, 4, 64
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (b, 1, hq, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, s, hkv, d), jnp.float32)
    t = jnp.int32(s - 1)
    t_kernel = _time(lambda: ops.flash_decode(q, k, v, t, block_k=256))
    pos = jnp.full((b, 1), s - 1, jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(s), (b, s))
    t_ref = _time(lambda: naive_attention(q, k, v, causal=True, window=0,
                                          q_positions=pos, k_positions=kpos))
    cache_bytes = 2 * b * s * hkv * d * 2  # one HBM sweep (bf16), the bound
    return {"name": "flash_decode_1k", "us_interpret": t_kernel,
            "us_jnp_ref": t_ref,
            "tpu_bandwidth_bound_us": cache_bytes / V5E.hbm_bytes_per_s * 1e6}


def run(*, smoke: bool = False, reps: int = 3) -> Dict:
    """Probe -> fit -> per-arch latency tables, as one JSON-able payload.

    ``gates`` holds the jitted hot-path times ``check_regression.py`` is
    allowed to gate on (compiled jnp probe times, keyed by kernel+shape).
    Pallas interpret-mode times are deliberately NOT gated: on CPU they
    emulate the TPU program in Python and are far too noisy.
    """
    mode = "smoke" if smoke else "full"
    probes = probe_kernels(mode=mode, reps=reps)
    fit = fit_roofline(probes)
    tables = build_latency_tables(fit, batch=DEFAULT_TABLE_BATCH,
                                  seq_len=DEFAULT_TABLE_SEQ)
    host = profile_from_throughput("bench-host", fit.ref_throughput)
    payload: Dict = {
        "schema": SCHEMA,
        "mode": mode,
        "backend": jax.default_backend(),
        "probes": [p.to_dict() for p in probes],
        "roofline_fit": fit.to_dict(),
        "host_profile": {"name": host.name, "peak_flops": host.peak_flops},
        "latency_tables": {a: t.to_dict() for a, t in tables.items()},
        "gates": {f"probe_{p.kernel}_{p.shape}_s": p.seconds for p in probes
                  if p.backend == "jnp"},
    }
    if not smoke:
        payload["kernels"] = [bench_lora_matmul(), bench_flash_attention(),
                              bench_ssd_scan(), bench_flash_decode()]
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small probe ladder only (CI bench-trajectory mode)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the BENCH_kernels.json payload here")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    payload = run(smoke=args.smoke, reps=args.reps)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json}")
    fit = payload["roofline_fit"]
    print(f"roofline fit [{payload['backend']}]: "
          f"C={fit['compute_flops_per_s']:.3g} FLOP/s "
          f"B={fit['bandwidth_bytes_per_s']:.3g} B/s "
          f"overhead={fit['overhead_s'] * 1e6:.0f}us "
          f"rel_residual={fit['rel_residual']:.3f}")
    for r in payload.get("kernels", ()):
        print(r)


if __name__ == "__main__":
    main()
