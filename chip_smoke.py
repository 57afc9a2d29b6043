#!/usr/bin/env python3
"""Bring-up check: the system's three hot paths on a TPU, through the normal
entry points, at the published widths of qwen3-0.6b with random weights.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded fleet sweep on four chips

With no option, four phases run in one process on one chip:

  split    ``SplitFineTuner`` over 2 Table I devices with the paper's
           Table II batch (4 x 512 tokens, 5 local epochs) at the cut CARD
           picks for the full-size model.
  serve    ``ServingEngine`` with 2 adapters, 8 slots and max_len 1024.
  card     vectorized vs scalar CARD decisions on 100 devices, then one
           round at 10^4 devices.
  kernels  every kernel of ``kernels.ops``, compiled, against
           ``kernels.ref``; the SSD scan at mamba2-370m widths.

With ``--chips 4`` only the sharded fleet sweep runs: ``simulate_fleet``
over ``make_fleet_mesh(4)`` at 10^4 devices, against the same sweep on one
device, and the two must be bit-identical.

Each phase prints one JSON line: ``compile_s`` (seconds of XLA compilation
or persistent-cache fetches), ``steady_s`` (its warm timed work), the
process's ``peak_bytes_in_use`` so far, and the checks it made. The last
line is ``{"ok": true, "device": {...}}``. A failed check raises, so the
script exits non-zero; so does any backend other than a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core.channel import SEED_STRIDE, WirelessChannel  # noqa: E402
from repro.core.cost_model import RoundContext, Workload  # noqa: E402
from repro.core.hardware import (EDGE_FLEET, SERVER_RTX4060TI,  # noqa: E402
                                 SimParams, chip_peaks,
                                 make_heterogeneous_fleet)
from repro.core.protocol import SplitFineTuner  # noqa: E402
from repro.core.scheduler import simulate_fleet  # noqa: E402
from repro.core.splitting import split_grads, split_lora  # noqa: E402
from repro.data import make_fleet_datasets  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_fleet_mesh  # noqa: E402
from repro.launch.train import make_train_step  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.optim import adamw, apply_updates, constant_schedule  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402

LR = 1e-3
FLEET_FIELDS = ("cuts", "freqs", "delays", "energies",
                "d_device", "d_uplink", "d_server", "d_downlink")

# Tolerances, each with its reason.
# The split step and the one-program train step run the same bf16 model
# with different attention blocking and remat; one bf16 ulp of the loss.
LOSS_RTOL = 2.0 ** -8
# bf16 activations through every layer against a float32 reference at
# "highest" matmul precision, on the same bf16 weights: 28 layers of
# qwen3-0.6b's kind at reduced width give 0.031 relative RMS error on the
# CPU; the limit is twice that. An 8-bit path would miss it by far.
LOGITS_REL_RMS = 2.0 ** -4
# A kernel and its oracle accumulate the same products in a different
# order, then round to bf16 (or take bf16 MXU passes on f32 operands): a
# few ulps at the top of the output range.
KERNEL_REL_MAX = 2.0 ** -6
# CARD's scalar oracle is float64 and the grid float32: a different
# decision is a near-tie only if its cost is within float32 noise.
CARD_COST_RTOL = 1e-5


class CompileClock:
    """Seconds of XLA compilation (or persistent-cache fetches) and cache
    hits in this process, read as deltas per phase."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.cache_hits


def report(phase, clock, mark, steady_s, checks, **extra):
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": phase,
        "compile_s": clock.seconds - mark[0],
        "cache_hits": clock.cache_hits - mark[1],
        "steady_s": steady_s,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        **{k: v for k, v in stats.items()
           if k.startswith("peak_") and k != "peak_bytes_in_use"},
        "checks": checks, **extra}), flush=True)


def init_params(cfg, seed):
    return jax.jit(model_lib.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


# ---------------------------------------------------------------------------
# split fine-tuning
# ---------------------------------------------------------------------------


def phase_split(cfg, clock, *, seed, sim=SimParams(), rounds=2, n_devices=2):
    mark = clock.mark()
    params = init_params(cfg, seed)
    frozen = params["frozen"]
    datasets = make_fleet_datasets(cfg, n_devices, seed=seed)
    ft = SplitFineTuner(
        cfg, frozen, params["lora"], adamw(constant_schedule(LR)),
        devices=list(EDGE_FLEET[:n_devices]), server=SERVER_RTX4060TI,
        channels=[WirelessChannel("normal", seed=seed + SEED_STRIDE * m)
                  for m in range(n_devices)],
        datasets=datasets, sim=sim, policy="card", cost_cfg=cfg)
    result = ft.run(rounds)
    losses = result.losses()
    if len(losses) != rounds * n_devices or not np.all(np.isfinite(losses)):
        raise AssertionError(f"protocol losses {losses}")

    # the same compiled step, T local epochs on one fixed batch
    cut = result.logs[0].cut
    batch = datasets[0].minibatch(sim.mini_batch, sim.seq_len)
    opt = adamw(constant_schedule(LR))
    lora, state = ft.lora, opt.init(ft.lora)
    fixed, times = [], []
    for _ in range(sim.local_epochs):
        t0 = time.perf_counter()
        loss, grads = ft.executor.step(frozen, lora, batch, cut)
        jax.block_until_ready((loss, grads))
        times.append(time.perf_counter() - t0)
        fixed.append(float(loss))
        updates, state = opt.update(grads, state, lora)
        lora = apply_updates(lora, updates)
    if not fixed[-1] < fixed[0]:
        raise AssertionError(f"fixed-batch loss did not fall: {fixed}")

    # the split step without the int8 link == the one-program train step
    tokens = jnp.asarray(batch["tokens"])
    labels = jnp.asarray(batch["labels"])
    lora_dev, lora_srv = split_lora(lora, cut)
    split_loss, _, _ = split_grads(frozen, lora_dev, lora_srv, tokens, labels,
                                   cfg=cfg, cut=cut, compress=False)
    full_loss, _, _ = jax.jit(make_train_step(cfg, opt))(
        frozen, lora, state, {"tokens": tokens, "labels": labels})
    gap = abs(float(split_loss) - float(full_loss))
    if not gap <= LOSS_RTOL * abs(float(full_loss)):
        raise AssertionError(f"split loss {float(split_loss)} vs train-step "
                             f"loss {float(full_loss)}")
    step_s = statistics.median(times)
    program = split_grads.lower(
        frozen, *split_lora(lora, cut), tokens, labels, cfg=cfg,
        cut=cut).compile().memory_analysis()
    report("split", clock, mark, step_s, {
        "protocol_losses_finite": len(losses),
        "fixed_batch_losses": fixed,
        "split_vs_train_step_loss_gap": gap,
        "loss_gap_limit": LOSS_RTOL * abs(float(full_loss))},
        cuts=sorted({log.cut for log in result.logs}),
        step_temp_bytes=program.temp_size_in_bytes,
        step_argument_bytes=program.argument_size_in_bytes,
        tokens_per_step=sim.mini_batch * sim.seq_len,
        tokens_per_s=sim.mini_batch * sim.seq_len / step_s)


# ---------------------------------------------------------------------------
# multi-tenant serving
# ---------------------------------------------------------------------------


def random_adapter(key, cfg):
    """LoRA adapters with a nonzero B, so that tenants really differ."""
    lora = model_lib.init_params(key, cfg)["lora"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(lora)
    keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
    out = [0.02 * jax.random.normal(k, v.shape, v.dtype)
           if path[-1].key == "b" else v
           for (path, v), k in zip(leaves, keys, strict=True)]
    return jax.tree_util.tree_unflatten(tree, out)


def reference_logits(frozen, lora, prompt, cfg):
    """Plain full-sequence forward in float32: naive attention, no cache,
    no kernels, "highest" matmul precision. Logits after the last token."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    frozen32 = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32),
                                      frozen)

    @jax.jit
    def fwd(frozen32, lora, tokens):
        with jax.default_matmul_precision("highest"):
            x, _ = model_lib.forward_hidden(frozen32, lora, tokens, cfg32,
                                            impl="naive", remat=False)
            return model_lib.logits_from_hidden(frozen32, x[:, -1:], cfg32)

    return fwd(frozen32, lora, jnp.asarray(prompt, jnp.int32)[None])[0, 0]


def phase_serve(cfg, clock, *, seed, slots=8, max_len=1024,
                prompt_lens=(64, 128, 256, 512, 80, 37), max_new=16):
    mark = clock.mark()
    frozen = init_params(cfg, seed + 1)["frozen"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 2)
    adapters = [random_adapter(k, cfg) for k in keys]
    engine = ServingEngine(cfg, frozen, adapters, slots=slots,
                           max_len=max_len)
    rng = np.random.default_rng(seed)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)

    # warm-up: one request compiles the prefill and the decode tick
    engine.submit(Request(uid=-1, prompt=prompt(engine._chunk), max_new=2))
    engine.run_until_drained()
    requests = [Request(uid=i, prompt=prompt(n), max_new=max_new,
                        adapter_id=i % len(adapters))
                for i, n in enumerate(prompt_lens)]
    ticks0 = engine.ticks
    t0 = time.perf_counter()
    for r in requests:
        engine.submit(r)
    stats = engine.run_until_drained()
    wall = time.perf_counter() - t0
    if not stats["drained"] or not all(r.done and len(r.output) == max_new
                                       for r in requests):
        raise AssertionError(f"serving did not drain: {stats}")

    # first-token logits of the engine's own jitted prefill vs the reference
    req = requests[0]
    chunk = engine._chunk
    if len(req.prompt) % chunk:
        raise ValueError("the checked prompt must be whole prefill chunks")
    cache = engine.cache
    for lo in range(0, len(req.prompt), chunk):
        got, cache = engine._prefill(
            frozen, engine._stacked(), cache,
            jnp.asarray(req.prompt[None, lo:lo + chunk]), jnp.int32(0),
            jnp.int32(lo), jnp.int32(req.adapter_id))
    got = np.asarray(got[0, :cfg.vocab_size], np.float64)
    want = np.asarray(reference_logits(frozen, adapters[req.adapter_id],
                                       req.prompt, cfg)[:cfg.vocab_size],
                      np.float64)
    rel_rms = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    if not rel_rms <= LOGITS_REL_RMS:
        raise AssertionError(f"prefill logits rel RMS error {rel_rms}")
    report("serve", clock, mark, wall, {
        "drained": stats["completed"] - 1,
        "prefill_logits_rel_rms": rel_rms,
        "logits_limit": LOGITS_REL_RMS},
        requests=len(requests), ticks=engine.ticks - ticks0,
        tokens=len(requests) * max_new,
        tokens_per_s=len(requests) * max_new / wall,
        mean_ttft_s=float(np.mean([r.first_token_at - r.submitted_at
                                   for r in requests])))


# ---------------------------------------------------------------------------
# CARD decision grid
# ---------------------------------------------------------------------------


def lane_cost(cfg, sim, device, seed, n, m, cut, f):
    """Eq. 12 cost of decision (cut, f) for device ``m`` in round ``n``,
    with the channel draw the scalar engine saw there."""
    chan = WirelessChannel("normal", seed=seed + SEED_STRIDE * m,
                           bandwidth_hz=sim.bandwidth_hz,
                           tx_power_dbm_up=sim.tx_power_dbm_up,
                           tx_power_dbm_down=sim.tx_power_dbm_down,
                           noise_dbm_per_hz=sim.noise_dbm_per_hz)
    for _ in range(n):
        chan.draw()
    ctx = RoundContext(workload=Workload(cfg, sim.mini_batch, sim.seq_len),
                       device=device, server=SERVER_RTX4060TI,
                       channel=chan.draw(), sim=sim)
    return ctx.cost(int(cut), float(f))


def phase_card(cfg, clock, *, seed, n_check=100, rounds=5, n_big=10**4):
    mark = clock.mark()
    sim = SimParams()
    fleet = make_heterogeneous_fleet(n_check, seed=seed)
    vec = simulate_fleet(cfg, rounds=rounds, devices=fleet, seed=seed)
    sca = simulate_fleet(cfg, rounds=rounds, devices=fleet, seed=seed,
                         engine="scalar")
    differ = (vec.cuts != sca.cuts) | ~np.isclose(vec.freqs, sca.freqs,
                                                  rtol=1e-5, atol=0.0)
    worst = 0.0
    for n, m in zip(*np.nonzero(differ), strict=True):
        c_sca = lane_cost(cfg, sim, fleet[m], seed, n, m, sca.cuts[n, m],
                          sca.freqs[n, m])
        c_vec = lane_cost(cfg, sim, fleet[m], seed, n, m, vec.cuts[n, m],
                          vec.freqs[n, m])
        gap = (c_vec - c_sca) / max(abs(c_sca), 1e-12)
        worst = max(worst, abs(gap))
        print(f"card mismatch round {n} device {m}: scalar (cut "
              f"{sca.cuts[n, m]}, f {sca.freqs[n, m]:.6g}) cost {c_sca:.9g}"
              f", vectorized (cut {vec.cuts[n, m]}, f {vec.freqs[n, m]:.6g})"
              f" cost {c_vec:.9g}, relative gap {gap:.3g}", flush=True)
    if worst > CARD_COST_RTOL:
        raise AssertionError(f"CARD engines disagree beyond a near-tie: "
                             f"relative cost gap {worst}")

    big = make_heterogeneous_fleet(n_big, seed=seed)
    t0 = time.perf_counter()
    simulate_fleet(cfg, rounds=1, devices=big, seed=seed)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    log = simulate_fleet(cfg, rounds=1, devices=big, seed=seed)
    steady = time.perf_counter() - t0
    if not np.all(np.isfinite(log.delays)):
        raise AssertionError("non-finite delays at fleet scale")
    report("card", clock, mark, steady, {
        "lanes_compared": int(differ.size),
        "decision_mismatches": int(differ.sum()),
        "worst_relative_cost_gap": worst},
        devices=n_big, first_round_s=first,
        decisions_per_s=n_big / steady)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _gqa_fold(x, group):
    """(B, S, H, D) -> (B*H*group, S, D): one row per query head."""
    if group > 1:
        x = jnp.repeat(x, group, axis=2)
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _check_kernel(name, got, want, checks):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.abs(got - want).max() / np.abs(want).max())
    checks[name] = err
    if not (got.shape == want.shape and err <= KERNEL_REL_MAX):
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape}, "
                             f"max error {err} of the output range")


def phase_kernels(cfg, ssm_cfg, clock, *, seed, tokens=(4, 512), slots=8,
                  cache_len=1024, ssm_len=512):
    mark = clock.mark()
    ks = iter(jax.random.split(jax.random.PRNGKey(seed + 3), 32))
    bf = jnp.bfloat16
    d, f, r = cfg.d_model, cfg.d_ff, cfg.lora.rank
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    group = hq // hkv
    b, s = tokens

    def normal(shape, dtype=bf, scale=1.0):
        return (scale * jax.random.normal(next(ks), shape)).astype(dtype)

    checks = {}
    times = {}

    def run(name, fn, *args):
        out = jax.block_until_ready(fn(*args))     # compiles
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times[name] = time.perf_counter() - t0
        return out

    x, w = normal((b * s, d)), normal((d, f), scale=d ** -0.5)
    a, bm = normal((d, r), jnp.float32, r ** -0.5), normal((r, f), jnp.float32)
    scale = cfg.lora.scale
    got = run("lora_matmul", lambda *z: ops.lora_matmul(*z, scale), x, w, a, bm)
    with jax.default_matmul_precision("highest"):
        _check_kernel("lora_matmul", got,
                      ref.lora_matmul_ref(x, w, a, bm, scale), checks)

    xg = normal((slots, 1, d))
    ag = normal((2, d, r), jnp.float32, r ** -0.5)
    bg = normal((2, r, f), jnp.float32)
    ids = jnp.arange(slots, dtype=jnp.int32) % 2
    got = run("lora_matmul_grouped",
              lambda *z: ops.lora_matmul_grouped(*z, scale), xg, w, ag, bg,
              ids)
    with jax.default_matmul_precision("highest"):
        _check_kernel("lora_matmul_grouped", got,
                      ref.lora_matmul_grouped_ref(xg, w, ag, bg, ids, scale),
                      checks)

    q, k, v = normal((b, s, hq, hd)), normal((b, s, hkv, hd)), \
        normal((b, s, hkv, hd))
    for window in (0, s // 2):
        name = f"flash_attention_window{window}"
        got = run(name, lambda q_, k_, v_, w_=window: ops.flash_attention(
            q_, k_, v_, causal=True, window=w_), q, k, v)
        want = ref.flash_attention_ref(
            _gqa_fold(q, 1), _gqa_fold(k, group), _gqa_fold(v, group),
            causal=True, window=window)
        _check_kernel(name, _gqa_fold(got, 1), want, checks)

    qd = normal((slots, 1, hq, hd))
    kc, vc = normal((slots, cache_len, hkv, hd)), \
        normal((slots, cache_len, hkv, hd))
    t = cache_len * 3 // 4
    got = run("flash_decode", lambda *z: ops.flash_decode(*z),
              qd, kc, vc, jnp.int32(t))
    want = ref.flash_attention_ref(
        _gqa_fold(qd, 1), _gqa_fold(kc[:, :t + 1], group),
        _gqa_fold(vc[:, :t + 1], group), causal=False)
    _check_kernel("flash_decode", _gqa_fold(got, 1), want, checks)

    nh = ssm_cfg.ssm_d_inner // ssm_cfg.ssm_head_dim
    ns, chunk = ssm_cfg.ssm_state, ssm_cfg.ssm_chunk
    xt = normal((2, ssm_len, nh, ssm_cfg.ssm_head_dim), jnp.float32, 0.2)
    la = -jnp.abs(normal((2, ssm_len, nh), jnp.float32, 0.1))
    B, C = normal((2, ssm_len, ns), jnp.float32, 0.3), \
        normal((2, ssm_len, ns), jnp.float32, 0.3)
    got, _ = run("ssd_scan", lambda *z: ops.ssd_scan(*z, chunk),
                 xt, la, B, C)
    with jax.default_matmul_precision("highest"):
        _check_kernel("ssd_scan", got, ref.ssd_full_ref(xt, la, B, C, chunk),
                      checks)
    report("kernels", clock, mark, sum(times.values()), checks,
           kernel_s=times, limit=KERNEL_REL_MAX)


# ---------------------------------------------------------------------------
# four chips: the sharded fleet sweep
# ---------------------------------------------------------------------------


def phase_sharded_sweep(cfg, clock, *, seed, n_chips, n_devices=10**4,
                        rounds=2):
    mark = clock.mark()
    fleet = make_heterogeneous_fleet(n_devices, seed=seed)
    mesh = make_fleet_mesh(n_chips)
    runs = {}
    for name, kw in (("one_device", {}), ("sharded", {"mesh": mesh})):
        simulate_fleet(cfg, rounds=rounds, devices=fleet, seed=seed, **kw)
        t0 = time.perf_counter()
        log = simulate_fleet(cfg, rounds=rounds, devices=fleet, seed=seed,
                             **kw)
        runs[name] = (log, time.perf_counter() - t0)
    (one, t_one), (shard, t_shard) = runs["one_device"], runs["sharded"]
    drift = [f for f in FLEET_FIELDS
             if not np.array_equal(getattr(one, f), getattr(shard, f))]
    if drift:
        raise AssertionError(f"sharded sweep drifted in {drift}")
    report("sharded_sweep", clock, mark, t_shard,
           {"bit_identical_fields": list(FLEET_FIELDS)},
           devices=n_devices, rounds=rounds, shards=n_chips,
           one_device_s=t_one,
           decisions_per_s=n_devices * rounds / t_shard)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded fleet sweep")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache(os.path.join(ROOT, ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} chips; JAX found "
                 f"{len(devices)}")
    peaks = chip_peaks(dev.device_kind)
    print(json.dumps({"device_kind": dev.device_kind, "count": len(devices),
                      "hbm_bytes": peaks.hbm_bytes,
                      "bf16_flops_per_s": peaks.bf16_flops_per_s}),
          flush=True)

    clock = CompileClock()
    cfg = get_config("qwen3-0.6b")
    if args.chips == 4:
        phase_sharded_sweep(cfg, clock, seed=args.seed, n_chips=4)
    else:
        phase_split(cfg, clock, seed=args.seed)
        phase_serve(cfg, clock, seed=args.seed)
        phase_card(cfg, clock, seed=args.seed)
        phase_kernels(cfg, get_config("mamba2-370m"), clock, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
