"""Split LoRA fine-tuning under the paper's protocol: the general runner of
every mix whose ``runner`` is ``split``.

Set-up makes the weights on the device from the seed, builds one
``SplitFineTuner`` over the mix's Table I devices, computes ahead from the
seeded channels every cut the policy will pick in the rounds the window
can hold, and compiles each. It then drives that same tuner through its
first round with the window's own call, ``run(1)``, recording the first
steps: each step's loss and batch, the optimizer state after step one and
the adapters that the fourth step receives. The window repeats ``run(1)``.

``correct`` compares those steps with the plain reference, each number by
its worst leaf: the norm of the first gradient as the optimizer got it
(AdamW's first moment after one step, over 1 - beta1), that gradient's
difference from the reference's, and the norm of the adapters' change over
the steps. Each step's loss gap is reported beside them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness


class TokenStream:
    """A device's local data: uniform tokens of the model's vocabulary,
    drawn from the seed; ``labels`` are the next tokens."""

    def __init__(self, vocab: int, seed: int, device: int):
        self.vocab = vocab
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed) % 2**63, 100 + device]))

    def minibatch(self, batch: int, seq_len: int) -> Dict[str, np.ndarray]:
        toks = self.rng.integers(0, self.vocab, size=(batch, seq_len + 1),
                                 dtype=np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


class StepRecorder:
    """Stands in for the tuner's executor during the recorded round, and
    passes every call through to it."""

    def __init__(self, inner, tuner, n_steps: int):
        self.inner = inner
        self.tuner = tuner
        self.n = n_steps
        self.losses: List[float] = []
        self.batches: List[Dict[str, np.ndarray]] = []
        self.cuts: List[int] = []
        self.first_moment = None
        self.lora_after = None

    def step(self, frozen, lora, batch, cut):
        k = len(self.batches)
        if k == 1:
            self.first_moment = self.tuner.opt_state["m"]
        if k == self.n:
            self.lora_after = lora
        out = self.inner.step(frozen, lora, batch, cut)
        if k < self.n:
            self.losses.append(float(out[0]))
            self.cuts.append(int(cut))
        self.batches.append({key: np.asarray(v) for key, v in batch.items()})
        return out


def _stage_leaves(tree, cut: int) -> Dict[str, Any]:
    """The adapter leaves as the two stages hold them: layers [0, cut) on
    the device and [cut, n) on the server."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if cut > 0:
            out["device" + name] = v[:cut]
        if cut < v.shape[0]:
            out["server" + name] = v[cut:]
    return out


def _norms(leaves: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(jnp.linalg.norm(jnp.asarray(v, jnp.float32).ravel()))
            for k, v in leaves.items()}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   keep=None) -> float:
    """Largest | |got| - |want| | of leaf norms, against the reference norm of
    that leaf or of the median leaf, whichever is larger."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keys)


@dataclasses.dataclass
class Readings:
    """What the first steps produced: per-step losses, the first gradient
    (its leaves, and their norms) and the change of the adapters, as leaf
    norms."""
    losses: List[float]
    grads: Dict[str, Any]
    change_norms: Dict[str, float]

    @property
    def grad_norms(self) -> Dict[str, float]:
        return _norms(self.grads)


def worst_leaf_difference(got: Dict[str, Any], want: Dict[str, Any]
                          ) -> float:
    """Largest |got - want| of leaf norms, against the reference norm of that
    leaf or of the median leaf, whichever is larger."""
    norms = _norms(want)
    med = float(np.median(list(norms.values())))
    diff = _norms({k: jnp.asarray(got[k], jnp.float32)
                   - jnp.asarray(want[k], jnp.float32) for k in want})
    return max(diff[k] / max(norms[k], med) for k in want)


def reference_readings(ref, c: Dict, frozen, lora0, batches, cut: int,
                       steps: int, prec: str) -> Readings:
    """The reference's own first ``steps`` steps from the same start."""
    from chipbench.reference import common
    opt = c["optimizer"]

    def loss_fn(lora, frozen, tokens, labels):
        return ref.split_loss(c, frozen, lora, tokens, labels, cut, prec)

    step = jax.jit(jax.value_and_grad(loss_fn))
    lora, state = lora0, common.adamw_init(lora0)
    losses, first = [], None
    for b in batches[:steps]:
        loss, grads = step(lora, frozen, jnp.asarray(b["tokens"]),
                           jnp.asarray(b["labels"]))
        losses.append(float(loss))
        if first is None:
            first = grads
        lora, state = common.adamw_step(
            lora, grads, state, lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"], weight_decay=opt["weight_decay"])
    change = jax.tree_util.tree_map(lambda a, b: a - b, lora, lora0)
    return Readings(losses, _stage_leaves(first, cut),
                    _norms(_stage_leaves(change, cut)))


def reported(got: Readings, want: Readings) -> Dict[str, float]:
    """Reported beside the checks and not compared: the worst relative gap
    of a step's loss, which no control or fault reads far enough above
    sound runs for a limit to hold (PERF.md)."""
    return {"loss_gap": max(harness.relative_gap(a, b, 1e-30) for a, b
                            in zip(got.losses, want.losses, strict=True))}


def compare(got: Readings, want: Readings, limits: Dict[str, float],
            comparison: harness.Comparison) -> None:
    comparison.add("grad_norm_gap",
                   worst_leaf_gap(got.grad_norms, want.grad_norms),
                   limits["grad_norm_gap"])
    comparison.add("grad_difference",
                   worst_leaf_difference(got.grads, want.grads),
                   limits["grad_difference"])
    med = float(np.median(list(want.grad_norms.values())))
    moved = {k for k, v in want.grad_norms.items() if v >= 1e-3 * med}
    comparison.add("update_norm_gap",
                   worst_leaf_gap(got.change_norms, want.change_norms, moved),
                   limits["update_norm_gap"])


def run(cell: harness.Cell, args, devices, clock: harness.CompileClock,
        tracer: harness.Tracer, program_cfg):
    from repro.core import card as card_lib
    from repro.core.channel import SEED_STRIDE, WirelessChannel
    from repro.core.cost_model import RoundContext, Workload
    from repro.core.hardware import EDGE_FLEET, SERVER_RTX4060TI, SimParams
    from repro.core.protocol import SplitFineTuner
    from repro.optim import adamw, constant_schedule

    c, t = cell.config, cell.traffic
    ref = cell.reference
    opt = c["optimizer"]
    n_dev = int(t["devices"])
    sim = SimParams(mini_batch=int(t["mini_batch"]), seq_len=int(t["seq_len"]),
                    local_epochs=int(t["local_epochs"]))
    seed = int(args.seed)

    make = jax.jit(lambda k1, k2: {"frozen": ref.make_frozen(c, k1),
                                   "lora": ref.make_lora(c, k2)})
    from chipbench.reference.common import key_from_seed
    params = make(key_from_seed(seed, 1), key_from_seed(seed, 2))
    frozen, lora0 = params["frozen"], params["lora"]
    chan_seed = harness.seed_words(seed, 3)
    channels = lambda: [WirelessChannel(t["channel"],
                                        seed=chan_seed + SEED_STRIDE * m)
                        for m in range(n_dev)]
    tuner = SplitFineTuner(
        program_cfg, frozen, lora0,
        adamw(constant_schedule(opt["lr"]), b1=opt["b1"], b2=opt["b2"],
              eps=opt["eps"], weight_decay=opt["weight_decay"]),
        devices=list(EDGE_FLEET[:n_dev]), server=SERVER_RTX4060TI,
        channels=channels(),
        datasets=[TokenStream(c["vocab_size"], seed, m)
                  for m in range(n_dev)],
        sim=sim, policy="card", cost_cfg=program_cfg,
        compress=bool(t["int8_link"]))

    # every cut the policy will pick in the rounds the window can hold
    ahead = channels()
    workload = Workload(program_cfg, sim.mini_batch, sim.seq_len)
    planned: List[int] = []
    for _ in range(int(t["planned_rounds"])):
        for m in range(n_dev):
            ctx = RoundContext(workload=workload, device=EDGE_FLEET[m],
                               server=SERVER_RTX4060TI,
                               channel=ahead[m].draw(), sim=sim)
            planned.append(card_lib.card(ctx).cut)
    dummy = TokenStream(c["vocab_size"], seed, -1).minibatch(
        sim.mini_batch, sim.seq_len)
    for cut in sorted(set(planned)):
        jax.block_until_ready(tuner.executor.step(frozen, lora0, dummy, cut))

    steps = int(t["checked_steps"])
    recorder = StepRecorder(tuner.executor, tuner, steps)
    tuner.executor = recorder
    with harness.span("bench.split_run"):
        tuner.run(1)
    tuner.executor = recorder.inner
    jax.block_until_ready(tuner.lora)
    setup_s = harness.elapsed_since_start()

    tokens_per_round = n_dev * sim.local_epochs * sim.mini_batch * sim.seq_len
    compiles0 = clock.compiles
    rounds = attempted = failed = 0
    traced_cuts: List[int] = []
    t0 = harness.now()
    while harness.now() - t0 < args.seconds:
        tracer.poll(harness.now() - t0)
        with harness.span("bench.split_run"):
            result = tuner.run(1)
        rounds += 1
        for log in result.logs:
            attempted += 1
            failed += log.status != "ok"
            if tracer.active:
                traced_cuts.append(log.cut)
    wall = harness.now() - t0
    tracer.stop()
    window_compiles = clock.compiles - compiles0
    memory = harness.memory_peak_bytes(devices)

    program = Readings(
        recorder.losses,
        _stage_leaves(jax.tree_util.tree_map(
            lambda m: m / (1.0 - opt["b1"]), recorder.first_moment),
            recorder.cuts[0]),
        _norms(_stage_leaves(jax.tree_util.tree_map(
            lambda a, b: a - b, recorder.lora_after, lora0),
            recorder.cuts[0])))
    del tuner
    recorder.first_moment = recorder.lora_after = None
    if len(set(recorder.cuts)) != 1:
        raise RuntimeError(f"the checked steps crossed cuts {recorder.cuts}")
    cut = recorder.cuts[0]
    want = reference_readings(ref, c, frozen, lora0, recorder.batches, cut,
                              steps, "f32")
    if args.control:
        program = reference_readings(ref, c, frozen, lora0, recorder.batches,
                                     cut, steps, "fp8")
    comparison = harness.Comparison()
    comparison.add("window_compiles", window_compiles, 0)
    compare(program, want, cell.limits, comparison)

    flops = {k: ref.train_flops(c, sim.mini_batch, sim.seq_len, k)
             for k in set(planned)}
    ctx = {"traced_required_flops": sum(
               sim.local_epochs * flops[k] for k in traced_cuts)}
    metrics = {
        "split_tokens_per_s": rounds * tokens_per_round / wall,
        "setup_s": setup_s,
    }
    info = {"rounds": rounds, "cuts": sorted(set(planned)),
            "checked_losses": recorder.losses,
            **reported(program, want)}
    return {"attempted": attempted, "failed": failed, "metrics": metrics,
            "memory": memory, "ctx": ctx, "info": info}, comparison
