"""Flash attention Pallas TPU kernels, forward and backward (causal or
sliding-window, GQA through the index maps).

Layout: q (B*Hq, S, D); k, v (B*Hkv, S, D), the query heads of one group
adjacent, so query row ``h`` reads KV row ``h // group`` through its
``BlockSpec``: no broadcast copy of K/V is made, and dK/dV sum over the
group inside their kernel.

``flash_attention`` is a ``jax.custom_vjp``. The forward kernel
(``flash_fwd``) runs the online softmax over KV blocks and also writes each
row's logsumexp (f32). The backward pass has one kernel for dQ
(``flash_bwd_dq``: per Q block, a sweep over KV blocks) and one for dK/dV
(``flash_bwd_dkv``: per KV block, a sweep over the group's query heads and
their Q blocks). Both recompute P block by block from q, k and the
logsumexp, so no S x S array reaches HBM.

Two levels of blocks, both chosen from S and D. The grid walks major
blocks of up to 512 rows, so a step is a few large DMAs and the grid has
few steps. Inside a step a static loop walks minor 128-row blocks: a minor
pair runs only where the causal or window mask leaves some of it visible,
and unmasked where all of it is. A skipped grid step's index map repeats
the block the last step read, so it starts no DMA either.

Scores, softmax statistics and accumulators are f32; matmul operands keep
the input dtype.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
MINOR = 128          # minor block rows: the MXU's width
MAJOR = 512          # major block rows for heads up to 128 wide
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


@dataclasses.dataclass(frozen=True)
class _Geom:
    """Static shape of one call: masks, scale and blocks."""
    causal: bool
    window: int
    scale: float
    bq: int                # major Q block rows
    bk: int                # major KV block rows
    sub_q: int             # minor Q block rows
    sub_k: int             # minor KV block rows
    nq: int
    nk: int
    skv: int               # keys at or past this are padding
    group: int             # query heads per KV head
    interpret: bool


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _major(s: int, d: int) -> int:
    """Rows of a major block for a sequence of ``s`` and heads of ``d``: a
    short sequence whole (16-row aligned), else up to MAJOR rows in
    multiples of MINOR, half that for heads wider than 128 (VMEM)."""
    if s <= MINOR:
        return _round_up(s, 16)
    return min(MAJOR if d <= 128 else MAJOR // 2, _round_up(s, MINOR))


def _minor(major: int) -> int:
    return MINOR if major % MINOR == 0 else major


def _and(a, b):
    """``a and b``, static where both are Python bools (``pl.when`` then
    emits the branch or nothing), else traced."""
    if isinstance(a, bool) and isinstance(b, bool):
        return a and b
    return jnp.logical_and(a, b)


def _block(axis: int, n: int):
    """The grid index along ``axis`` of ``n`` blocks: 0, static, where
    there is one block."""
    return 0 if n == 1 else pl.program_id(axis)


def _visible(g: _Geom, q0, k0):
    """For the minor pair at query row ``q0`` and key ``k0``: whether some
    entry is visible (the pair runs) and whether all are (it runs
    unmasked); Python bools where ``q0`` and ``k0`` are ints."""
    q1, k1 = q0 + g.sub_q - 1, k0 + g.sub_k - 1
    run, full = k0 < g.skv, k1 < g.skv
    if g.causal:
        run, full = _and(run, k0 <= q1), _and(full, k1 <= q0)
    if g.window:
        run = _and(run, k1 > q0 - g.window)
        full = _and(full, k0 > q1 - g.window)
    return run, full


def _mask(g: _Geom, q0, k0, transposed: bool = False) -> jax.Array:
    """Visibility of the minor pair at (``q0``, ``k0``), (sub_q, sub_k), or
    (sub_k, sub_q) ``transposed``."""
    shape = (g.sub_k, g.sub_q) if transposed else (g.sub_q, g.sub_k)
    qa, ka = (1, 0) if transposed else (0, 1)
    rows = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, qa)
    cols = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
    mask = cols < g.skv
    if g.causal:
        mask &= cols <= rows
    if g.window:
        mask &= cols > rows - g.window
    return mask


def _each_pair(g: _Geom, q_base, k_base, body) -> None:
    """``body(rows, cols, q0, k0, masked)`` for each minor pair of the major
    pair at (``q_base``, ``k_base``) that has a visible entry; ``rows`` and
    ``cols`` slice the major blocks."""
    for a in range(g.bq // g.sub_q):
        for b in range(g.bk // g.sub_k):
            q0, k0 = q_base + a * g.sub_q, k_base + b * g.sub_k
            rows = slice(a * g.sub_q, (a + 1) * g.sub_q)
            cols = slice(b * g.sub_k, (b + 1) * g.sub_k)
            run, full = _visible(g, q0, k0)
            part = (not full if isinstance(full, bool)
                    else jnp.logical_not(full))
            pl.when(_and(run, full))(
                functools.partial(body, rows, cols, q0, k0, False))
            pl.when(_and(run, part))(
                functools.partial(body, rows, cols, q0, k0, True))


def _kv_block(g: _Geom, i, j):
    """The KV block that grid step (Q block ``i``, KV block ``j``) reads:
    ``j`` held inside the blocks Q block ``i`` sees."""
    if g.causal:
        j = jnp.minimum(j, ((i + 1) * g.bq - 1) // g.bk)
    if g.window:
        j = jnp.maximum(j, jnp.maximum(i * g.bq - g.window + 1, 0) // g.bk)
    return jnp.minimum(j, (g.skv - 1) // g.bk)


def _q_block(g: _Geom, j, i):
    """The Q block that grid step (KV block ``j``, Q block ``i``) reads:
    ``i`` held inside the blocks that see KV block ``j``."""
    if g.causal:
        i = jnp.maximum(i, (j * g.bk) // g.bq)
    if g.window:
        i = jnp.minimum(i, ((j + 1) * g.bk + g.window - 2) // g.bq)
    return jnp.minimum(i, g.nq - 1)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, g: _Geom):
    i, j = _block(1, g.nq), _block(2, g.nk)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(rows, cols, q0, k0, masked):
        v = v_ref[0, cols, :]
        s = jax.lax.dot_general(q_ref[0, rows, :], k_ref[0, cols, :], _NT,
                                preferred_element_type=jnp.float32) * g.scale
        if masked:
            s = jnp.where(_mask(g, q0, k0), s, NEG_INF)
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows, :] = alpha * l_ref[rows, :] + jnp.sum(p, axis=1,
                                                          keepdims=True)
        m_ref[rows, :] = m_new
        acc_ref[rows, :] = alpha * acc_ref[rows, :] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _each_pair(g, i * g.bq, j * g.bk, body)

    @pl.when(j == g.nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l)).reshape(1, g.bq)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, g: _Geom):
    i, j = _block(1, g.nq), _block(2, g.nk)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(rows, cols, q0, k0, masked):
        k = k_ref[0, cols, :]
        s = jax.lax.dot_general(q_ref[0, rows, :], k, _NT,
                                preferred_element_type=jnp.float32) * g.scale
        if masked:
            s = jnp.where(_mask(g, q0, k0), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, rows].reshape(g.sub_q, 1))
        dp = jax.lax.dot_general(do_ref[0, rows, :], v_ref[0, cols, :], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, :, rows].reshape(g.sub_q, 1))
        acc_ref[rows, :] += jnp.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    _each_pair(g, i * g.bq, j * g.bk, body)

    @pl.when(j == g.nk - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * g.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, g: _Geom):
    """Scores transposed, (keys, queries), so the logsumexp and di of the
    queries broadcast along rows and dK, dV are plain matmuls."""
    j, h, i = _block(1, g.nk), _block(2, g.group), _block(3, g.nq)

    @pl.when(_and(h == 0, i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(rows, cols, q0, k0, masked):
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        s = jax.lax.dot_general(k_ref[0, cols, :], q, _NT,
                                preferred_element_type=jnp.float32) * g.scale
        if masked:
            s = jnp.where(_mask(g, q0, k0, transposed=True), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, rows])
        dv_acc[cols, :] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0, cols, :], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, :, rows])
        dk_acc[cols, :] += jnp.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _each_pair(g, i * g.bq, j * g.bk, body)

    @pl.when(_and(h == g.group - 1, i == g.nq - 1))
    def _finish():
        dk_ref[0] = (dk_acc[...] * g.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Calls
# ---------------------------------------------------------------------------


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _by_q_block(g: _Geom, d: int):
    """Block specs of a grid (query row, Q block, KV block): a Q block, its
    group's KV block, and a Q block's row of per-row statistics."""
    return (pl.BlockSpec((1, g.bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, g.bk, d),
                         lambda h, i, j: (h // g.group, _kv_block(g, i, j),
                                          0)),
            pl.BlockSpec((1, 1, g.bq), lambda h, i, j: (h, 0, i)))


def _forward(g: _Geom, q, k, v):
    bhq, sq, d = q.shape
    q_spec, kv_spec, row_spec = _by_q_block(g, d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, g=g),
        grid=(bhq, g.nq, g.nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bhq, 1, sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g.bq, d), jnp.float32),
                        pltpu.VMEM((g.bq, 1), jnp.float32),
                        pltpu.VMEM((g.bq, 1), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=g.interpret,
        name="flash_fwd",
    )(q, k, v)


def _backward(g: _Geom, q, k, v, o, lse, do):
    bhq, sq, d = q.shape
    bhkv = k.shape[0]
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, None, :]

    q_spec, kv_spec, row_spec = _by_q_block(g, d)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, g=g),
        grid=(bhq, g.nq, g.nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((g.bq, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=g.interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, di)

    # grid (KV row, KV block, query head of the group, Q block)
    def q_map(hk, j, h, i):
        return (hk * g.group + h, _q_block(g, j, i), 0)

    def row_map(hk, j, h, i):
        return (hk * g.group + h, 0, _q_block(g, j, i))

    q_spec = pl.BlockSpec((1, g.bq, d), q_map)
    kv_spec = pl.BlockSpec((1, g.bk, d), lambda hk, j, h, i: (hk, j, 0))
    row_spec = pl.BlockSpec((1, 1, g.bq), row_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, g=g),
        grid=(bhkv, g.nk, g.group, g.nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((g.bk, d), jnp.float32),
                        pltpu.VMEM((g.bk, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=g.interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attention(g: _Geom, q, k, v):
    return _forward(g, q, k, v)[0]


def _attention_fwd(g: _Geom, q, k, v):
    o, lse = _forward(g, q, k, v)
    return o, (q, k, v, o, lse)


def _attention_bwd(g: _Geom, res, do):
    return _backward(g, *res, do)


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "sm_scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B*Hq, Sq, D); k, v: (B*Hkv, Skv, D), query heads of one group
    adjacent (``ops.flash_attention`` folds them so). Positions are 0..S-1
    (train and prefill). ``sm_scale``: the softmax scale, 1/sqrt(D) if
    None. Differentiable in q, k and v. The major blocks come from S and D;
    ``block_q``/``block_k`` set them instead, for tests of many blocks at
    small S.
    """
    bhq, sq, d = q.shape
    bhkv, skv = k.shape[0], k.shape[1]
    bq = block_q or _major(sq, d)
    bk = block_k or _major(skv, d)
    pq, pk = (-sq) % bq, (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    g = _Geom(causal=causal, window=window,
              scale=float(d ** -0.5 if sm_scale is None else sm_scale),
              bq=bq, bk=bk, sub_q=_minor(bq), sub_k=_minor(bk),
              nq=q.shape[1] // bq, nk=k.shape[1] // bk, skv=skv,
              group=bhq // bhkv, interpret=interpret)
    return _attention(g, q, k, v)[:, :sq]
