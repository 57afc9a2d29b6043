"""Mamba-2 SSD intra-chunk Pallas TPU kernel.

The SSD algorithm splits into (i) an intra-chunk quadratic part + per-chunk
final state, both embarrassingly parallel over (batch, head, chunk), and
(ii) a tiny inter-chunk linear recurrence. This kernel implements (i) with
VMEM tiling — the (chunk x chunk) decay/score matrices never leave VMEM.
The O(n_chunks) recurrence (ii) and the cross-chunk output correction stay
in jnp (they are bandwidth-trivial); see ops.ssd_scan.

Grid: (batch, n_heads, n_chunks). Per cell:
  y_diag[i] = sum_{j<=i} (C_i . B_j) * exp(cum_i - cum_j) * xt_j
  state     = sum_j exp(cum_last - cum_j) * B_j (x) xt_j
  (also emits exp(cum) and exp(cum_last - cum) decay vectors for the jnp
  cross-chunk correction)

VMEM at defaults (chunk=256, hp=64, ns=128, f32): xt 64 KiB, B/C 128 KiB,
decay/score matrices 256 KiB each — well under budget, MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, dec_ref, *,
            chunk: int):
    xt = xt_ref[0, 0].astype(jnp.float32)          # (cl, hp)
    a = a_ref[0, 0].astype(jnp.float32)            # (cl, 1)
    B = b_ref[0, 0].astype(jnp.float32)            # (cl, ns)
    C = c_ref[0, 0].astype(jnp.float32)            # (cl, ns)

    # Inclusive prefix sums of the log-decays as masked reductions over the
    # (cl, cl) tile: Mosaic has no cumsum lowering, but lowers broadcasts,
    # selects and row/column sums. cum_row[0, j] = sum_{k<=j} a_k; the
    # column form is read off its diagonal, which avoids a transpose.
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cum_row = jnp.sum(jnp.where(ii <= jj, a, 0.0), axis=0,
                      keepdims=True)               # (1, cl)
    cum = jnp.sum(jnp.where(ii == jj, cum_row, 0.0), axis=1,
                  keepdims=True)                   # (cl, 1)
    cum_last = jnp.sum(a, axis=0, keepdims=True)   # (1, 1)
    decay = jnp.where(ii >= jj, jnp.exp(cum - cum_row), 0.0)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general((scores * decay).astype(xt.dtype), xt,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # chunk-final state: sum_j exp(cum_last - cum_j) B_j (x) xt_j
    bw = B * jnp.exp(cum_last - cum)               # (cl, ns)
    st = jax.lax.dot_general(bw, xt, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    st_ref[0, 0] = st.astype(st_ref.dtype)         # (ns, hp)

    # decay vectors for the jnp cross-chunk correction:
    #   dec[:, 0] = exp(cum)  (applied to h_prev),  dec[:, 1] = total decay
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, 2), 1)
    dec_ref[0, 0] = jnp.where(lane == 0, jnp.exp(cum),
                              jnp.exp(cum_last)).astype(dec_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(xt: jax.Array, a: jax.Array, B: jax.Array, C: jax.Array,
                    *, interpret: bool = False):
    """xt: (b, nc, cl, nh, hp) pre-multiplied by dt; a: (b, nc, cl, nh);
    B, C: (b, nc, cl, ns). Returns:
      y_diag: (b, nc, cl, nh, hp), states: (b, nc, nh, ns, hp),
      dec:    (b, nc, cl, nh, 2)  [exp(cum), total-decay]
    """
    b, nc, cl, nh, hp = xt.shape
    ns = B.shape[-1]
    # layout: head-major for the grid
    xt_h = xt.transpose(0, 3, 1, 2, 4).reshape(b * nh, nc, cl, hp)
    a_h = a.transpose(0, 3, 1, 2).reshape(b * nh, nc, cl, 1)
    B_r = jnp.broadcast_to(B[:, None], (b, nh, nc, cl, ns)).reshape(
        b * nh, nc, cl, ns)
    C_r = jnp.broadcast_to(C[:, None], (b, nh, nc, cl, ns)).reshape(
        b * nh, nc, cl, ns)

    y, st, dec = pl.pallas_call(
        functools.partial(_kernel, chunk=cl),
        grid=(b * nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, cl, hp), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, cl, 1), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, cl, ns), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, cl, ns), lambda g, c: (g, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cl, hp), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, ns, hp), lambda g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, 1, cl, 2), lambda g, c: (g, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nh, nc, cl, hp), jnp.float32),
            jax.ShapeDtypeStruct((b * nh, nc, ns, hp), jnp.float32),
            jax.ShapeDtypeStruct((b * nh, nc, cl, 2), jnp.float32),
        ],
        interpret=interpret,
    )(xt_h, a_h, B_r, C_r)

    y = y.reshape(b, nh, nc, cl, hp).transpose(0, 2, 3, 1, 4)
    st = st.reshape(b, nh, nc, ns, hp).transpose(0, 2, 1, 3, 4)
    dec = dec.reshape(b, nh, nc, cl, 2).transpose(0, 2, 3, 1, 4)
    return y, st, dec
