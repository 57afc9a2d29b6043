"""Mamba2 SSD properties: chunk-size invariance, decode==scan, decay limits."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models.mamba import ssd_chunked


def _rand(seed, *shape, scale=0.3):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


@settings(max_examples=10, deadline=None)
@given(l=st.integers(8, 80), c1=st.sampled_from([8, 16, 32]),
       c2=st.sampled_from([8, 16, 32]))
def test_chunk_size_invariance(l, c1, c2):
    """SSD output must not depend on the chunking."""
    b, nh, hp, ns = 1, 2, 8, 12
    xt = _rand(0, b, l, nh, hp)
    a = -jnp.abs(_rand(1, b, l, nh, scale=0.1))
    B = _rand(2, b, l, ns)
    C = _rand(3, b, l, ns)
    y1, h1 = ssd_chunked(xt, a, B, C, c1)
    y2, h2 = ssd_chunked(xt, a, B, C, c2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-5)


def test_ssd_equals_naive_recurrence():
    """Chunked scan == the literal state-space recurrence."""
    b, l, nh, hp, ns, chunk = 1, 40, 2, 4, 6, 16
    xt = _rand(4, b, l, nh, hp)
    a = -jnp.abs(_rand(5, b, l, nh, scale=0.2))
    B = _rand(6, b, l, ns)
    C = _rand(7, b, l, ns)
    y, h_final = ssd_chunked(xt, a, B, C, chunk)

    h = np.zeros((b, nh, hp, ns))
    ys = []
    xt_n, a_n = np.asarray(xt), np.asarray(a)
    B_n, C_n = np.asarray(B), np.asarray(C)
    for t in range(l):
        h = h * np.exp(a_n[:, t])[:, :, None, None] \
            + xt_n[:, t][:, :, :, None] * B_n[:, t][:, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", h, C_n[:, t]))
    naive = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), naive, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_final), h, atol=2e-5)


def test_ssd_from_a_state_equals_recurrence():
    """From an initial state h0, at a length no multiple of the chunk: the
    outputs and the final state are the literal recurrence's."""
    b, l, nh, hp, ns, chunk = 2, 37, 3, 4, 5, 8
    xt = _rand(10, b, l, nh, hp)
    a = -jnp.abs(_rand(11, b, l, nh, scale=0.3))
    B = _rand(12, b, l, ns)
    C = _rand(13, b, l, ns)
    h0 = _rand(14, b, nh, hp, ns)
    y, h_final = ssd_chunked(xt, a, B, C, chunk, h0=h0)

    h = np.asarray(h0, np.float64)
    ys = []
    for t in range(l):
        h = h * np.exp(np.asarray(a)[:, t])[:, :, None, None] \
            + np.asarray(xt)[:, t][:, :, :, None] \
            * np.asarray(B)[:, t][:, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", h, np.asarray(C)[:, t]))
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, axis=1),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_final), h, atol=2e-5)


def test_zero_decay_is_cumulative_sum():
    """a == 0 (no decay): the state is a running sum of B-weighted inputs."""
    b, l, nh, hp, ns = 1, 24, 1, 2, 3
    xt = _rand(8, b, l, nh, hp)
    a = jnp.zeros((b, l, nh))
    B = jnp.ones((b, l, ns))
    C = jnp.ones((b, l, ns))
    y, h = ssd_chunked(xt, a, B, C, 8)
    # y_t = C . sum_{j<=t} B x_j = ns * cumsum(x)_t
    expect = ns * np.cumsum(np.asarray(xt), axis=1)
    np.testing.assert_allclose(np.asarray(y), expect, atol=1e-5)


def test_strong_decay_forgets():
    """Very negative a: y_t ~ contribution of x_t only."""
    b, l, nh, hp, ns = 1, 16, 1, 2, 3
    xt = _rand(9, b, l, nh, hp)
    a = jnp.full((b, l, nh), -50.0)
    B = jnp.ones((b, l, ns))
    C = jnp.ones((b, l, ns))
    y, _ = ssd_chunked(xt, a, B, C, 8)
    expect = ns * np.asarray(xt)
    np.testing.assert_allclose(np.asarray(y), expect, atol=1e-4)
