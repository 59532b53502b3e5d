"""The Kimi-Delta-Attention mixer (nn/kda.py) at tiny widths on the CPU:
the chunked (WY) form against the one-token recurrence and against the
plain reference's scan over time (benchmarks/lib/
reference_ling_hybrid.py; nothing imported from ``quintnet_tpu``).

Tolerances. Everything here is f32 on the CPU, where a matmul is exact
f32: the forms differ in the ORDER of their sums and in one triangular
solve a chunk, a few ulp of values of size about 1. ``ATOL`` 5e-5 is
ten times the largest difference seen (4.6e-6 on a state of size 2) and
far under what any missing piece does (a decay or a write strength
forced to 1 moves the output by 0.1 and more).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.nn.kda import (KDADims, delta_chunked, delta_step,
                                 kda_chunk, kda_init, kda_step)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 5e-5
DIMS = KDADims(n_heads=2, d_k=16, d_v=16, d_conv=4,
               lower_bound=-5.0, eps=1e-6)
DIM = 32


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_ling_hybrid", os.path.join(
            ROOT, "benchmarks", "lib", "reference_ling_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()
REF_CFG = (("num_attention_heads", DIMS.n_heads), ("head_dim", DIMS.d_k),
           ("short_conv_kernel_size", DIMS.d_conv),
           ("rms_norm_eps", DIMS.eps), ("kda_lower_bound", DIMS.lower_bound))


def _unit(a):
    return a / jnp.linalg.norm(a, axis=-1, keepdims=True)


def _case(seed, r=2, t=128, *, decay=None):
    """Random heads' inputs of the delta rule: unit q and k, decays that
    span slow and fast channels (or ``decay`` on every channel), a
    non-zero entry state."""
    ks = jax.random.split(jax.random.key(seed), 6)
    h, dk, dv = DIMS.n_heads, DIMS.d_k, DIMS.d_v
    q = _unit(jax.random.normal(ks[0], (r, t, h, dk)))
    k = _unit(jax.random.normal(ks[1], (r, t, h, dk)))
    v = jax.random.normal(ks[2], (r, t, h, dv))
    g = (-5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (r, t, h, dk))
                               - 2.0) if decay is None
         else jnp.full((r, t, h, dk), decay, jnp.float32))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (r, t, h)))
    state = jax.random.normal(ks[5], (r, h, dk, dv))
    return q, k, v, g, beta, state


@jax.jit
def _recurrence(q, k, v, g, beta, state):
    def step(s, xs):
        o, s = delta_step(*xs, s)
        return s, o

    state, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


# ---------------------------------------------------------------------
# the delta rule alone
# ---------------------------------------------------------------------
@pytest.mark.parametrize("chunk,sub", [(16, 16), (64, 16), (32, 8),
                                       (128, 16)])
def test_chunked_form_equals_the_recurrence(chunk, sub):
    q, k, v, g, beta, state = _case(1)
    assert float(jnp.exp(g).min()) < 0.05 < 0.95 < float(jnp.exp(g).max())
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    got_o, got_s = jax.jit(lambda *a: delta_chunked(
        *a, chunk=chunk, sub=sub))(q, k, v, g, beta, state)
    np.testing.assert_allclose(got_o, want_o, atol=ATOL)
    np.testing.assert_allclose(got_s, want_s, atol=ATOL)


@pytest.mark.parametrize("decay", [-4.99, -2.5, -1e-4])
def test_one_decay_on_every_channel_for_64_steps_stays_finite(decay):
    """At the lower bound a chunk's cumulative log-decay reaches -320:
    ``exp`` of it is 0 and its inverse is not an f32. Only differences
    inside a sub-block of 16 are ever exponentiated (at most e^80)."""
    q, k, v, g, beta, state = _case(2, t=64, decay=decay)
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    got_o, got_s = delta_chunked(q, k, v, g, beta, state, chunk=64)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o, want_o, atol=ATOL)
    np.testing.assert_allclose(got_s, want_s, atol=ATOL)


def test_pad_positions_leave_the_state_as_it_was():
    """g = 0 and beta = 0 at a pad: nothing decays, nothing is written."""
    q, k, v, g, beta, state = _case(3, t=32)
    real = (jnp.arange(32) < 21)[None, :, None]
    g_p = jnp.where(real[..., None], g, 0.0)
    beta_p = jnp.where(real, beta, 0.0)
    _, want = _recurrence(*(a[:, :21] for a in (q, k, v, g, beta)), state)
    _, got = delta_chunked(q, k, v, g_p, beta_p, state, chunk=16)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_a_run_that_is_not_whole_chunks_is_refused():
    q, k, v, g, beta, state = _case(4, t=24)
    with pytest.raises(ValueError, match="whole chunks"):
        delta_chunked(q, k, v, g, beta, state, chunk=16)
    with pytest.raises(ValueError, match="whole sub-blocks"):
        delta_chunked(q, k, v, g, beta, state, chunk=24, sub=16)


# ---------------------------------------------------------------------
# the mixer: projections, conv and its tail, gate, norm
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def params():
    return kda_init(jax.random.key(5), DIM, DIMS)


def _x(seed, r, t):
    return jax.random.normal(jax.random.key(seed), (r, t, DIM))


@jax.jit
def _steps(params, x, state, tail):
    def step(c, x_t):
        y, *c = kda_step(params, x_t, *c, DIMS)
        return tuple(c), y

    (state, tail), y = jax.lax.scan(step, (state, tail),
                                    jnp.moveaxis(x, 1, 0))
    return jnp.moveaxis(y, 0, 1), state, tail


def _zero(r):
    return (jnp.zeros((r, DIMS.n_heads, DIMS.d_k, DIMS.d_v)),
            jnp.zeros((r, DIMS.d_conv - 1, DIMS.d_qkv)))


@pytest.mark.parametrize("t,lens", [(16, (16, 16)), (16, (5, 11)),
                                    (64, (64, 37)), (128, (100, 128)),
                                    (32, (0, 32))])
def test_chunk_equals_steps_equals_reference(params, t, lens):
    """Ragged lengths in one bucket: each row's outputs up to its
    length, its state and its conv tail after its last real token are
    the recurrence's, and the reference's scan over time."""
    x = _x(6, 2, t)
    got_y, got_s, got_t = jax.jit(lambda x, n: kda_chunk(
        params, x, *_zero(2), n, DIMS))(x, jnp.asarray(lens))
    for row, n in enumerate(lens):
        if n == 0:
            assert not np.asarray(got_s[row]).any()
            assert not np.asarray(got_t[row]).any()
            continue
        want_y, want_s, want_t = _steps(params, x[row:row + 1, :n],
                                        *_zero(1))
        np.testing.assert_allclose(got_y[row, :n], want_y[0], atol=ATOL)
        np.testing.assert_allclose(got_s[row], want_s[0], atol=ATOL)
        np.testing.assert_allclose(got_t[row], want_t[0], atol=ATOL)
        ref_y, ref_s = reference._kda(params, x[row:row + 1, :n],
                                      jnp.asarray([n - 1]), cfg=REF_CFG)
        np.testing.assert_allclose(got_y[row, :n], ref_y[0], atol=ATOL)
        np.testing.assert_allclose(got_s[row], ref_s[0], atol=ATOL)


@pytest.mark.parametrize("first", [3, 16, 21])
def test_a_chunk_call_past_0_continues_from_state_and_tail(params, first):
    """A second call that starts past 0 (chunked prefill): entry state
    and conv tail non-zero. Both calls together are the one-shot run."""
    x = _x(7, 1, 48)
    want_y, want_s, want_t = _steps(params, x[:, :40], *_zero(1))
    pad = jnp.zeros((1, 64, DIM))
    a = jnp.concatenate([x[:, :first], pad], axis=1)[:, :32]
    _, s, tail = kda_chunk(params, a, *_zero(1), jnp.asarray([first]), DIMS)
    assert np.asarray(s).any() and np.asarray(tail).any()
    b = jnp.concatenate([x[:, first:40], pad], axis=1)[:, :64]
    got_y, got_s, got_t = kda_chunk(params, b, s, tail,
                                    jnp.asarray([40 - first]), DIMS)
    np.testing.assert_allclose(got_y[0, :40 - first], want_y[0, first:],
                               atol=ATOL)
    np.testing.assert_allclose(got_s, want_s, atol=ATOL)
    np.testing.assert_allclose(got_t, want_t, atol=ATOL)


def test_the_tail_is_stored_in_the_dtype_it_came_in(params):
    x = _x(8, 1, 16)
    state, tail = _zero(1)
    _, _, out = kda_chunk(params, x, state, tail.astype(jnp.bfloat16),
                          jnp.asarray([16]), DIMS)
    assert out.dtype == jnp.bfloat16
    _, s, out = kda_step(params, x[:, 0], state, tail.astype(jnp.bfloat16),
                         DIMS)
    assert out.dtype == jnp.bfloat16 and s.dtype == jnp.float32


def test_seeded_decays_span_slow_and_fast_channels_in_every_head():
    """``kda_published``: with a unit-spread projection the per-channel
    ``alpha`` of a random token runs from above 0.95 to under 0.3 in
    every head — decays all alike would hide a fault in the cumulative
    product."""
    dims = KDADims(n_heads=4, d_k=32, d_v=32, d_conv=4,
                   lower_bound=-5.0, eps=1e-6)
    p = kda_init(jax.random.key(9), 64, dims)
    # weights as the benchmark seeds them: x W_a of unit spread
    p = {**p, "decay": {"w": jax.random.normal(
        jax.random.key(10), (64, dims.d_key)) / 8.0}}
    from quintnet_tpu.nn.kda import _gates

    g, beta = _gates(p, jax.random.normal(jax.random.key(11), (64,)), dims)
    alpha = np.exp(np.asarray(g))                       # [H, dk]
    assert (alpha.max(axis=-1) > 0.95).all()
    assert (alpha.min(axis=-1) < 0.3).all()
    assert ((np.asarray(g) > -5.0) & (np.asarray(g) < 0.0)).all()
    assert ((np.asarray(beta) > 0) & (np.asarray(beta) < 1)).all()
