"""The Mamba-2 mixer (state-space duality, Dao & Gu 2024) in the two
forms serving needs, which must agree with each other and with the
plain recurrence (benchmarks/lib/reference_granite_hybrid.py):

- :func:`mamba2_chunk` — a run of tokens per row from each row's
  current state: the CHUNKED scan. Inside a chunk of ``chunk`` tokens
  the quadratic form masked by the cumulative decay (matmuls), between
  chunks the f32 state carried. Takes an initial state and conv tail
  and a valid length per row; pad positions contribute ``dt = 0`` (the
  state neither decays nor grows there) and do not enter the conv
  tail, so what comes back is the state and tail after the last REAL
  token. Prefill, chunked prefill and the verify contract run this.
- :func:`mamba2_step` — one token per row: the recurrence itself.

Per head ``h`` with state ``S`` in R^{d_head x d_state}::

    [z | xBC | dt] = W_in u                    (no bias)
    xBC = silu(causal_depthwise_conv1d(xBC, kernel d_conv) + b)
    [x | B | C] = xBC        (x: n_heads x d_head; B, C shared: 1 group)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t
    y = w * g / sqrt(mean(g^2) + eps),  g = y * silu(z)   (mean over all)
    out = W_out y

The state ``S`` and everything of the recurrence is f32. The conv tail
is the last ``d_conv - 1`` rows of ``xBC`` AS PROJECTED (before the
conv and its silu), stored in the dtype of the pool it lives in.

Layout, not mathematics: ``W_in`` is three leaves by what its columns
give (``in_z``, ``in_xbc``, ``in_dt``) where the published model fuses
them. The fused width (8512 at the published sizes) is not a multiple
of the TPU's 128-lane tile: as one parameter the compiler kept it in a
layout of its own and copied all layers' worth of it to the matmul's
layout in every program (1.17 GiB; compile, PR 27), and ``z``, ``xBC``
and ``dt`` were cut out of the product at unaligned columns.

Scopes (obs/scopes.py): ``in_proj``, ``conv``, ``ssd``, ``gate_norm``,
``out_proj`` here; the caller that owns the state buffers opens
``state_update`` around the write.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from quintnet_tpu.nn.layers import linear_init, quantized_matmul


class Mamba2Dims(NamedTuple):
    n_heads: int
    d_head: int
    d_state: int
    d_conv: int
    chunk: int
    eps: float

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def d_xbc(self) -> int:
        """Width of the conv's channels: x, then B and C of one group."""
        return self.d_inner + 2 * self.d_state


def ssm_published(u_a, u_dt):
    """``A_log``, ``dt_bias``, ``D`` as Mamba-2 publishes them, from two
    arrays of uniform(0, 1) draws: ``A`` uniform in [1, 16], ``dt``
    log-uniform in [1e-3, 1e-1] through the inverse softplus, ``D`` = 1.
    The decay of a step is then ``exp(-dt A)`` in about [0.2, 0.999]:
    state that carries over hundreds of positions."""
    dt = jnp.exp(math.log(1e-3) + u_dt * math.log(1e-1 / 1e-3))
    return {"A_log": jnp.log(1.0 + 15.0 * u_a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones_like(u_a)}


def conv_published(u, d_conv: int):
    """The depthwise conv's weight as ``torch.nn.Conv1d`` draws it
    (fan-in ``d_conv``), from uniform(0, 1) draws ``[..., d_conv,
    channels]``: uniform in +-1/sqrt(d_conv). Tap 0 is the oldest
    position."""
    return (2.0 * u - 1.0) / math.sqrt(d_conv)


def mamba2_init(key, dim: int, dims: Mamba2Dims, *, dtype=jnp.float32):
    k_z, k_xbc, k_dt, k_conv, k_ssm, k_out = jax.random.split(key, 6)

    def lin(k, fin, fout):
        return linear_init(k, fin, fout, use_bias=False, dtype=dtype)

    return {
        "in_z": lin(k_z, dim, dims.d_inner),
        "in_xbc": lin(k_xbc, dim, dims.d_xbc),
        "in_dt": lin(k_dt, dim, dims.n_heads),
        "conv": {"w": conv_published(jax.random.uniform(
                     k_conv, (dims.d_conv, dims.d_xbc)), dims.d_conv),
                 "b": jnp.zeros((dims.d_xbc,), jnp.float32)},
        **ssm_published(*jax.random.uniform(k_ssm, (2, dims.n_heads))),
        "norm": {"scale": jnp.ones((dims.d_inner,), jnp.float32)},
        "out_proj": lin(k_out, dims.d_inner, dim),
    }


def _project(p, u):
    """``u`` [..., D] -> z [..., d_inner], xBC [..., d_xbc] (as
    projected), dt [..., H] (softplus applied), all f32."""
    with jax.named_scope("in_proj"):
        z, xbc, dt = (quantized_matmul(u, p[name]).astype(jnp.float32)
                      for name in ("in_z", "in_xbc", "in_dt"))
        return z, xbc, jax.nn.softplus(dt + p["dt_bias"])


def _split_xbc(xbc, dims: Mamba2Dims):
    x, b, c = jnp.split(
        xbc, [dims.d_inner, dims.d_inner + dims.d_state], axis=-1)
    return x.reshape(*x.shape[:-1], dims.n_heads, dims.d_head), b, c


def _gate_out(p, y, z, x_dtype, dims: Mamba2Dims):
    """Gated RMSNorm over all ``d_inner`` channels, then ``W_out``."""
    with jax.named_scope("gate_norm"):
        g = y * jax.nn.silu(z)
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + dims.eps)
        g = (g * p["norm"]["scale"]).astype(x_dtype)
    with jax.named_scope("out_proj"):
        return quantized_matmul(g, p["out_proj"])


def _segment_decay(cum):
    """``exp(cum[q] - cum[s])`` for ``q >= s``, 0 above the diagonal:
    [..., Q, H] -> [..., H, Q, Q]. The mask goes on the exponent: above
    the diagonal it is positive and its exponential may overflow."""
    c = jnp.moveaxis(cum, -1, -2)                         # [..., H, Q]
    diff = c[..., :, None] - c[..., None, :]
    q = cum.shape[-2]
    keep = jnp.tril(jnp.ones((q, q), bool))
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


def ssd_chunked(x, dt, a_neg, b, c, state, *, chunk: int):
    """The chunked scan. ``x`` [R, T, H, P], ``dt`` [R, T, H] (0 where
    the position is a pad), ``a_neg`` [H] (= -exp(A_log)), ``b``/``c``
    [R, T, N], ``state`` [R, H, P, N]; all f32, ``T`` a multiple of
    ``chunk``. Returns (``y`` [R, T, H, P] without the ``D x`` term,
    the state after position T - 1)."""
    r, t, h, p = x.shape
    n = b.shape[-1]
    nc = t // chunk
    xdt = (x * dt[..., None]).reshape(r, nc, chunk, h, p)
    cum = jnp.cumsum((dt * a_neg).reshape(r, nc, chunk, h), axis=2)
    bc = b.reshape(r, nc, chunk, n)
    cc = c.reshape(r, nc, chunk, n)

    # inside a chunk: y[q] = sum_{s<=q} (C_q . B_s) decay(s -> q) dt_s x_s
    scores = jnp.einsum("rcqn,rcsn->rcqs", cc, bc)
    mixed = scores[:, :, None] * _segment_decay(cum)      # [R,nc,H,Q,Q]
    y = jnp.einsum("rchqs,rcshp->rcqhp", mixed, xdt)

    # what a chunk adds to the state, and how much of the old it keeps
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)             # [R,nc,Q,H]
    added = jnp.einsum("rcshp,rcsn->rchpn", xdt * to_end[..., None], bc)
    kept = jnp.exp(cum[:, :, -1, :])                      # [R,nc,H]

    def carry(s, step):
        add, keep = step
        return keep[..., None, None] * s + add, s

    state, entering = lax.scan(
        carry, state, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(kept, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)               # [R,nc,H,P,N]
    y = y + (jnp.einsum("rcqn,rchpn->rcqhp", cc, entering)
             * jnp.exp(cum)[..., None])
    return y.reshape(r, t, h, p), state


def mamba2_chunk(p, u, state, tail, lens, dims: Mamba2Dims):
    """``u`` [R, T, D] (normalized residual), ``state`` [R, H, P, N]
    f32, ``tail`` [R, d_conv - 1, d_xbc], ``lens`` [R] real tokens a
    row -> (out [R, T, D], state, tail) after each row's last real
    token. A row of length 0 gets its state and tail back unchanged."""
    r, t, _ = u.shape
    k = dims.d_conv
    z, xbc, dt = _project(p, u)
    real = jnp.arange(t)[None, :] < lens[:, None]
    with jax.named_scope("conv"):
        ext = jnp.concatenate([tail.astype(jnp.float32), xbc], axis=1)
        w = p["conv"]["w"]
        conv = p["conv"]["b"] + sum(
            ext[:, j:j + t] * w[j] for j in range(k))
        # the last k - 1 real rows as projected: ext[len : len + k - 1]
        rows = lens[:, None] + jnp.arange(k - 1)[None, :]
        new_tail = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
        x, b, c = _split_xbc(jax.nn.silu(conv), dims)
    with jax.named_scope("ssd"):
        dt = jnp.where(real[:, :, None], dt, 0.0)
        chunk = min(dims.chunk, t)
        if t % chunk:
            raise ValueError(
                f"a run of {t} tokens is not a whole number of chunks "
                f"of {chunk}: pad the bucket to a multiple")
        y, state = ssd_chunked(x, dt, -jnp.exp(p["A_log"]), b, c, state,
                               chunk=chunk)
        y = y + p["D"][:, None] * x
    out = _gate_out(p, y.reshape(r, t, dims.d_inner), z, u.dtype, dims)
    return out, state, new_tail.astype(tail.dtype)


def mamba2_step(p, u, state, tail, dims: Mamba2Dims):
    """One token a row: ``u`` [R, D], ``state`` [R, H, P, N] f32,
    ``tail`` [R, d_conv - 1, d_xbc] -> (out [R, D], state, tail)."""
    z, xbc, dt = _project(p, u)
    with jax.named_scope("conv"):
        window = jnp.concatenate(
            [tail.astype(jnp.float32), xbc[:, None, :]], axis=1)
        conv = p["conv"]["b"] + jnp.sum(window * p["conv"]["w"], axis=1)
        x, b, c = _split_xbc(jax.nn.silu(conv), dims)
    with jax.named_scope("ssd"):
        decay = jnp.exp(dt * -jnp.exp(p["A_log"]))           # [R, H]
        state = (decay[:, :, None, None] * state
                 + (dt[:, :, None] * x)[..., None] * b[:, None, None, :])
        y = (jnp.sum(state * c[:, None, None, :], axis=-1)
             + p["D"][:, None] * x)
    out = _gate_out(p, y.reshape(-1, dims.d_inner), z, u.dtype, dims)
    return out, state, window[:, 1:].astype(tail.dtype)
