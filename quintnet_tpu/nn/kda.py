"""The Kimi-Delta-Attention mixer (KDA: the gated delta rule with a
PER-CHANNEL decay; Kimi Linear, arXiv:2510.26692) in the two forms
serving needs, which must agree with each other and with the plain
recurrence (benchmarks/lib/reference_ling_hybrid.py):

- :func:`kda_chunk` — a run of tokens per row from each row's current
  state: the CHUNKED form. Takes an entry state and conv tail and a
  valid length per row; pad positions decay nothing (``g = 0``) and
  write nothing (``beta = 0``) and do not enter the conv tail, so what
  comes back is the state and tail after the last REAL token. Prefill,
  chunked prefill and the verify contract run this.
- :func:`kda_step` — one token per row: the recurrence itself.

Per head ``h`` with state ``S`` in R^{d_k x d_v}, no positional
encoding::

    [q~ | k~ | v~] = x [W_q | W_k | W_v]       (no bias)
    [q | k | v] = silu(causal_depthwise_conv1d([q~ | k~ | v~], kernel d_conv))
    q = q / |q| * d_k^-1/2;  k = k / |k|        per head
    g = lower_bound * sigmoid(exp(A_log_h) * (x W_a + dt_bias))   in (lower_bound, 0)
    alpha = exp(g) in R^{d_k};  beta = sigmoid(x W_b)_h
    S' = Diag(alpha_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T;  o_t = S_t^T q_t
    y = (rmsnorm_head(o_t) * w * sigmoid(x W_g)_h) W_o

The state and everything of the recurrence is f32; the decays live in
LOG space (``g``) until a difference of two of them is exponentiated.
The conv tail is the last ``d_conv - 1`` rows of ``[q~ | k~ | v~]`` AS
PROJECTED (before the conv and its silu), stored in the dtype of the
pool it lives in.

The chunked form (WY / UT: one triangular solve a chunk). Inside a
chunk with entry state ``S_0`` and ``G_t = sum_{s<=t} g_s``::

    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp(G)) S_0)
    A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])      s < t, else 0
    o_t = S_0^T (exp(G_t) * q_t) + sum_{s<=t} ((k_s * exp(G_t - G_s)) . q_t) u_s
    S_C = Diag(exp(G_C)) S_0 + sum_s Diag(exp(G_C - G_s)) k_s u_s^T

``A`` is a matmul only once ``exp(G_t - G_s)`` is split into a factor
of ``t`` and one of ``s``, and ``exp(-G_s)`` over a whole chunk leaves
f32 (64 steps of -5 are e^320). So the split is made a SUB-BLOCK of
``sub`` = 16 positions at a time, relative to the sub-block's start
``b``: ``exp(G_t - G_b)`` is at most 1 and ``exp(G_b - G_s)`` at most
``exp(16 * 5) = e^80 < e^88``, which is what the lower bound of -5 is
for; for ``s`` in an earlier sub-block ``G_b - G_s`` is negative.
``1 / exp(G)`` over a chunk is never formed.

Scopes (obs/scopes.py): ``qkv``, ``conv``, ``gate``, ``delta_chunk`` /
``delta_step``, ``norm_gate``, ``proj`` here; the caller that owns the
state buffers opens ``state_update`` around the write.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from quintnet_tpu.nn.layers import linear_init, quantized_matmul
from quintnet_tpu.nn.ssm import conv_published

# the products that carry the f32 state (and the small matrices the
# solve inverts): at the chip's default an f32 matmul rounds its inputs
# to bf16, which is a bf16 state by another road
STATE_PRECISION = lax.Precision.HIGHEST


# positions a chunk of the chunked form, and positions a sub-block,
# inside which alone a ratio of cumulative decays is formed: SUB x 5 =
# 80 < 88, what f32's exp holds and a lower bound of -5 is for
CHUNK = 64
SUB = 16


class KDADims(NamedTuple):
    n_heads: int
    d_k: int
    d_v: int
    d_conv: int
    lower_bound: float
    eps: float

    @property
    def d_key(self) -> int:
        return self.n_heads * self.d_k

    @property
    def d_value(self) -> int:
        return self.n_heads * self.d_v

    @property
    def d_qkv(self) -> int:
        """Width of the conv's channels: q, then k, then v."""
        return 2 * self.d_key + self.d_value


def kda_published(u_a, u_dt):
    """``A_log`` [H] and ``dt_bias`` [H * d_k] from two arrays of
    uniform(0, 1) draws: ``exp(A_log)`` uniform in [0.75, 1.5],
    ``dt_bias`` uniform in [-8, 2]. With a projection ``x W_a`` of unit
    spread the gate's argument then runs from about -9 to 3 over the
    channels of every head: a step's per-channel decay ``alpha`` from
    above 0.99 (state that carries over hundreds of positions) down to
    0.01 — a check whose decays are all alike cannot see a fault in the
    cumulative product."""
    return {"A_log": jnp.log(0.75 + 0.75 * u_a),
            "dt_bias": -8.0 + 10.0 * u_dt}


def kda_init(key, dim: int, dims: KDADims, *, dtype=jnp.float32):
    ks = jax.random.split(key, 10)

    def lin(k, fin, fout):
        return linear_init(k, fin, fout, use_bias=False, dtype=dtype)

    return {
        "q": lin(ks[0], dim, dims.d_key),
        "k": lin(ks[1], dim, dims.d_key),
        "v": lin(ks[2], dim, dims.d_value),
        "decay": lin(ks[3], dim, dims.d_key),
        "beta": lin(ks[4], dim, dims.n_heads),
        "gate": lin(ks[5], dim, dims.n_heads),
        "o": lin(ks[6], dims.d_value, dim),
        # tap 0 is the oldest position; fan-in d_conv, no bias
        "conv": {"w": conv_published(jax.random.uniform(
            ks[7], (dims.d_conv, dims.d_qkv)), dims.d_conv)},
        **kda_published(jax.random.uniform(ks[8], (dims.n_heads,)),
                        jax.random.uniform(ks[9], (dims.d_key,))),
        "norm": {"scale": jnp.ones((dims.d_v,), jnp.float32)},
    }


# ---------------------------------------------------------------------
# projections, the gate, the output
# ---------------------------------------------------------------------
def _project(p, x):
    """``x`` [..., D] -> ``[q~ | k~ | v~]`` [..., d_qkv] as projected,
    f32."""
    with jax.named_scope("qkv"):
        return jnp.concatenate(
            [quantized_matmul(x, p[n]).astype(jnp.float32)
             for n in ("q", "k", "v")], axis=-1)


def _gates(p, x, dims: KDADims):
    """``x`` [..., D] -> the log-decay ``g`` [..., H, d_k] in
    ``(lower_bound, 0)`` and the write strength ``beta`` [..., H], f32."""
    with jax.named_scope("gate"):
        a = quantized_matmul(x, p["decay"]).astype(jnp.float32)
        a = (a + p["dt_bias"]).reshape(*a.shape[:-1], dims.n_heads,
                                       dims.d_k)
        g = dims.lower_bound * jax.nn.sigmoid(
            jnp.exp(p["A_log"])[:, None] * a)
        beta = jax.nn.sigmoid(
            quantized_matmul(x, p["beta"]).astype(jnp.float32))
        return g, beta


def _heads(qkv, dims: KDADims):
    """The conv's output [..., d_qkv] -> q, k [..., H, d_k] normalized
    (``q`` scaled by ``d_k^-1/2`` as well) and v [..., H, d_v]."""
    q, k, v = jnp.split(qkv, [dims.d_key, 2 * dims.d_key], axis=-1)
    q = q.reshape(*q.shape[:-1], dims.n_heads, dims.d_k)
    k = k.reshape(*k.shape[:-1], dims.n_heads, dims.d_k)
    v = v.reshape(*v.shape[:-1], dims.n_heads, dims.d_v)

    def unit(a):
        return a * lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True)
                             + 1e-6)

    return unit(q) * dims.d_k ** -0.5, unit(k), v


def _norm_gate_out(p, o, x, dims: KDADims):
    """``o`` [..., H, d_v] f32: the per-head RMSNorm with its learned
    scale, the head-wise sigmoid gate of ``x``, then ``W_o``."""
    with jax.named_scope("norm_gate"):
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + dims.eps) * p["norm"]["scale"]
        gate = jax.nn.sigmoid(
            quantized_matmul(x, p["gate"]).astype(jnp.float32))
        o = (o * gate[..., None]).astype(x.dtype)
    with jax.named_scope("proj"):
        return quantized_matmul(
            o.reshape(*o.shape[:-2], dims.d_value), p["o"])


# ---------------------------------------------------------------------
# the delta rule
# ---------------------------------------------------------------------
def delta_step(q, k, v, g, beta, state):
    """The recurrence, one token a row: ``q``/``k``/``g`` [R, H, K],
    ``v`` [R, H, V], ``beta`` [R, H], ``state`` [R, H, K, V], all f32
    -> (``o`` [R, H, V], the new state). Elementwise products and sums
    over the state where it lies: nothing rounds it."""
    decayed = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(decayed * k[..., None], axis=-2))
    state = decayed + k[..., None] * u[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def delta_chunked(q, k, v, g, beta, state, *, chunk: int = CHUNK,
                  sub: int = SUB):
    """The chunked form (module docstring). ``q``/``k``/``g`` [R, T, H,
    K], ``v`` [R, T, H, V], ``beta`` [R, T, H] (``g`` and ``beta`` 0
    where the position is a pad), ``state`` [R, H, K, V]; all f32, ``T``
    a multiple of ``chunk`` and ``chunk`` of ``sub``. Returns (``o``
    [R, T, H, V], the state after position T - 1)."""
    r, t, h, dk = q.shape
    dv = v.shape[-1]
    if t % chunk or chunk % sub:
        raise ValueError(
            f"a run of {t} tokens is not whole chunks of {chunk}, or the "
            f"chunk not whole sub-blocks of {sub}")
    nc, n = t // chunk, chunk // sub
    hi = STATE_PRECISION

    def chunks(a):                      # [R, T, H, .] -> [R, nc, H, C, .]
        return a.reshape(r, nc, chunk, h, -1).transpose(0, 1, 3, 2, 4)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])                       # [R,nc,H,C,1]
    cum = jnp.cumsum(g, axis=-2)                         # G, [R,nc,H,C,K]
    # G just before each sub-block's start, and each position's G
    # relative to its own sub-block's: in [sub * lower_bound, 0]
    before = jnp.concatenate(
        [jnp.zeros_like(cum[..., :1, :]),
         cum[..., sub - 1:chunk - 1:sub, :]], axis=-2)   # [R,nc,H,n,K]
    local = jnp.exp(cum - jnp.repeat(before, sub, axis=-2))
    # the keys as sub-block i meets them: k_s exp(G_b(i) - G_s) for
    # every s before the sub-block's end (at most e^(sub * 5) inside
    # it, at most 1 before it), 0 after
    s_at = jnp.arange(chunk)
    seen = s_at[None, :] < (jnp.arange(n)[:, None] + 1) * sub     # [n, C]
    met = k[..., None, :, :] * jnp.exp(jnp.where(
        seen[:, :, None],
        before[..., :, None, :] - cum[..., None, :, :], -jnp.inf))

    def against_met(a):
        """sum_c a_t[c] exp(G_t - G_s)[c] k_s[c]: [R,nc,H,C,C]."""
        a = (a * local).reshape(r, nc, h, n, sub, dk)
        return jnp.einsum("...itc,...isc->...its", a, met,
                          precision=hi).reshape(r, nc, h, chunk, chunk)

    earlier = s_at[:, None] > s_at[None, :]              # s < t
    a_kk = jnp.where(earlier, against_met(k), 0.0)
    a_qk = jnp.where(earlier | jnp.eye(chunk, dtype=bool),
                     against_met(q), 0.0)
    # (I + Diag(beta) A) [U_v | W_k] = Diag(beta) [V | K exp(G)]: the
    # part of U that needs no state, and what multiplies the entry state
    solved = solve_triangular(
        jnp.eye(chunk) + beta * a_kk,
        beta * jnp.concatenate([v, k * jnp.exp(cum)], axis=-1),
        lower=True, unit_diagonal=True)
    u_v, w_k = solved[..., :dv], solved[..., dv:]
    q_in = q * jnp.exp(cum)
    to_end = k * jnp.exp(cum[..., -1:, :] - cum)
    kept = jnp.exp(cum[..., -1, :])                      # [R,nc,H,K]

    def carry(s, xs):
        u_v, w_k, q_in, a_qk, to_end, kept = xs
        u = u_v - jnp.einsum("rhck,rhkv->rhcv", w_k, s, precision=hi)
        o = (jnp.einsum("rhck,rhkv->rhcv", q_in, s, precision=hi)
             + jnp.einsum("rhts,rhsv->rhtv", a_qk, u, precision=hi))
        s = kept[..., None] * s + jnp.einsum("rhck,rhcv->rhkv", to_end, u,
                                             precision=hi)
        return s, o

    state, o = lax.scan(carry, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (u_v, w_k, q_in, a_qk, to_end, kept)))
    # [nc, R, H, C, V] -> [R, T, H, V]
    return o.transpose(1, 0, 3, 2, 4).reshape(r, t, h, dv), state


# ---------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------
def kda_chunk(p, x, state, tail, lens, dims: KDADims):
    """``x`` [R, T, D] (normalized residual), ``state`` [R, H, d_k, d_v]
    f32, ``tail`` [R, d_conv - 1, d_qkv], ``lens`` [R] real tokens a row
    -> (out [R, T, D], state, tail) after each row's last real token. A
    row of length 0 gets its state and tail back unchanged."""
    r, t, _ = x.shape
    kk = dims.d_conv
    qkv = _project(p, x)
    real = jnp.arange(t)[None, :] < lens[:, None]
    with jax.named_scope("conv"):
        ext = jnp.concatenate([tail.astype(jnp.float32), qkv], axis=1)
        w = p["conv"]["w"]
        conv = sum(ext[:, j:j + t] * w[j] for j in range(kk))
        # the last d_conv - 1 real rows as projected
        rows = lens[:, None] + jnp.arange(kk - 1)[None, :]
        new_tail = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
        q, k, v = _heads(jax.nn.silu(conv), dims)
    g, beta = _gates(p, x, dims)
    with jax.named_scope("delta_chunk"):
        g = jnp.where(real[:, :, None, None], g, 0.0)
        beta = jnp.where(real[:, :, None], beta, 0.0)
        # a run that is not whole chunks (a ladder's top bucket, a short
        # verify run) rides in one that is: the columns added are pads
        sub = min(SUB, t)
        chunk = min(CHUNK, -(-t // sub) * sub)
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, -t % chunk)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
        o, state = delta_chunked(q, k, v, g, beta, state, chunk=chunk,
                                 sub=sub)
        o = o[:, :t]
    return _norm_gate_out(p, o, x, dims), state, new_tail.astype(tail.dtype)


def kda_step(p, x, state, tail, dims: KDADims):
    """One token a row: ``x`` [R, D], ``state`` [R, H, d_k, d_v] f32,
    ``tail`` [R, d_conv - 1, d_qkv] -> (out [R, D], state, tail)."""
    qkv = _project(p, x)
    with jax.named_scope("conv"):
        window = jnp.concatenate(
            [tail.astype(jnp.float32), qkv[:, None, :]], axis=1)
        conv = jnp.sum(window * p["conv"]["w"], axis=1)
        q, k, v = _heads(jax.nn.silu(conv), dims)
    g, beta = _gates(p, x, dims)
    with jax.named_scope("delta_step"):
        o, state = delta_step(q, k, v, g, beta, state)
    return (_norm_gate_out(p, o, x, dims), state,
            window[:, 1:].astype(tail.dtype))
