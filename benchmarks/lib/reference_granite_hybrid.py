"""Granite 4.0-H (``granitemoehybrid``, dense), plainly: the forward pass
in ``jax.numpy``, float32, every matmul at ``precision="highest"``, the
state-space layers as a plain ``lax.scan`` over TIME — one position
after another, no chunking, no cache, no paging, no batching tricks.
Nothing is imported from ``quintnet_tpu``.

Written from the published description (the model's ``config.json``
and the ``GraniteMoeHybrid`` / Mamba-2 modelling code it names; Dao &
Gu 2024 for the recurrence). With ``x`` the residual stream and ``r =
residual_multiplier``::

    x0 = E[ids] * embedding_multiplier            (no positions at all)
    x  = x + r * mixer(rmsnorm(x))                per layer, by layer_types
    x  = x + r * W_d (silu(W_g u) * (W_u u)),  u = rmsnorm(x)
    logits = (rmsnorm(x) E^T) / logits_scaling

attention mixer: q, k, v projections without bias and WITHOUT rotation,
``num_key_value_heads`` KV heads each shared by a contiguous group of
query heads, ``scores = q k^T * attention_multiplier``, causal softmax,
``W_o``. Mamba-2 mixer (one group), per head h with S in R^{P x N}::

    [z | xBC | dt] = W_in u
    xBC_t = silu(b + sum_j w[j] xBC_{t-(K-1)+j})     causal, depthwise
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    g = y * silu(z);  out = W_out (w * g / sqrt(mean(g^2) + eps))

Departures in layout only: HF fuses ``W_g | W_u`` (``input_linear``)
and ``W_in``'s three column groups (``in_proj``); the tree has
``mlp.gate`` and ``mlp.up``, and ``mixer.in_z``, ``in_xbc``, ``in_dt``.

The parameter TREE is the program's (the same weights are compared):
``embedding.tok``; ``blocks.mamba.{ln1,ln2}.scale``, ``.mixer.{in_z,in_xbc,
in_dt,out_proj}.w``, ``.mixer.conv.{w [K, C], b}``, ``.mixer.{A_log,dt_bias,
D}``, ``.mixer.norm.scale``, ``.mlp.{gate,up,down}.w`` with a leading
axis over the Mamba layers in model order; ``blocks.attn.{ln1,ln2}``,
``.attn.{q,k,v,o}.w``, ``.mlp`` with a leading axis over the attention
layers; ``head.ln_f``. Leaves may be stored in bf16; each layer's are
cast up as it is used, one layer at a time in a Python loop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _mlp(blk, x, cfg):
    u = _rms(blk["ln2"]["scale"], x, cfg["rms_norm_eps"])
    m = blk["mlp"]
    h = jax.nn.silu(_mm(u, m["gate"]["w"])) * _mm(u, m["up"]["w"])
    return x + cfg["residual_multiplier"] * _mm(h, m["down"]["w"])


def _attention(p, u, cfg):
    b, t, d = u.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq

    def heads(name, n):
        return _mm(u, p[name]["w"]).reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    q, k, v = heads("q", hq), heads("k", hkv), heads("v", hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)        # head g serves q heads
    v = jnp.repeat(v, hq // hkv, axis=1)        # [g*rep, (g+1)*rep)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=HI) * cfg["attention_multiplier"]
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v,
                   precision=HI)
    return _mm(o.transpose(0, 2, 1, 3).reshape(b, t, d), p["o"]["w"])


def _mamba2(p, u, cfg, lengths):
    """-> (out [b, t, d], S [b, h, P, N] after each row's last position:
    past ``lengths`` a row's ``dt`` is 0, so its state stands still)."""
    b, t, _ = u.shape
    h, pd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    d_in = h * pd
    z, xbc, dt = (_mm(u, p[name]["w"]) for name in ("in_z", "in_xbc",
                                                    "in_dt"))
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = p["conv"]["b"] + sum(
        padded[:, j:j + t] * p["conv"]["w"][j] for j in range(k))
    x, bm, cm = jnp.split(jax.nn.silu(conv), [d_in, d_in + n], axis=-1)
    x = x.reshape(b, t, h, pd)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # [b, t, h]
    dt = jnp.where((jnp.arange(t) < lengths[:, None])[:, :, None], dt, 0.0)
    a = -jnp.exp(p["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, :, None, None] * s
             + jnp.einsum("bh,bhp,bn->bhpn", dt_t, x_t, b_t, precision=HI))
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HI)

    s_end, y = jax.lax.scan(
        step, jnp.zeros((b, h, pd, n), jnp.float32),
        (x.transpose(1, 0, 2, 3), bm.transpose(1, 0, 2),
         cm.transpose(1, 0, 2), dt.transpose(1, 0, 2)))
    y = y.transpose(1, 0, 2, 3) + p["D"][:, None] * x
    g = y.reshape(b, t, d_in) * jax.nn.silu(z)
    return _mm(_rms(p["norm"]["scale"], g, cfg["rms_norm_eps"]),
               p["out_proj"]["w"]), s_end


def _layer(blk, x, cfg, kind: str, lengths):
    blk = _f32(blk)
    u = _rms(blk["ln1"]["scale"], x, cfg["rms_norm_eps"])
    mixed, s_end = ((_attention(blk["attn"], u, cfg), None)
                    if kind == "attention"
                    else _mamba2(blk["mixer"], u, cfg, lengths))
    return _mlp(blk, x + cfg["residual_multiplier"] * mixed, cfg), s_end


_HASHED = ("rms_norm_eps", "residual_multiplier", "attention_multiplier",
           "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
           "mamba_d_head", "mamba_d_state", "mamba_d_conv")


@partial(jax.jit, static_argnames=("scalars", "kind"))
def _layer_jit(blk, x, lengths, scalars, kind):
    return _layer(blk, x, dict(scalars), kind, lengths)


@partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(ln_f, table, h, eps, scaling):
    h = _rms(ln_f["scale"].astype(jnp.float32), h, eps)
    return _mm(h, table.astype(jnp.float32).T) / scaling


def forward(params, ids, config, *, positions=None, lengths=None):
    """``ids`` [B, T] int32 -> logits float32, at every position or at
    ``positions`` (a list of indices: the head is as large as the
    vocabulary and is computed only where it is read). ``config`` is
    the configuration file's dict (the Hugging Face keys).

    With ``lengths`` (one a row; a row's logits past its own are then
    not to be read) -> (logits, S [Mamba layers, B, heads, P, N]): each
    state-space layer's state after each row's LAST position, what a
    cache has to hold for the row to go on."""
    scalars = tuple((k, config[k]) for k in _HASHED)
    x = (params["embedding"]["tok"][ids].astype(jnp.float32)
         * config["embedding_multiplier"])
    ends = jnp.asarray([ids.shape[1]] * ids.shape[0] if lengths is None
                       else lengths, jnp.int32)
    seen = {"mamba": 0, "attention": 0}
    states = []
    for kind in config["layer_types"]:
        stack = params["blocks"]["attn" if kind == "attention" else "mamba"]
        blk = jax.tree.map(lambda a: a[seen[kind]], stack)
        seen[kind] += 1
        x, s_end = _layer_jit(blk, x, ends, scalars=scalars, kind=kind)
        if s_end is not None:
            states.append(s_end)
    if positions is not None:
        x = x[:, jnp.asarray(positions)]
    logits = _head(params["head"]["ln_f"], params["embedding"]["tok"], x,
                   eps=config["rms_norm_eps"],
                   scaling=config["logits_scaling"])
    return logits if lengths is None else (logits, jnp.stack(states))
