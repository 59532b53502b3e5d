"""Ling 3.0 (``bailing_hybrid``), plainly: the forward pass in
``jax.numpy``, float32, every matmul at ``precision="highest"`` — the
linear-attention layers as a plain ``lax.scan`` over TIME, one position
after another (no chunks, no WY form, no triangular solve), the latent
layer with no cache, no paging and no absorption of ``W_uk``/``W_uv``,
the experts a Python loop with a mask (no sort, no grouped matmul).
Nothing is imported from ``quintnet_tpu``.

Written from the published description (the model's ``config.json``;
Kimi Linear, arXiv:2510.26692, for the delta rule with a per-channel
decay; the DeepSeek-V2/V3 papers for latent attention and the
``noaux_tc`` router). With ``h`` the residual stream, every norm an
RMSNorm with ``rms_norm_eps``, pre-norm::

    h = h + mixer(N1(h));  h = h + FFN(N2(h));  logits = Nf(h) W_head

KDA mixer (layers ``i`` with ``(i + 1) % layer_group_size != 0``; no
positional encoding), per head with ``S`` in R^{dk x dv}::

    q~ = x W_q, k~ = x W_k, v~ = x W_v
    [q | k | v]_t = silu(sum_j w[j] [q~ | k~ | v~]_{t-(K-1)+j})   causal, depthwise, zeros before 0
    q = q / sqrt(|q|^2 + 1e-6) / sqrt(dk);  k = k / sqrt(|k|^2 + 1e-6)
    g = kda_lower_bound * sigmoid(exp(A_log_h) (x W_a + dt_bias));  alpha = exp(g)
    beta = sigmoid(x W_b)_h
    S' = Diag(alpha_t) S_{t-1};  u = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u^T;  o_t = S_t^T q_t
    y = concat_h(w * o_t / sqrt(mean(o_t^2) + eps) * sigmoid(x W_g)_h) W_o

MLA mixer (layers with ``(i + 1) % layer_group_size == 0``): ``q = x
W_q`` -> heads of ``[q_nope | q_rope]`` (no down-projection, no query
norm); ``[ckv | k_r] = x W_dkv``; ``c = Nkv(ckv)``; ``k_rope =
RoPE(k_r)`` shared by all heads, ``q_rope = RoPE(q_rope)`` (theta
``rope_theta``, plain frequencies, lanes i and i + d/2 a pair);
``[k_nope | v] = c W_ukv`` per head; scores ``(q_nope . k_nope + q_rope
. k_rope) / sqrt(nope + rope)``, causal softmax; ``y = concat_h(
sigmoid(x W_g)_h P v) W_o``.

``FFN``: SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers; after them ``s = sigmoid(x W_r)``
over the router's ``num_experts_published`` experts; selection score
``s + b``; ``n_group`` groups of consecutive experts, a group's score
the sum of its two largest ``s + b``; the ``topk_group`` best groups;
the ``num_experts_per_tok`` largest ``s + b`` inside them; ``w =
routed_scaling_factor * s_e / sum(s chosen)`` from the UNBIASED ``s``;
``y = SwiGLU_shared(x) + sum over the chosen experts e HELD HERE of w_e
SwiGLU_e(x)``: the experts held are ``[experts_first, experts_first +
num_experts)``, a routing to any other adds nothing (it is another
chip's part of the sum). Ties go to the lower index.

The parameter TREE is the program's (the same weights are compared):
``embedding.tok`` [V, D]; ``blocks.kda`` (leading axis: the KDA layers
in model order) ``ln1.scale``, ``mixer.{q,k,v,decay,beta,gate,o}.w``,
``mixer.conv.w`` [K, C], ``mixer.{A_log [H], dt_bias [H dk]}``,
``mixer.norm.scale`` [dv]; ``blocks.mla`` (the latent layers)
``ln1.scale``, ``attn.{q,kv_down,kv_up,gate,o}.w``,
``attn.kv_norm.scale``; ``blocks.dense`` (the leading layers' FFN)
``ln2.scale``, ``mlp.{gate,up,down}.w``; ``blocks.moe`` (every later
layer's) ``ln2.scale``, ``moe.router.{w [D, E], e_score_correction_bias
[E]}``, ``moe.experts.{gate,up,down}.w`` [held, in, out],
``moe.shared.{gate,up,down}.w``; ``head.ln_f.scale``, ``head.out.w``
[D, V]. Leaves may be stored in bf16: a layer's are cast up as it is
used, the experts one at a time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(
        scale)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HI)


def _swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"]["w"])) * _mm(x, p["up"]["w"]),
               p["down"]["w"])


def _rope(x, positions, theta):
    """x [..., T, d] rotated at ``positions`` [T]: lane i pairs with
    lane i + d/2."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


# ---------------------------------------------------------------------
# the KDA mixer: a scan over time
# ---------------------------------------------------------------------
@partial(jax.jit, static_argnames=("cfg",))
def _kda(p, x, state_at, cfg):
    """x [B, T, D] -> (y [B, T, D], the state [B, H, dk, dv] after each
    row's position ``state_at`` [B])."""
    c = dict(cfg)
    b, t, _ = x.shape
    h, dk = c["num_attention_heads"], c["head_dim"]
    kk, eps = c["short_conv_kernel_size"], c["rms_norm_eps"]
    raw = jnp.concatenate([_mm(x, p[n]["w"]) for n in ("q", "k", "v")],
                          axis=-1)
    ext = jnp.pad(raw, ((0, 0), (kk - 1, 0), (0, 0)))
    w = _f32(p["conv"]["w"])
    qkv = jax.nn.silu(sum(ext[:, j:j + t] * w[j] for j in range(kk)))
    q, k, v = (a.reshape(b, t, h, dk) for a in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / jnp.sqrt(jnp.float32(dk))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    a = (_mm(x, p["decay"]["w"]) + _f32(p["dt_bias"])).reshape(b, t, h, dk)
    alpha = jnp.exp(c["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(_f32(p["A_log"]))[:, None] * a))
    beta = jax.nn.sigmoid(_mm(x, p["beta"]["w"]))          # [b, t, h]

    def step(carry, xs):
        s, kept = carry
        q_t, k_t, v_t, alpha_t, beta_t, at = xs
        s = s * alpha_t[..., None]                         # Diag(alpha) S
        u = beta_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision=HI))
        s = s + k_t[..., None] * u[..., None, :]
        kept = jnp.where((at == state_at)[:, None, None, None], s, kept)
        return (s, kept), jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HI)

    zero = jnp.zeros((b, h, dk, dk), jnp.float32)
    (_, kept), o = jax.lax.scan(
        step, (zero, zero),
        (*(jnp.moveaxis(z, 1, 0) for z in (q, k, v, alpha, beta)),
         jnp.arange(t)))
    o = jnp.moveaxis(o, 0, 1)                              # [b, t, h, dv]
    o = _rms(p["norm"]["scale"], o, eps) * jax.nn.sigmoid(
        _mm(x, p["gate"]["w"]))[..., None]
    return _mm(o.reshape(b, t, h * dk), p["o"]["w"]), kept


# ---------------------------------------------------------------------
# the latent mixer
# ---------------------------------------------------------------------
@partial(jax.jit, static_argnames=("cfg",))
def _mla(p, x, cfg):
    """x [B, T, D] -> (y [B, T, D], the rows ``[c | k_rope]`` [B, T,
    rank + rope] a latent cache would hold)."""
    c = dict(cfg)
    b, t, _ = x.shape
    h = c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, vd, eps = c["kv_lora_rank"], c["v_head_dim"], c["rms_norm_eps"]
    pos = jnp.arange(t)
    q = _mm(x, p["q"]["w"]).reshape(b, t, h, nope + rope).transpose(
        0, 2, 1, 3)                                        # [b, h, t, .]
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos,
                                          c["rope_theta"])
    ckv = _mm(x, p["kv_down"]["w"])
    lat = _rms(p["kv_norm"]["scale"], ckv[..., :rank], eps)
    k_rope = _rope(ckv[..., rank:], pos, c["rope_theta"])  # [b, t, rope]
    kv = _mm(lat, p["kv_up"]["w"]).reshape(b, t, h, nope + vd).transpose(
        0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope, precision=HI)
              + jnp.einsum("bhqd,bkd->bhqk", q_rope, k_rope, precision=HI)
              ) / jnp.sqrt(jnp.float32(nope + rope))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v,
                   precision=HI).transpose(0, 2, 1, 3)     # [b, t, h, vd]
    o = o * jax.nn.sigmoid(_mm(x, p["gate"]["w"]))[..., None]
    return (_mm(o.reshape(b, t, h * vd), p["o"]["w"]),
            jnp.concatenate([lat, k_rope], axis=-1))


# ---------------------------------------------------------------------
# the mixture
# ---------------------------------------------------------------------
def _largest(x, k):
    """Indices of the ``k`` largest along the last axis, the lower
    index first among equals."""
    return jnp.argsort(-x, axis=-1, stable=True)[..., :k]


@partial(jax.jit, static_argnames=("k", "scale", "norm", "n_group",
                                   "topk_group", "bias_in_weights"))
def _route(w_router, bias, x, k, scale, norm, n_group, topk_group,
           bias_in_weights=False):
    """-> (chosen experts [b, t, k], their weights [b, t, k], the kept
    groups [b, t, n_group] bool)."""
    s = jax.nn.sigmoid(_mm(x, w_router))
    select = s + _f32(bias)
    e = s.shape[-1]
    by_group = select.reshape(*select.shape[:-1], n_group, e // n_group)
    group_score = jnp.sum(-jnp.sort(-by_group, axis=-1)[..., :2], axis=-1)
    best = _largest(group_score, topk_group)
    kept = jnp.any(best[..., :, None] == jnp.arange(n_group), axis=-2)
    idx = _largest(jnp.where(jnp.repeat(kept, e // n_group, axis=-1),
                             select, -jnp.inf), k)
    top = jnp.take_along_axis(select if bias_in_weights else s, idx,
                              axis=-1)
    if norm:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * scale, kept


@jax.jit
def _expert_part(gate, up, down, x, weight):
    """One expert's part for every token: ``weight`` [b, t] is the
    token's gate for it, 0 where it was not chosen."""
    return _swiglu({"gate": {"w": gate}, "up": {"w": up},
                    "down": {"w": down}}, x) * weight[..., None]


_swiglu_jit = jax.jit(_swiglu)


def _pick(leaf, layer):
    """One layer's slice of a stacked leaf (``layer`` None: the leaf is
    a single layer's already)."""
    return leaf if layer is None else leaf[layer]


def _swiglu_of(p, x, layer, cols=slice(None)):
    """SwiGLU ``p`` (of ``layer``) over the hidden columns ``cols``."""
    return _swiglu_jit(
        {"gate": {"w": _pick(p["gate"]["w"], layer)[:, cols]},
         "up": {"w": _pick(p["up"]["w"], layer)[:, cols]},
         "down": {"w": _pick(p["down"]["w"], layer)[cols]}}, x)


def route(p, x, config, *, layer=None, bias_in_weights=False):
    """The router of one layer alone: (chosen experts, weights, kept
    groups) for x [b, t, d]."""
    return _route(
        _pick(p["router"]["w"], layer),
        _pick(p["router"]["e_score_correction_bias"], layer), x,
        config["num_experts_per_tok"],
        float(config["routed_scaling_factor"]),
        bool(config["norm_topk_prob"]), int(config["n_group"]),
        int(config["topk_group"]), bias_in_weights)


def moe(p, x, config, *, experts_held=None, layer=None, shared=True,
        bias_in_weights=False):
    """The mixture of one layer: ``p`` that layer's ``moe`` node, or the
    stacked node and ``layer``: x [b, t, d] -> (y, chosen experts
    [b, t, k]). ``experts_held`` (first, count) overrides the
    configuration's share; the experts in ``p`` from its first on are
    those. ``shared=False`` leaves the shared expert out (the routed
    part alone); ``bias_in_weights`` takes the weights from ``s + b``
    (a control: the published router takes them from ``s``)."""
    first, held = experts_held if experts_held is not None else (
        config.get("experts_first", 0), config["num_experts"])
    idx, w, _ = route(p, x, config, layer=layer,
                      bias_in_weights=bias_in_weights)
    y = _swiglu_of(p["shared"], x, layer) if shared else jnp.zeros_like(x)
    e = p["experts"]
    for j in range(held):                       # a loop and a mask
        weight = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        y = y + _expert_part(*(_pick(e[n]["w"], layer)[j]
                               for n in ("gate", "up", "down")), x, weight)
    return y, idx


def _dense_mlp(p, x, layer, block: int = 2048):
    """The dense SwiGLU, its hidden columns a block at a time (the sum
    over hidden units splits)."""
    hidden = p["gate"]["w"].shape[-1]
    return sum(_swiglu_of(p, x, layer, slice(lo, lo + block))
               for lo in range(0, hidden, block))


_KDA_KEYS = ("num_attention_heads", "head_dim", "short_conv_kernel_size",
             "rms_norm_eps", "kda_lower_bound")
_MLA_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
             "kv_lora_rank", "v_head_dim", "rms_norm_eps", "rope_theta")


@partial(jax.jit, static_argnames=("eps",))
def _normed(scale, h, eps):
    return _rms(scale, h, eps)


@partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, w_out, h, eps):
    return _mm(_rms(ln_f["scale"], h, eps), w_out)


def latent_rows(p, x, config, *, layer=0):
    """The rows ``[c | k_rope]`` [B, T, rank + rope] the latent layer
    ``layer`` of the stack ``p`` (``blocks.mla``) would cache for the
    residual stream ``x`` [B, T, D] at positions 0..T-1: its own norm,
    then :func:`_mla`'s rows."""
    one = jax.tree.map(lambda a: a[layer], p)
    return _mla(one["attn"],
                _normed(one["ln1"]["scale"], x, eps=config["rms_norm_eps"]),
                cfg=tuple((k, config[k]) for k in _MLA_KEYS))[1]


def forward(params, ids, config, *, positions=None, state_at=None,
            routed: bool = True, bias_in_weights: bool = False,
            latent_rows: bool = False):
    """``ids`` [B, T] int32 -> (logits float32 at every position or at
    ``positions`` (a list of indices); the chosen experts of every MoE
    layer [L_moe, B, T, k]; the FIRST KDA layer's state [B, H, dk, dv]
    after each row's position ``state_at`` [B] (default: the last)).
    ``config`` is the configuration file's dict (the Hugging Face keys
    and the share: ``num_experts`` held from ``experts_first`` on, of
    ``num_experts_published``). One layer at a time, one position at a
    time in the KDA layers, one expert at a time, in Python loops.
    ``routed=False`` leaves the routed experts' part out (the shared
    expert alone) and ``bias_in_weights`` takes the routing weights
    from ``s + b``: controls that show whether a check sees them.
    ``latent_rows`` adds a fourth result: the FIRST latent layer's rows
    ``[c | k_rope]`` [B, T, rank + rope], what its cache would hold."""
    eps = config["rms_norm_eps"]
    kda_cfg = tuple((k, config[k]) for k in _KDA_KEYS)
    mla_cfg = tuple((k, config[k]) for k in _MLA_KEYS)
    group, n_dense = config["layer_group_size"], config[
        "first_k_dense_replace"]
    ids = jnp.asarray(ids)
    at = jnp.asarray(state_at if state_at is not None
                     else [ids.shape[1] - 1] * ids.shape[0], jnp.int32)
    h = _f32(params["embedding"]["tok"][ids])
    blocks = params["blocks"]
    chosen, first_state, first_rows = [], None, None
    for i in range(config["num_hidden_layers"]):
        if (i + 1) % group:
            ki = i - i // group                   # index among KDA layers
            p = jax.tree.map(lambda a: a[ki], blocks["kda"])
            y, state = _kda(p["mixer"], _normed(p["ln1"]["scale"], h,
                                                eps=eps), at, cfg=kda_cfg)
            if first_state is None:
                first_state = state
        else:
            p = jax.tree.map(lambda a: a[i // group], blocks["mla"])
            y, rows = _mla(p["attn"], _normed(p["ln1"]["scale"], h,
                                              eps=eps), cfg=mla_cfg)
            if first_rows is None:
                first_rows = rows
        del p
        h = h + y
        if i < n_dense:
            stack = blocks["dense"]
            u = _normed(stack["ln2"]["scale"][i], h, eps=eps)
            h = h + _dense_mlp(stack["mlp"], u, i)
        else:
            stack, layer = blocks["moe"], i - n_dense
            u = _normed(stack["ln2"]["scale"][layer], h, eps=eps)
            m, idx = moe(stack["moe"], u, config, layer=layer,
                         experts_held=None if routed else (0, 0),
                         bias_in_weights=bias_in_weights)
            chosen.append(idx)
            h = h + m
    if positions is not None:
        h = h[:, jnp.asarray(positions)]
    out = (_head(params["head"]["ln_f"], params["head"]["out"]["w"], h,
                 eps=eps), jnp.stack(chosen), first_state)
    return (*out, first_rows) if latent_rows else out
