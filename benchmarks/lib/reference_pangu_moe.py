"""openPangu-Ultra-MoE (``pangu_ultra_moe``), plainly: the forward pass
in ``jax.numpy``, float32, every matmul at ``precision="highest"`` — no
cache, no paging, no absorption of ``W_uk``/``W_uv``, no sorting and no
grouped matmul: the experts are a Python loop with a mask. Nothing is
imported from ``quintnet_tpu``.

Written from the published description (the model's ``config.json``;
the DeepSeek-V2/V3 papers for latent attention and the sigmoid router,
which this family follows). With ``h`` the residual stream and every
norm an RMSNorm with ``rms_norm_eps``::

    a = MLA(N1(h));  h = h + N2(a)          sandwich_norm: four norms
    m = FFN(N3(h));  h = h + N4(m)          a layer
    logits = Nf(h) W_head

``FFN``: SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers; after them ``s = sigmoid(x W_g)`` over
the router's ``n_routed_experts_published`` experts, the
``num_experts_per_tok`` largest, ``w = routed_scaling_factor * s /
sum(s chosen)`` (``norm_topk_prob``), ``y = SwiGLU_shared(x) + sum over
the chosen experts e HELD HERE of w_e SwiGLU_e(x)``: the experts held
are ``[experts_first, experts_first + n_routed_experts)``, a routing to
any other adds nothing (it is another chip's part of the sum).

MLA: ``cq = Nq(x W_dq)``; ``q = cq W_uq`` -> heads of ``[q_nope |
q_rope]``; ``[ckv | k_r] = x W_dkv``; ``c = Nkv(ckv)``; ``k_rope =
RoPE(k_r)`` shared by all heads, ``q_rope = RoPE(q_rope)`` (theta
``rope_theta``, plain frequencies, lanes i and i + d/2 a pair);
``[k_nope | v] = c W_ukv`` per head; scores ``(q_nope . k_nope + q_rope
. k_rope) / sqrt(nope + rope)``, causal softmax, ``o = concat_h(P v)
W_o``.

The parameter TREE is the program's (the same weights are compared):
``embedding.tok`` [V, D]; ``blocks.dense`` and ``blocks.moe``, each with
a leading axis over its layers: ``{ln1,ln1_post,ln2,ln2_post}.scale``,
``attn.{q_down,q_up,kv_down,kv_up,o}.w``, ``attn.{q_norm,kv_norm}.scale``
and ``mlp.{gate,up,down}.w`` or ``moe.router.w`` [D, E],
``moe.experts.{gate,up,down}.w`` [held, in, out],
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


@partial(jax.jit, static_argnames=("cfg",))
def _mla(p, x, cfg):
    c = dict(cfg)
    b, t, _ = x.shape
    h = c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, vd, eps = c["kv_lora_rank"], c["v_head_dim"], c["rms_norm_eps"]
    pos = jnp.arange(t)
    cq = _rms(p["q_norm"]["scale"], _mm(x, p["q_down"]["w"]), eps)
    q = _mm(cq, p["q_up"]["w"]).reshape(b, t, h, nope + rope).transpose(
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
                   precision=HI)
    return _mm(o.transpose(0, 2, 1, 3).reshape(b, t, h * vd), p["o"]["w"])


@partial(jax.jit, static_argnames=("k", "scale", "norm"))
def _route(w_router, x, k, scale, norm):
    """-> (chosen experts [b, t, k], their weights [b, t, k])."""
    s = jax.nn.sigmoid(_mm(x, w_router))
    top, idx = jax.lax.top_k(s, k)
    if norm:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * scale


@jax.jit
def _expert_part(gate, up, down, x, weight):
    """One expert's part for every token: ``weight`` [b, t] is the
    token's gate for it, 0 where it was not chosen."""
    return _swiglu({"gate": {"w": gate}, "up": {"w": up},
                    "down": {"w": down}}, x) * weight[..., None]


_swiglu_jit = jax.jit(_swiglu)


def _pick(leaf, layer):
    """One layer's slice of a stacked leaf (``layer`` None: the leaf is
    a single layer's already). Leaves are picked one at a time, where
    they are used: a whole layer of the published model at once is two
    gigabytes beside the engine."""
    return leaf if layer is None else leaf[layer]


def _swiglu_of(p, x, layer, cols=slice(None)):
    """SwiGLU ``p`` (of ``layer``) over the hidden columns ``cols``."""
    return _swiglu_jit(
        {"gate": {"w": _pick(p["gate"]["w"], layer)[:, cols]},
         "up": {"w": _pick(p["up"]["w"], layer)[:, cols]},
         "down": {"w": _pick(p["down"]["w"], layer)[cols]}}, x)


def moe(p, x, config, *, experts_held=None, layer=None):
    """The mixture of one layer: ``p`` that layer's ``moe`` node, or the
    stacked node and ``layer``: x [b, t, d] -> (y, chosen experts
    [b, t, k]). ``experts_held`` (first, count) overrides the
    configuration's share; the experts in ``p`` are those."""
    first, held = experts_held if experts_held is not None else (
        config.get("experts_first", 0), config["n_routed_experts"])
    idx, w = _route(_pick(p["router"]["w"], layer), x,
                    config["num_experts_per_tok"],
                    float(config["routed_scaling_factor"]),
                    bool(config["norm_topk_prob"]))
    y = _swiglu_of(p["shared"], x, layer)
    e = p["experts"]
    for j in range(held):                       # a loop and a mask
        weight = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        y = y + _expert_part(*(_pick(e[n]["w"], layer)[j]
                               for n in ("gate", "up", "down")), x, weight)
    return y, idx


def _dense_mlp(p, x, layer, block: int = 4096):
    """The dense SwiGLU, its hidden columns a block at a time (the sum
    over hidden units splits): at the published 18,432 the f32 copies
    of all three matrices at once would be 1.7 GB beside the engine."""
    hidden = p["gate"]["w"].shape[-1]
    return sum(_swiglu_of(p, x, layer, slice(lo, lo + block))
               for lo in range(0, hidden, block))


_MLA_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
             "kv_lora_rank", "v_head_dim", "rms_norm_eps", "rope_theta")


@partial(jax.jit, static_argnames=("eps",))
def _add_normed(scale, h, y, eps):
    return h + _rms(scale, y, eps)


@partial(jax.jit, static_argnames=("eps",))
def _normed(scale, h, eps):
    return _rms(scale, h, eps)


@partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, w_out, h, eps):
    return _mm(_rms(ln_f["scale"], h, eps), w_out)


def forward(params, ids, config, *, positions=None, routed: bool = True):
    """``ids`` [B, T] int32 -> (logits float32 at every position or at
    ``positions`` (a list of indices), the chosen experts of every MoE
    layer [L_moe, B, T, k]). ``config`` is the configuration file's
    dict (the Hugging Face keys and the share: ``n_routed_experts``
    held from ``experts_first`` on, of ``n_routed_experts_published``).
    One layer at a time, one expert at a time, in a Python loop.
    ``routed=False`` leaves the routed experts' part out (the shared
    expert alone): the control that shows whether a check sees them."""
    eps = config["rms_norm_eps"]
    mla_cfg = tuple((k, config[k]) for k in _MLA_KEYS)
    h = _f32(params["embedding"]["tok"][ids])
    chosen = []
    n_dense = config["first_k_dense_replace"]
    for i in range(config["num_hidden_layers"]):
        dense = i < n_dense
        stack = params["blocks"]["dense" if dense else "moe"]
        layer = i if dense else i - n_dense

        def scale(name):
            return stack[name]["scale"][layer]

        attn = jax.tree.map(lambda a: a[layer], stack["attn"])
        a = _mla(attn, _normed(scale("ln1"), h, eps=eps), cfg=mla_cfg)
        del attn
        h = _add_normed(scale("ln1_post"), h, a, eps=eps)
        u = _normed(scale("ln2"), h, eps=eps)
        if dense:
            m = _dense_mlp(stack["mlp"], u, layer)
        else:
            m, idx = moe(stack["moe"], u, config, layer=layer,
                         experts_held=None if routed else (0, 0))
            chosen.append(idx)
        h = _add_normed(scale("ln2_post"), h, m, eps=eps)
    if positions is not None:
        h = h[:, jnp.asarray(positions)]
    return (_head(params["head"]["ln_f"], params["head"]["out"]["w"], h,
                  eps=eps), jnp.stack(chosen))
