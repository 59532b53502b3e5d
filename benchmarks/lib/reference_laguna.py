"""Laguna (``model_type`` ``laguna``), plainly: the forward pass in
``jax.numpy``, float32, every matmul at ``precision="highest"`` — no
cache, no paging, no ring, no sorting and no grouped matmul: every
layer scores a full ``[T, T]`` mask, the experts are a Python loop with
a mask. Nothing is imported from ``quintnet_tpu``.

Written from the published description (the model's ``config.json`` as
the catalog holds it). With ``x`` the residual stream, every norm an
RMSNorm with ``rms_norm_eps``, layer ``l`` of kind ``layer_types[l]``
with ``H = num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` kv heads of ``d = head_dim``::

    h = N1(x);  q = h W_q [H, d];  k = h W_k, v = h W_v [H_kv, d]
    q, k = RoPE(q), RoPE(k)        rope_parameters[layer_types[l]]
    P = softmax(q k^T / sqrt(d)) over keys j <= i and, on a
        sliding_attention layer, j > i - sliding_window
    o_h = sigmoid(h W_g)_h (P_h v)                 gating
    x = x + concat(o) W_o
    u = N2(x);  x = x + FFN(u);    logits = Nf(x) W_head

RoPE, lanes i and i + rot/2 of the first ``rot = partial_rotary_factor
* d`` features a pair (``x_i cos - x_{i + rot/2} sin``, ``x_{i + rot/2}
cos + x_i sin``), the rest unrotated. ``default``: ``inv_freq_i =
theta^(-2i / rot)``. ``yarn``: ``extrap_i = theta^(-2i / rot)``,
``interp_i = extrap_i / factor``, ``c(b) = rot ln(original_max / (2 pi
b)) / (2 ln theta)``, ``low = max(floor(c(beta_fast)), 0)``, ``high =
min(ceil(c(beta_slow)), rot - 1)``, ``ramp_i = clip((i - low) / (high -
low), 0, 1)``, ``inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i)``,
and cos and sin are multiplied by ``attention_factor``.

``FFN``: SwiGLU of ``intermediate_size`` on a ``dense`` layer
(``mlp_layer_types``); on a ``sparse`` one ``s = sigmoid(u W_r)`` over
all ``num_experts``, the ``num_experts_per_tok`` largest, ``w =
moe_routed_scaling_factor * s / sum(s chosen)``, ``y = SwiGLU_shared(u)
+ sum over the chosen e of w_e SwiGLU_e(u)`` (the weight on the
expert's OUTPUT: ``moe_apply_router_weight_on_input`` false).

The parameter TREE is the program's (the same weights are compared):
``embedding.tok`` [V, D]; ``blocks.<attn>_<mlp>`` for ``attn`` in
full/sliding and ``mlp`` in dense/sparse, each with a leading axis over
ITS layers in model order: ``{ln1,ln2}.scale``,
``attn.{q,k,v,o,gate}.w`` and ``mlp.{gate,up,down}.w`` or
``moe.router.w`` [D, E], ``moe.shared.{gate,up,down}.w``;
``blocks.experts.{gate,up,down}.w`` [L_sparse, E, in, out] over ALL the
sparse layers; ``head.ln_f.scale``, ``head.out.w`` [D, V]. Leaves may be
stored in bf16: a layer's are cast up as it is used, the experts one at
a time.

``controls`` (a set of names) each leave ONE piece of the mathematics
out or wrong; the cell's check must refuse every one of them
(benchmarks/tools/window_moe_probe.py): ``window_off`` (sliding layers
see every earlier key), ``rope_swapped`` (each kind rotates with the
other's setting), ``no_gate`` (``o_h`` ungated), ``no_attention_factor``
(the yarn tables unscaled).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
CONTROLS = ("window_off", "rope_swapped", "no_gate", "no_attention_factor")
_KIND = {"full_attention": "full", "sliding_attention": "sliding"}


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


def rope_tables(setting, head_dim: int, t: int, *, scaled: bool = True):
    """(cos, sin) [t, head_dim] of one ``rope_parameters`` entry (module
    docstring), the frequencies duplicated over the two halves of the
    ``rot`` rotating features; past them cos is 1 and sin 0 (the
    features pass through). Computed on the host in float64."""
    rot = int(head_dim * setting.get("partial_rotary_factor", 1))
    theta = float(setting["rope_theta"])
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    factor = 1.0
    if setting.get("rope_type", "default") == "yarn":
        def c(beta):
            return (rot * math.log(setting["original_max_position_embeddings"]
                                   / (2 * math.pi * beta))
                    / (2 * math.log(theta)))

        low = max(math.floor(c(setting["beta_fast"])), 0)
        high = min(math.ceil(c(setting["beta_slow"])), rot - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        inv = inv / setting["factor"] * ramp + inv * (1.0 - ramp)
        if scaled:
            factor = float(setting["attention_factor"])
    ang = np.arange(t, dtype=np.float64)[:, None] * inv
    cos, sin = np.ones((t, head_dim)), np.zeros((t, head_dim))
    cos[:, :rot] = np.concatenate([np.cos(ang)] * 2, axis=-1) * factor
    sin[:, :rot] = np.concatenate([np.sin(ang)] * 2, axis=-1) * factor
    return (jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32),
            jnp.asarray(_half_turn(head_dim, rot)))


def _half_turn(d: int, rot: int):
    """The [d, d] matrix that takes x to its rotary partner: ``(x P)_i =
    -x_{i + rot/2}`` for ``i < rot/2``, ``x_{i - rot/2}`` for ``rot/2 <=
    i < rot``, 0 past the rotating features. (A matrix of 0 and +-1, so
    the product is exact; slicing and concatenating halves of a
    64-of-128-lane tensor is what the chip's compiler refused.)"""
    p = np.zeros((d, d), np.float32)
    for i in range(rot // 2):
        p[i + rot // 2, i] = -1.0
        p[i, i + rot // 2] = 1.0
    return p


def _rope(x, cos, sin, turn):
    """x [..., T, d] by tables [T, d]: ``x cos + partner(x) sin``."""
    return x * cos + jnp.matmul(x, turn, precision=HI) * sin


@partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, *, window):
    """The query heads q [b, g, t, d] that share ONE kv head k, v
    [b, t, d]: softmax over the keys each query sees -> [b, g, t, d]."""
    t, d = q.shape[-2:]
    scores = jnp.einsum("bgqd,bkd->bgqk", q, k, precision=HI) / math.sqrt(d)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum("bgqk,bkd->bgqd", jax.nn.softmax(scores, axis=-1), v,
                      precision=HI)


@partial(jax.jit, static_argnames=("heads", "head_dim"))
def _projected(w, h, rope, *, heads, head_dim):
    """h W as [b, heads, t, d], rotated where tables are given."""
    b, t, _ = h.shape
    x = _mm(h, w).reshape(b, t, heads, head_dim).transpose(0, 2, 1, 3)
    return x if rope is None else _rope(x, *rope)


@partial(jax.jit, static_argnames=("gated",))
def _gated_out(p, h, o, *, gated):
    """o [b, H, t, d] -> gate, concatenate, W_o."""
    b, heads, t, d = o.shape
    o = o.transpose(0, 2, 1, 3)
    if gated:
        o = o * jax.nn.sigmoid(_mm(h, p["gate"]["w"]))[..., None]
    return _mm(o.reshape(b, t, heads * d), p["o"]["w"])


def _attention(p, h, rope, *, kv_heads, head_dim, window, gated):
    """One layer's attention on its normed input h [b, t, D]; ``window``
    None = every earlier key. Query head i reads kv head ``i // (H /
    kv_heads)``; the full ``[t, t]`` scores go one kv head's query
    heads at a time (a Python loop: all 64 heads of a 1,328-token row
    at once would be half a gigabyte beside the engine)."""
    heads = p["q"]["w"].shape[-1] // head_dim
    rep = heads // kv_heads
    q = _projected(p["q"]["w"], h, rope, heads=heads, head_dim=head_dim)
    k = _projected(p["k"]["w"], h, rope, heads=kv_heads, head_dim=head_dim)
    v = _projected(p["v"]["w"], h, None, heads=kv_heads, head_dim=head_dim)
    o = jnp.concatenate(
        [_attend(q[:, g * rep:(g + 1) * rep], k[:, g], v[:, g],
                 window=window) for g in range(kv_heads)], axis=1)
    return _gated_out(p, h, o, gated=gated)


@partial(jax.jit, static_argnames=("k", "scale"))
def _route(w_router, x, k, scale):
    """-> (chosen experts [b, t, k], their weights [b, t, k])."""
    s = jax.nn.sigmoid(_mm(x, w_router))
    top, idx = jax.lax.top_k(s, k)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True) * scale


@jax.jit
def _expert_part(gate, up, down, x, weight):
    """One expert's part for every token: ``weight`` [b, t] is the
    token's weight for it, 0 where it was not chosen."""
    return _swiglu({"gate": {"w": gate}, "up": {"w": up},
                    "down": {"w": down}}, x) * weight[..., None]


_swiglu_jit = jax.jit(_swiglu)


def _swiglu_of(p, x, layer, cols=slice(None)):
    """SwiGLU ``p`` of ``layer`` over the hidden columns ``cols``."""
    return _swiglu_jit(
        {"gate": {"w": p["gate"]["w"][layer][:, cols]},
         "up": {"w": p["up"]["w"][layer][:, cols]},
         "down": {"w": p["down"]["w"][layer][cols]}}, x)


def routed_part(experts, router_w, x, config, *, layer: int):
    """The ROUTED experts' part of sparse layer ``layer`` (its index
    among the sparse layers; ``router_w`` that layer's [D, E]) for x
    [b, t, D], without the shared expert: (y, chosen [b, t, k])."""
    idx, w = _route(router_w, x, config["num_experts_per_tok"],
                    float(config["moe_routed_scaling_factor"]))
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(config["num_experts"]):        # a loop and a mask
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        y = y + _expert_part(*(experts[n]["w"][layer, e]
                               for n in ("gate", "up", "down")), x, weight)
    return y, idx


def _dense_mlp(p, x, layer, block: int = 4096):
    """The dense SwiGLU, its hidden columns a block at a time (the sum
    over hidden units splits)."""
    hidden = p["gate"]["w"].shape[-1]
    return sum(_swiglu_of(p, x, layer, slice(lo, lo + block))
               for lo in range(0, hidden, block))


@partial(jax.jit, static_argnames=("eps",))
def _normed(scale, h, eps):
    return _rms(scale, h, eps)


@partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, w_out, h, eps):
    return _mm(_rms(ln_f["scale"], h, eps), w_out)


def forward(params, ids, config, *, positions=None, controls=()):
    """``ids`` [B, T] int32 -> (logits float32 at every position or at
    ``positions`` (a list of indices), the chosen experts of every
    sparse layer [L_sparse, B, T, k]). ``config`` is the configuration
    file's dict (the Hugging Face keys; the layers run are the first
    ``num_hidden_layers`` entries of the per-layer lists). One layer at
    a time, one expert at a time, in a Python loop. ``controls``: module
    docstring."""
    unknown = set(controls) - set(CONTROLS)
    if unknown:
        raise ValueError(f"unknown controls {sorted(unknown)}")
    eps = config["rms_norm_eps"]
    t = ids.shape[1]
    # a configuration cut in depth keeps the published lists whole
    kinds = list(config["layer_types"])[:config["num_hidden_layers"]]
    rope = {name: rope_tables(
        config["rope_parameters"][name], config["head_dim"], t,
        scaled="no_attention_factor" not in controls) for name in set(kinds)}
    if "rope_swapped" in controls:
        a, b = sorted(rope)
        rope = {a: rope[b], b: rope[a]}
    x = _f32(params["embedding"]["tok"][ids])
    chosen, seen, sparse = [], {}, 0
    for kind, mlp in zip(kinds, config["mlp_layer_types"]):
        name = f"{_KIND[kind]}_{mlp}"
        stack, layer = params["blocks"][name], seen.get(name, 0)
        seen[name] = layer + 1
        h = _normed(stack["ln1"]["scale"][layer], x, eps=eps)
        x = x + _attention(
            jax.tree.map(lambda a: a[layer], stack["attn"]), h, rope[kind],
            kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            window=(config["sliding_window"]
                    if kind == "sliding_attention"
                    and "window_off" not in controls else None),
            gated="no_gate" not in controls)
        u = _normed(stack["ln2"]["scale"][layer], x, eps=eps)
        if mlp == "dense":
            x = x + _dense_mlp(stack["mlp"], u, layer)
            continue
        y, idx = routed_part(params["blocks"]["experts"],
                             stack["moe"]["router"]["w"][layer], u, config,
                             layer=sparse)
        x = x + y + _swiglu_of(stack["moe"]["shared"], u, layer)
        chosen.append(idx)
        sparse += 1
    if positions is not None:
        x = x[:, jnp.asarray(positions)]
    return (_head(params["head"]["ln_f"], params["head"]["out"]["w"], x,
                  eps=eps), jnp.stack(chosen))
