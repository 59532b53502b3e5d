"""Mixture-of-Experts layer with expert parallelism over the ``ep`` axis.

The reference lists EP/MoE as absent (SURVEY.md §2.2: "EP / expert
parallel (MoE) — Absent"; the package docstring's "Towards 5D
Parallelism", reference __init__.py:2, never materialises). Here expert
parallelism is a first-class mesh axis, built the TPU way:

- **Routing** is dense math on the MXU: top-k gate over a [S, E] router
  matmul, capacity-bounded dispatch with static shapes (XLA-friendly: no
  dynamic shapes, drops are masked writes to a dump row, not ragged
  tensors).
- **Dispatch/combine** are scatter-adds into a [E*C, D] buffer (O(S*k*D)
  work) rather than the O(S^2)-memory one-hot dispatch einsum.
- **Expert exchange** is one ``lax.all_to_all`` over ``ep`` each way —
  the same collective family as Ulysses (ops/ulysses_attention.py), so
  it rides ICI on a TPU slice. Each device owns E/ep experts and
  processes ep*C rows per expert per step.
- **TP composes**: expert FFN weights may additionally be column/row
  sharded over ``tp`` (w1 on hidden-out, w2 on hidden-in, one psum).

Gradient semantics (parallel/train_step.py): ``ep`` acts as a *data*
axis — tokens are sharded over it — while expert weights are *sharded*
over it. The all_to_all transpose delivers each expert's grad already
summed over every source rank, so reduce_grads divides ep-sharded leaves
by ep instead of pmeaning them.

Load-balance auxiliary loss follows the Switch-Transformer form
(E * sum_e f_e * P_e over the k assignments) computed on the device-local
token batch, plus an optional router z-loss.

TWO ROUTERS, ONE LAYER. The path above (softmax gate, a per-expert
capacity, drops) serves the tiny MoE families and training. ``MoEArgs(
dropless=True)`` is the other (:func:`_moe_dropless`; inference only,
no ``ep``/``tp`` axis yet): ``scoring`` softmax or sigmoid over the FULL
router, the ``top_k`` largest, gates normalised over the chosen and
scaled by ``routed_scale``, and NO capacity — the assignments are
sorted by expert and the experts run as one grouped matmul
(``lax.ragged_dot``) over rows that are exactly the routings, so
nothing is dropped at any skew. ``experts_held = (first, count)`` tells
the layer which experts of the router's range it holds (expert
parallelism's share of one chip): it routes over all of them and
computes the part of the result its own give; a routing to an absent
expert adds nothing here. ``n_group`` / ``topk_group`` add DeepSeek-V3's
group-limited selection (``noaux_tc``): the experts are ``n_group``
runs of consecutive ones, a group's score is the sum of its two largest
SELECTION scores, only the ``topk_group`` best groups stay eligible and
the ``top_k`` largest selection scores inside them are chosen. The
selection score is ``s + b`` where the router's params carry a bias
(``router.e_score_correction_bias`` [E]); the gates are always the
UNBIASED ``s`` of the chosen. A ``shared`` SwiGLU in the params is added
once, for every token. There the expert weights are linear NODES
(``experts.{gate,up,down}.w`` [held, in, out]) that a weight layout
policy packs like any other matmul (serve/weight_quant.py).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from quintnet_tpu.core import collectives as cc
from quintnet_tpu.nn.layers import gelu, linear_init, swiglu_apply, swiglu_init


class MoEArgs(NamedTuple):
    """Static MoE hyperparameters (trace-time constants)."""

    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    capacity: Optional[int] = None  # explicit per-rank per-expert override
    aux_weight: float = 1e-2
    z_weight: float = 0.0
    normalize_gates: bool = True
    # "topk": tokens choose experts (Switch/Mixtral; needs the aux
    # load-balance loss, may drop tokens at capacity).
    # "expert_choice": experts choose their top-C tokens (Zhou et al.
    # 2022) — perfectly load-balanced by construction, no aux loss, no
    # drops (a token may instead be served by 0..E experts; the
    # residual path covers unserved tokens). NON-CAUSAL: selection runs
    # over the whole flattened [B*T] token set, so position t's output
    # depends on later positions — fine for encoders (ViT-MoE etc.),
    # WRONG for autoregressive LMs (the causal model configs reject it;
    # GPT2Config/LlamaConfig.moe_args).
    router: str = "topk"
    # the dropless router (module docstring): no capacity, a grouped
    # matmul over the experts held. ``scoring``: "softmax" | "sigmoid"
    # (sigmoid needs dropless: the capacity path's auxiliary loss is
    # the softmax's). ``routed_scale`` multiplies the normalised gates.
    # ``experts_held``: (first, count) of the router's experts whose
    # weights this layer has; None = all of them.
    dropless: bool = False
    scoring: str = "softmax"
    routed_scale: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None
    # group-limited selection (module docstring); 0 = every expert is
    # eligible for every token
    n_group: int = 0
    topk_group: int = 0


def moe_init(key, dim: int, hidden: int, n_experts: int, *,
             dtype=jnp.float32, expert_type: str = "mlp"):
    """Router + per-expert FFN params with GLOBAL expert dim E leading.

    ``expert_type``: "mlp" (fc->act->proj with biases, GPT-2/ViT style)
    or "swiglu" (gate/up/down, no biases — Llama/Mixtral style).
    Expert weights follow the same fan-in uniform init as
    nn/layers.py:linear_init so a 1-expert MoE matches a dense
    MLP/SwiGLU's statistics."""
    kr, kw1, kb1, kw2, kb2 = jax.random.split(key, 5)
    s1 = 1.0 / math.sqrt(dim)
    s2 = 1.0 / math.sqrt(hidden)

    def u(k, shape, s):
        return jax.random.uniform(k, shape, dtype, minval=-s, maxval=s)

    # router kept/computed in f32: tiny, and gate ordering is
    # precision-sensitive (cast_floating exempts it — layers.py)
    router = {"w": u(kr, (dim, n_experts), s1).astype(jnp.float32)}
    if expert_type == "swiglu":
        return {
            "router": router,
            "wg": u(kw1, (n_experts, dim, hidden), s1),
            "wu": u(kb1, (n_experts, dim, hidden), s1),
            "wd": u(kw2, (n_experts, hidden, dim), s2),
        }
    return {
        "router": router,
        "w1": u(kw1, (n_experts, dim, hidden), s1),
        "b1": u(kb1, (n_experts, hidden), s1),
        "w2": u(kw2, (n_experts, hidden, dim), s2),
        "b2": u(kb2, (n_experts, dim), s2),
    }


def moe_held_init(key, dim: int, hidden: int, n_experts: int, *,
                  held: int, shared_hidden: int = 0, dtype=jnp.float32,
                  selection_bias: bool = False):
    """Params of the dropless layer (module docstring): a router over
    all ``n_experts``, SwiGLU weights of the ``held`` experts this
    layer has as linear nodes ``experts.{gate,up,down}.w`` [held, in,
    out] (fan-in uniform like :func:`moe_init`), and a ``shared``
    SwiGLU of width ``shared_hidden`` where that is not 0.
    ``selection_bias`` adds ``router.e_score_correction_bias`` [E] f32,
    normal(0, 0.01)."""
    kr, kg, ku, kd, ks = jax.random.split(key, 5)

    def stack(k, fin, fout):
        return {"w": jnp.stack([
            linear_init(kk, fin, fout, use_bias=False, dtype=dtype)["w"]
            for kk in jax.random.split(k, held)])}

    p = {"router": {"w": linear_init(kr, dim, n_experts, use_bias=False,
                                     dtype=jnp.float32)["w"]},
         "experts": {"gate": stack(kg, dim, hidden),
                     "up": stack(ku, dim, hidden),
                     "down": stack(kd, hidden, dim)}}
    if shared_hidden:
        p["shared"] = swiglu_init(ks, dim, shared_hidden, dtype=dtype)
    if selection_bias:
        p["router"]["e_score_correction_bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(kr, 1), (n_experts,), jnp.float32)
    return p


def moe_specs(*, ep_axis: Optional[str] = "ep",
              tp_axis: Optional[str] = None,
              stacked: bool = False, pp_axis: Optional[str] = None,
              expert_type: str = "mlp"):
    """PartitionSpecs: experts sharded over ``ep``; inside each expert the
    FFN is column/row sharded over ``tp`` (parallel/tp.py convention);
    router replicated."""

    def lead(*tail):
        return P(pp_axis, *tail) if stacked else P(*tail)

    if expert_type == "swiglu":
        return {
            "router": {"w": lead(None, None)},
            "wg": lead(ep_axis, None, tp_axis),
            "wu": lead(ep_axis, None, tp_axis),
            "wd": lead(ep_axis, tp_axis, None),
        }
    return {
        "router": {"w": lead(None, None)},
        "w1": lead(ep_axis, None, tp_axis),
        "b1": lead(ep_axis, tp_axis),
        "w2": lead(ep_axis, tp_axis, None),
        "b2": lead(ep_axis, None),
    }


def _capacity(s_local: int, args: MoEArgs) -> int:
    if args.capacity is not None:
        return int(args.capacity)
    c = math.ceil(s_local * args.top_k / args.n_experts
                  * args.capacity_factor)
    return max(int(c), 1)


def moe_apply(p, x, args: MoEArgs, *, ep_axis: Optional[str] = None,
              tp_axis: Optional[str] = None, act=gelu,
              return_stats: bool = False, token_mask=None,
              expert_layer=None):
    """x: [B, T_local, D] -> (y, aux_loss[, stats]).

    All shapes static: S = B*T local tokens, E experts, per-rank
    per-expert capacity C. Tokens routed beyond capacity are dropped
    (identity residual path in the transformer block keeps them alive).

    ``return_stats`` adds a routing-stats dict (all f32, computed from
    the replicated routing math so every ep/tp rank holds identical
    values): ``expert_tokens`` [E] — routed assignment demand per
    expert BEFORE the capacity cut (the honest skew signal: post-cut
    loads saturate at C under a hot expert); ``dropped`` — assignments
    past capacity (masked into the dump row); ``assigned`` — total
    assignments S*k; ``entropy`` — mean per-token router entropy in
    nats. The serving engine ships these to ServeMetrics per step.

    ``token_mask`` [B, T] bool (dropless router only): tokens that are
    padding — a bucket's pad columns, an empty slot's row. They are
    routed nowhere: no expert row, no count in the stats.
    ``expert_layer`` (dropless router only): the expert weights in ``p``
    are a whole STACK's, ``[layers, held, in, out]``, and this index
    picks the layer (:func:`_moe_dropless` says why).
    """
    B, T, D = x.shape
    S = B * T
    E = args.n_experts
    k = args.top_k
    if not 1 <= k <= E:
        raise ValueError(
            f"top_k={k} must be in [1, n_experts={E}]")
    if args.dropless:
        if ep_axis is not None or tp_axis is not None:
            raise NotImplementedError(
                "the dropless router has no exchange over an ep axis "
                "and no tp sharding yet (ROADMAP M1)")
        return _moe_dropless(p, x, args, return_stats=return_stats,
                             token_mask=token_mask,
                             expert_layer=expert_layer)
    if (args.scoring != "softmax" or args.experts_held is not None
            or token_mask is not None or expert_layer is not None
            or args.n_group):
        raise NotImplementedError(
            "sigmoid scoring, experts_held, n_group, token_mask and "
            "expert_layer belong to the dropless router: pass "
            "MoEArgs(dropless=True)")
    ep = 1 if ep_axis is None else lax.axis_size(ep_axis)
    if E % ep != 0:
        raise ValueError(f"n_experts={E} must divide by ep={ep}")
    C = _capacity(S, args)

    xt = x.reshape(S, D)

    # ---- routing (f32) ---------------------------------------------------
    logits = jnp.dot(xt.astype(jnp.float32), p["router"]["w"])  # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)

    if args.router == "expert_choice":
        return _moe_expert_choice(p, xt, probs, logits, (B, T, D), C,
                                  args, ep_axis=ep_axis, tp_axis=tp_axis,
                                  act=act, return_stats=return_stats)

    gate_v, gate_i = lax.top_k(probs, k)  # [S, k]
    if args.normalize_gates:
        gate_v = gate_v / jnp.sum(gate_v, axis=-1, keepdims=True)

    # k-major priority flatten: every token's 1st choice outranks any 2nd
    idx_f = gate_i.T.reshape(-1)                     # [k*S]
    val_f = gate_v.T.reshape(-1)
    s_of = jnp.tile(jnp.arange(S), k)

    oh = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)   # [k*S, E]
    pos_in_e = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=-1)
    keep = pos_in_e < C
    slot = jnp.where(keep, idx_f * C + pos_in_e, E * C)  # E*C = dump row

    # ---- dispatch: scatter into [E, C, D] --------------------------------
    buf = jnp.zeros((E * C + 1, D), x.dtype).at[slot].add(xt[s_of])
    xe = buf[: E * C].reshape(E, C, D)

    if ep_axis is not None:
        # send expert block e to its owner; receive my experts' rows from
        # every source rank: [E, C, D] -> [E/ep, ep*C, D]
        xe = cc.all_to_all(xe, ep_axis, split_dim=0, concat_dim=1)

    # ---- expert FFN (batched einsum -> MXU) ------------------------------
    y = _expert_ffn(p, xe, act=act, tp_axis=tp_axis)

    if ep_axis is not None:
        # route outputs back to the token-owning ranks
        y = cc.all_to_all(y, ep_axis, split_dim=1, concat_dim=0)  # [E, C, D]

    # ---- combine: gather + gate-weight + scatter back to tokens ----------
    ybuf = jnp.concatenate(
        [y.reshape(E * C, D), jnp.zeros((1, D), y.dtype)], axis=0)
    yc = ybuf[slot] * val_f.astype(y.dtype)[:, None]
    yt = jnp.zeros((S, D), y.dtype).at[s_of].add(yc)

    # ---- aux losses (device-local stats, f32) ----------------------------
    f_e = jnp.sum(oh, axis=0).astype(jnp.float32) / (S * k)   # [E]
    p_e = jnp.mean(probs, axis=0)                             # [E]
    aux = args.aux_weight * E * jnp.sum(f_e * p_e)
    if args.z_weight:
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        aux = aux + args.z_weight * jnp.mean(jnp.square(z))

    y_out = yt.reshape(B, T, D)
    if return_stats:
        return y_out, aux, _routing_stats(oh, keep, probs, S * k)
    return y_out, aux


def _moe_dropless(p, x, args: MoEArgs, *, return_stats: bool,
                  token_mask=None, expert_layer=None):
    """The dropless router of :func:`moe_apply` (module docstring): x
    [B, T, D] -> (y, 0[, stats]). ``S * k`` assignments, sorted so that
    the held experts' rows come first in expert order; a grouped matmul
    runs each expert over exactly its rows (the rows routed elsewhere
    trail behind every group and are skipped: ``lax.ragged_dot``, or on
    the TPU a kernel that reads only the touched experts,
    :func:`_expert_rows`), and the gate-weighted results are added back
    to their tokens. The stats keep the capacity
    path's names — ``expert_tokens`` over ALL the router's experts,
    ``dropped`` 0 by construction — and add ``held_rows`` (routings
    that landed on held experts), ``touched`` (held experts with at
    least one row) and ``elsewhere`` (routings to absent experts).
    Scopes ``router`` / ``sort`` / ``experts`` / ``shared`` /
    ``combine``.

    With ``expert_layer`` the expert weights are a whole stack's,
    ``[layers, held, in, out]``, and the grouped matmul reads this
    layer's experts out of it in place (:func:`_expert_rows`). The
    grouped matmul is one call on a whole operand, so a layer's slice
    of the stack (what a layer scan hands its body) would be COPIED out
    first — at the published widths 1.5 GB a layer a step, 21 of a
    106-ms decode step (my chip run, PR 31). The stats also carry
    ``tile_visits`` (:func:`_expert_rows`)."""
    B, T, D = x.shape
    S, E, k = B * T, args.n_experts, args.top_k
    first, held = (0, E) if args.experts_held is None else args.experts_held
    w_gate, w_up, w_down = (p["experts"][n]["w"]
                            for n in ("gate", "up", "down"))
    if w_gate.shape[-3] != held or not 0 <= first <= E - held:
        raise ValueError(
            f"experts_held={args.experts_held} of {E} experts does not "
            f"match the {w_gate.shape[-3]} experts in the params")
    if (expert_layer is None) != (w_gate.ndim == 3):
        raise ValueError(
            "expert weights [held, in, out] take no expert_layer; a "
            "stack's [layers, held, in, out] needs one")
    xt = x.reshape(S, D)

    with jax.named_scope("router"):
        logits = jnp.dot(xt.astype(jnp.float32), p["router"]["w"])
        if args.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        elif args.scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(f"unknown scoring {args.scoring!r}")
        bias = p["router"].get("e_score_correction_bias")
        kept_groups = None
        if bias is None and not args.n_group:
            gate_v, gate_i = lax.top_k(scores, k)              # [S, k]
        else:
            select = scores if bias is None else scores + bias
            if args.n_group:
                select, kept_groups = _group_limited(select, args)
            _, gate_i = lax.top_k(select, k)
            # the gates are the unbiased scores of the chosen
            gate_v = jnp.take_along_axis(scores, gate_i, axis=-1)
        if args.normalize_gates:
            gate_v = gate_v / jnp.sum(gate_v, axis=-1, keepdims=True)
        gate_v = gate_v * args.routed_scale

    with jax.named_scope("sort"):
        local = gate_i.reshape(-1) - first                     # [S * k]
        live = (jnp.ones((S * k,), bool) if token_mask is None
                else jnp.repeat(token_mask.reshape(S), k))
        here = (local >= 0) & (local < held) & live
        # absent experts sort behind every held one, into no group
        order = jnp.argsort(jnp.where(here, local, held), stable=True)
        tok = order // k
        # live routings by expert, over the whole router; the held
        # experts' are the groups' sizes
        counts = jnp.sum(jax.nn.one_hot(
            jnp.where(live, gate_i.reshape(-1), E), E + 1,
            dtype=jnp.int32), axis=0)[:E]
        sizes = counts[first:first + held]
        gates = jnp.where(here, gate_v.reshape(-1), 0.0)[order]
        in_group = here[order]      # rows that belong to a group

    with jax.named_scope("experts"):
        # the small operand goes down to the weights' dtype, as the
        # matrix unit would round it anyway; the sums stay f32
        xs = xt.astype(w_gate.dtype)[tok] if (
            w_gate.dtype.itemsize < xt.dtype.itemsize) else xt[tok]
        ys, tile_visits = _expert_rows(
            xs, (w_gate, w_up, w_down), sizes, expert_layer)   # [S*k, D]

    with jax.named_scope("combine"):
        # rows past every group are whatever the grouped matmul left
        # there (not zeros, on the chip): selected out, not scaled out
        yt = jnp.zeros((S, D), jnp.float32).at[tok].add(
            jnp.where(in_group[:, None], ys * gates[:, None], 0.0))
    if "shared" in p:
        with jax.named_scope("shared"):
            yt = yt + swiglu_apply(p["shared"], xt)
    y_out = yt.astype(x.dtype).reshape(B, T, D)
    aux = jnp.zeros((), jnp.float32)
    if not return_stats:
        return y_out, aux
    pr = scores / jnp.sum(scores, axis=-1, keepdims=True)
    grouped_stats = {}
    if kept_groups is not None:
        # live tokens none of whose kept groups has an expert held
        # here: they get the shared expert alone from this layer
        size = E // args.n_group
        group_held = ((jnp.arange(args.n_group) + 1) * size > first) & (
            jnp.arange(args.n_group) * size < first + held)
        missed = ~jnp.any(kept_groups & group_held, axis=-1)
        if token_mask is not None:
            missed = missed & token_mask.reshape(S)
        grouped_stats = {"no_held_group": jnp.sum(missed).astype(
            jnp.float32)}
    return y_out, aux, {
        **grouped_stats,
        "expert_tokens": counts.astype(jnp.float32),
        "dropped": jnp.zeros((), jnp.float32),
        "assigned": jnp.sum(counts).astype(jnp.float32),
        "entropy": -jnp.mean(jnp.sum(pr * jnp.log(pr + 1e-9), axis=-1)),
        "held_rows": jnp.sum(sizes).astype(jnp.float32),
        "touched": jnp.sum(sizes > 0).astype(jnp.float32),
        "elsewhere": (jnp.sum(counts) - jnp.sum(sizes)).astype(jnp.float32),
        "tile_visits": tile_visits,
    }


def _expert_rows(xs, weights, sizes, expert_layer):
    """The held experts' SwiGLU over the sorted routings ``xs`` [S*k,
    D] -> (``ys`` [S*k, D] f32, ``tile_visits``): three grouped matmuls
    (gate, up, down) over the groups ``sizes`` [held] with ``silu *
    up`` between, operands as given, f32 sums. Rows past every group
    come back as whatever the grouped matmul left there.

    WHICH grouped matmul is decided from shapes, where the program is
    lowered: for a TPU, with ``D`` and the experts' width whole
    128-lane tiles and float weights in the rows' dtype, the kernel
    that visits only (row tile, touched expert) pairs and reads each
    touched expert's weights once, the stack indexed in place
    (ops/grouped_matmul.py); for anything else (another platform, a
    tiny preset, a packed weight) ``lax.ragged_dot`` — over a stack,
    as ``layers * held`` groups of which only this layer's have rows
    (:func:`_moe_dropless`). Where this process's backend and a TPU
    would choose apart both are traced under ``lax.platform_dependent``
    (nn/attention.kernel_where_it_lowers), so a CPU process compiling
    for a described chip sizes what the chip runs. ``tile_visits``: the
    (row tile, expert) visits the kernel's metadata lists — over
    ``touched``, how often a row tile boundary made an expert's
    weights meet the matrix unit again; 0 where no kernel is traced."""
    from quintnet_tpu.nn.attention import kernel_where_it_lowers
    from quintnet_tpu.ops import grouped_matmul as gm

    held, rows = sizes.shape[0], xs.shape[0]

    def lowers_for(backend):
        return all(gm.grouped_matmul_lowers_for(
            backend, k=w.shape[-2], n=w.shape[-1], x_dtype=xs.dtype,
            w_dtype=w.dtype) for w in weights)

    def swiglu(grouped, xs, w_gate, w_up, w_down):
        hid = jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
        return grouped(hid.astype(xs.dtype), w_down)

    def by_ragged_dot(xs, weights, sizes, visits, layer):
        groups = sizes if expert_layer is None else lax.dynamic_update_slice(
            jnp.zeros((weights[0].shape[0] * held,), sizes.dtype), sizes,
            (layer * held,))
        return swiglu(lambda a, w: lax.ragged_dot(
            a, w.reshape(-1, *w.shape[-2:]), groups,
            preferred_element_type=jnp.float32), xs, *weights)

    def by_kernel(xs, weights, sizes, visits, layer):
        return swiglu(lambda a, w: gm.grouped_matmul(
            a, w, visits, layer=None if expert_layer is None else layer),
            xs, *weights)

    here, on_tpu = lowers_for(jax.default_backend()), lowers_for("tpu")
    layer = jnp.asarray(0 if expert_layer is None else expert_layer,
                        jnp.int32)
    if not (here or on_tpu):
        return (by_ragged_dot(xs, weights, sizes, None, layer),
                jnp.zeros((), jnp.float32))
    visits = gm.group_visits(sizes, rows=rows,
                             row_tile=gm.row_tile_for(rows, xs.dtype))
    return (kernel_where_it_lowers(here, on_tpu, by_kernel, by_ragged_dot,
                                   xs, weights, sizes, visits, layer),
            visits.count[0].astype(jnp.float32))


def _group_limited(select, args: MoEArgs):
    """DeepSeek-V3's group limit on the selection scores ``select`` [S,
    E]: ``n_group`` runs of consecutive experts, a group's score the sum
    of its two largest, the ``topk_group`` best groups kept. Returns
    (``select`` with every other group's experts at -inf, the kept
    groups [S, n_group] bool)."""
    S, E = select.shape
    G, keep = args.n_group, args.topk_group
    if E % G or not 1 <= keep <= G or args.top_k > keep * (E // G):
        raise ValueError(
            f"n_group={G} must divide n_experts={E}, topk_group={keep} "
            f"lie in [1, n_group] and the kept groups hold top_k="
            f"{args.top_k} experts")
    by_group = select.reshape(S, G, E // G)
    group_score = jnp.sum(lax.top_k(by_group, min(2, E // G))[0], axis=-1)
    _, best = lax.top_k(group_score, keep)                     # [S, keep]
    kept = jnp.any(best[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
    return jnp.where(kept[:, :, None], by_group,
                     -jnp.inf).reshape(S, E), kept


def _routing_stats(oh, keep, probs, assigned: int):
    """Per-call routing stats from the (replicated) dispatch masks —
    see :func:`moe_apply`'s docstring for field semantics."""
    return {
        "expert_tokens": jnp.sum(oh, axis=0).astype(jnp.float32),
        "dropped": jnp.sum(~keep).astype(jnp.float32),
        "assigned": jnp.asarray(float(assigned), jnp.float32),
        "entropy": -jnp.mean(
            jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1)),
    }


def _expert_ffn(p, xe, *, act, tp_axis):
    """Batched per-expert FFN on [E, C, D] rows (mlp or swiglu experts;
    shared by both routers)."""
    if "wg" in p:
        h = (jax.nn.silu(jnp.einsum("ecd,edh->ech", xe,
                                    p["wg"].astype(xe.dtype)))
             * jnp.einsum("ecd,edh->ech", xe, p["wu"].astype(xe.dtype)))
        y = jnp.einsum("ech,ehd->ecd", h, p["wd"].astype(h.dtype))
        if tp_axis is not None:
            y = lax.psum(y, tp_axis)
        return y
    h = jnp.einsum("ecd,edh->ech", xe, p["w1"].astype(xe.dtype))
    h = act(h + p["b1"].astype(h.dtype)[:, None, :])
    y = jnp.einsum("ech,ehd->ecd", h, p["w2"].astype(h.dtype))
    if tp_axis is not None:
        y = lax.psum(y, tp_axis)
    return y + p["b2"].astype(y.dtype)[:, None, :]


def _moe_expert_choice(p, xt, probs, logits, btd, C, args: MoEArgs, *,
                       ep_axis, tp_axis, act=gelu, return_stats=False):
    """Expert-choice routing: expert e takes the C tokens with the
    highest affinity probs[:, e]; combine weight = that affinity.
    Every expert buffer is exactly full (no drops, no load imbalance),
    so no aux loss — only the optional router z-loss survives."""
    B, T, D = btd
    S = xt.shape[0]
    gate, idx = lax.top_k(probs.T, min(C, S))      # each [E, C']
    if C > S:  # capacity above token count: pad with repeats at 0 gate
        pad = C - S
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        gate = jnp.pad(gate, ((0, 0), (0, pad)))
    xe = xt[idx.reshape(-1)].reshape(idx.shape[0], C, D)     # [E, C, D]

    if ep_axis is not None:
        xe = cc.all_to_all(xe, ep_axis, split_dim=0, concat_dim=1)

    y = _expert_ffn(p, xe, act=act, tp_axis=tp_axis)

    if ep_axis is not None:
        y = cc.all_to_all(y, ep_axis, split_dim=1, concat_dim=0)

    yw = y * gate.astype(y.dtype)[:, :, None]                # [E, C, D]
    yt = (jnp.zeros((S, D), y.dtype)
          .at[idx.reshape(-1)].add(yw.reshape(-1, D)))

    aux = jnp.zeros((), jnp.float32)
    if args.z_weight:
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        aux = args.z_weight * jnp.mean(jnp.square(z))
    if return_stats:
        # expert choice is perfectly balanced by construction: every
        # expert takes exactly C tokens, nothing is dropped
        E = probs.shape[-1]
        stats = {
            "expert_tokens": jnp.full((E,), float(C), jnp.float32),
            "dropped": jnp.zeros((), jnp.float32),
            "assigned": jnp.asarray(float(E * C), jnp.float32),
            "entropy": -jnp.mean(
                jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1)),
        }
        return yt.reshape(B, T, D), aux, stats
    return yt.reshape(B, T, D), aux
