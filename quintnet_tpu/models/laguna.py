"""Laguna (``model_type`` ``laguna``): sliding-window attention layers
among global ones, a different number of query heads by layer kind, a
rotary setting by layer kind, a per-head output gate, and a sigmoid
top-k mixture of small experts beside a shared one after a leading
dense layer. Serving only.

With ``x`` the residual stream, every norm an RMSNorm, layer ``l`` of
attention kind ``t`` (``layer_types[l]``: full or sliding)::

    h = N1(x);  q = h W_q as [H_t, d];  k, v = h W_k, h W_v as [H_kv, d]
    q, k = RoPE_t(q), RoPE_t(k)
    P = softmax(q k^T / sqrt(d)) over the keys j <= i and, on a sliding
        layer, j > i - sliding_window (the query's own position counts)
    o_h = sigmoid(h W_g)_h * (P_h v)        one gate scalar a head
    x = x + concat_h(o) W_o
    x = x + FFN(N2(x))

``H_t`` is ``num_attention_heads_per_layer[l]`` (one value a kind), the
``H_kv`` kv heads are shared by ``H_t / H_kv`` query heads each.
``RoPE_t`` (``rope_parameters``, nn/attention.py): full layers rotate
the first ``partial_rotary_factor * d`` features only, with YaRN
frequencies and cos/sin scaled by ``attention_factor``; sliding layers
rotate all ``d`` with the plain frequencies. ``FFN`` is a SwiGLU of
``intermediate_size`` on a ``dense`` layer (``mlp_layer_types``) and,
on a ``sparse`` one, the dropless mixture (nn/moe.py): ``s = sigmoid(u
W_r)`` in f32 over all ``num_experts``, the ``num_experts_per_tok``
largest, ``w = moe_routed_scaling_factor * s / sum(s chosen)`` applied
to the experts' OUTPUT, plus ``SwiGLU_shared(u)``. Every expert is held
here (``experts_held`` all).

The cache is of TWO kinds (serve/kv_pool.py): a full layer pages all
of a sequence's positions into the block pool; a sliding layer keeps a
ring of ``sliding_window + block_size`` positions a slot
(nn/attention.window_attend), whatever the sequence's length.

Layers of one (attention kind, FFN kind) stack together
(``blocks.full_dense``, ``blocks.sliding_sparse``, ...: ``W_q`` is
``[D, 48 d]`` on one kind and ``[D, 64 d]`` on the other, so the kinds
do not stack as one); the routed experts of ALL sparse layers are one
stack ``blocks.experts`` (nn/moe.py says why a layer scan keeps them
whole). The serving programs walk :attr:`LagunaConfig.runs`: maximal
runs of consecutive layers of one kind, a scan each
(serve/families.laguna_family).

Assumed, where the published config is silent (the benchmark's
configuration file states each with its ground): the gate is per head
and reads the layer's normed input; the router is sigmoid, normalised
over the chosen, with no groups, bias or softcap; no QK-norm; the
rotary pairing is the half-split one of this repo. There is no
training path (ROADMAP M2).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from quintnet_tpu.core.pytree import tree_stack
from quintnet_tpu.nn.attention import (apply_rope, paged_attend,
                                       rope_cos_sin, window_attend,
                                       yarn_inv_freq)
from quintnet_tpu.nn.layers import (linear_init, quantized_matmul,
                                    rms_norm_apply, rms_norm_init,
                                    swiglu_apply, swiglu_init)
from quintnet_tpu.nn.moe import MoEArgs, moe_apply, moe_held_init

# positions a prefill chunk reads of a global layer's cache at a time
# (nn/attention._paged_attend_key_blocked): 1,024 divides the cell's
# 17,408-position table, and one block's f32 scores for the 6 query
# heads of a kv head x 8 kv heads x 1,024 tokens are 0.2 GB
PREFILL_KEY_BLOCK = 1024

FULL, SLIDING = "full", "sliding"
DENSE, SPARSE = "dense", "sparse"
_ATTN_KINDS = {"full_attention": FULL, "sliding_attention": SLIDING}


class Run(NamedTuple):
    """Consecutive layers of one kind: ``count`` layers from ``first``
    of the stack ``blocks[kind]``; their cache layers start at
    ``cache_first`` (of the block pool on a full kind, of the window
    store on a sliding one), their experts at ``expert_first`` of
    ``blocks.experts`` (None on a dense kind)."""

    kind: str
    attn: str
    mlp: str
    first: int
    count: int
    cache_first: int
    expert_first: Optional[int]


@dataclass(frozen=True)
class RopeSetting:
    """One layer kind's rotary setting (an entry of the published
    ``rope_parameters``)."""

    rope_theta: float = 10000.0
    rope_type: str = "default"          # "default" | "yarn"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "RopeSetting":
        names = {f.name for f in dataclasses.fields(RopeSetting)}
        return RopeSetting(**{k: v for k, v in d.items() if k in names})


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: bool = True
    sliding_window: int = 512
    moe_routed_scaling_factor: float = 2.5
    moe_apply_router_weight_on_input: bool = False
    # by layer, in model order; None = the published pattern: (full,
    # sliding x3) repeated, layer 0 dense, 48 / 64 heads by kind
    layer_types: Optional[Tuple[str, ...]] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    rope_full: RopeSetting = RopeSetting(
        rope_theta=500000.0, rope_type="yarn", partial_rotary_factor=0.5,
        factor=64.0, original_max_position_embeddings=4096,
        beta_fast=64.0, beta_slow=1.0,
        attention_factor=1.4158883083359672)
    rope_sliding: RopeSetting = RopeSetting()

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                "sliding_attention" if i % 4 else "full_attention"
                for i in range(n)))
        if self.mlp_layer_types is None:
            object.__setattr__(self, "mlp_layer_types", tuple(
                SPARSE if i else DENSE for i in range(n)))
        if self.num_attention_heads_per_layer is None:
            object.__setattr__(self, "num_attention_heads_per_layer", tuple(
                self.num_attention_heads if t == "full_attention"
                else self.num_attention_heads * 4 // 3
                for t in self.layer_types))
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            # a model cut in depth keeps the published lists whole: the
            # layers run are their first ``num_hidden_layers`` entries
            object.__setattr__(self, name, tuple(getattr(self, name))[:n])
            if len(getattr(self, name)) != n:
                raise ValueError(
                    f"{name} lists {len(getattr(self, name))} layers; "
                    f"num_hidden_layers is {n}")
        only = {"attention_bias": False, "tie_word_embeddings": False,
                "gating": True, "moe_apply_router_weight_on_input": False}
        for key, want in only.items():
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"laguna: {key}={getattr(self, key)!r} is not "
                    f"implemented (only {want!r})")
        bad = (set(self.layer_types) - set(_ATTN_KINDS)
               | set(self.mlp_layer_types) - {DENSE, SPARSE})
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if SPARSE not in self.mlp_layer_types:
            raise ValueError("need at least one sparse layer (the "
                             "serving programs return routing stats)")
        for kind in set(self.attn_kinds):
            heads = {h for h, t in zip(self.num_attention_heads_per_layer,
                                       self.attn_kinds) if t == kind}
            if len(heads) != 1 or heads.pop() % self.num_key_value_heads:
                raise ValueError(
                    f"the {kind} layers need ONE head count, a multiple "
                    f"of the {self.num_key_value_heads} kv heads: "
                    f"{self.num_attention_heads_per_layer}")

    @property
    def attn_kinds(self) -> Tuple[str, ...]:
        """``layer_types`` as this module names the kinds."""
        return tuple(_ATTN_KINDS[t] for t in self.layer_types)

    def heads_of(self, attn: str) -> int:
        return next(h for h, t in zip(self.num_attention_heads_per_layer,
                                      self.attn_kinds) if t == attn)

    def rope_of(self, attn: str) -> RopeSetting:
        return self.rope_full if attn == FULL else self.rope_sliding

    def n_layers_of(self, attn: str) -> int:
        return sum(1 for t in self.attn_kinds if t == attn)

    @property
    def n_sparse_layers(self) -> int:
        return sum(1 for t in self.mlp_layer_types if t == SPARSE)

    @property
    def runs(self) -> Tuple[Run, ...]:
        """The layers in model order as maximal runs of one kind."""
        out, seen, cache, experts = [], {}, {FULL: 0, SLIDING: 0}, 0
        for attn, mlp in zip(self.attn_kinds, self.mlp_layer_types):
            kind = f"{attn}_{mlp}"
            if out and out[-1].kind == kind:
                out[-1] = out[-1]._replace(count=out[-1].count + 1)
            else:
                out.append(Run(kind, attn, mlp, seen.get(kind, 0), 1,
                               cache[attn],
                               experts if mlp == SPARSE else None))
            seen[kind] = seen.get(kind, 0) + 1
            cache[attn] += 1
            experts += mlp == SPARSE
        return tuple(out)

    @property
    def moe_args(self) -> MoEArgs:
        return MoEArgs(
            n_experts=self.num_experts, top_k=self.num_experts_per_tok,
            normalize_gates=True, dropless=True, scoring="sigmoid",
            routed_scale=self.moe_routed_scaling_factor)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "LagunaConfig":
        """From the configuration file's dict (the Hugging Face keys;
        ``rope_parameters`` by ``full_attention`` /
        ``sliding_attention``)."""
        names = {f.name for f in dataclasses.fields(LagunaConfig)}
        kw = {k: v for k, v in d.items() if k in names}
        rope = d.get("rope_parameters") or {}
        for key, field in (("full_attention", "rope_full"),
                           ("sliding_attention", "rope_sliding")):
            if key in rope:
                kw[field] = RopeSetting.from_dict(rope[key])
        return LagunaConfig(**kw)

    def to_dict(self) -> Dict[str, Any]:
        """The Hugging Face keys back (:meth:`from_dict`'s inverse):
        what the plain reference reads."""
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self)
             if f.name not in ("rope_full", "rope_sliding")}
        d["rope_parameters"] = {
            "full_attention": dataclasses.asdict(self.rope_full),
            "sliding_attention": dataclasses.asdict(self.rope_sliding)}
        return d

    @staticmethod
    def tiny(**kw) -> "LagunaConfig":
        """The published pattern's first five layers at toy widths (CPU
        tests): a window of 8, 6 / 8 query heads over 2 kv heads of 16
        features, 16 experts, top-4."""
        d = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                 num_hidden_layers=5, num_attention_heads=6,
                 num_key_value_heads=2, head_dim=16,
                 max_position_embeddings=256, num_experts=16,
                 num_experts_per_tok=4, moe_intermediate_size=24,
                 shared_expert_intermediate_size=24, sliding_window=8,
                 rope_full=RopeSetting(
                     rope_theta=500000.0, rope_type="yarn",
                     partial_rotary_factor=0.5, factor=8.0,
                     original_max_position_embeddings=16, beta_fast=4.0,
                     beta_slow=1.0, attention_factor=1.2079441541679836))
        d.update(kw)
        return LagunaConfig(**d)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------
def _attn_init(key, cfg: LagunaConfig, heads: int, dtype):
    kq, kk, kv, ko, kg = jax.random.split(key, 5)
    d, hd = cfg.hidden_size, cfg.head_dim

    def lin(k, fin, fout):
        return linear_init(k, fin, fout, use_bias=False, dtype=dtype)

    return {"q": lin(kq, d, heads * hd),
            "k": lin(kk, d, cfg.num_key_value_heads * hd),
            "v": lin(kv, d, cfg.num_key_value_heads * hd),
            "o": lin(ko, heads * hd, d),
            "gate": lin(kg, d, heads)}


def _block_init(key, cfg: LagunaConfig, attn: str, mlp: str, dtype):
    """One layer WITHOUT its routed experts (-> (block, experts or
    None): those stack apart, over all the sparse layers)."""
    k_attn, k_ffn = jax.random.split(key)
    d = cfg.hidden_size
    p = {"attn": _attn_init(k_attn, cfg, cfg.heads_of(attn), dtype),
         "ln1": rms_norm_init(d, dtype), "ln2": rms_norm_init(d, dtype)}
    if mlp == DENSE:
        p["mlp"] = swiglu_init(k_ffn, d, cfg.intermediate_size, dtype=dtype)
        return p, None
    moe = moe_held_init(
        k_ffn, d, cfg.moe_intermediate_size, cfg.num_experts,
        held=cfg.num_experts,
        shared_hidden=cfg.shared_expert_intermediate_size, dtype=dtype)
    experts = moe.pop("experts")
    p["moe"] = moe
    return p, experts


def laguna_init(key, cfg: LagunaConfig, *, dtype=jnp.float32):
    """``blocks[kind]`` stacks the layers of each (attention, FFN) kind
    in model order, ``blocks.experts`` the routed experts of every
    sparse layer ``[L_sparse, E, in, out]``."""
    k_emb, k_head, k_blocks = jax.random.split(key, 3)
    d = cfg.hidden_size
    stacks: Dict[str, list] = {}
    experts = []
    for k, attn, mlp in zip(
            jax.random.split(k_blocks, cfg.num_hidden_layers),
            cfg.attn_kinds, cfg.mlp_layer_types):
        blk, ex = _block_init(k, cfg, attn, mlp, dtype)
        stacks.setdefault(f"{attn}_{mlp}", []).append(blk)
        if ex is not None:
            experts.append(ex)
    return {
        "embedding": {"tok": jax.random.normal(
            k_emb, (cfg.vocab_size, d), dtype) * 0.02},
        "blocks": {**{kind: tree_stack(v) for kind, v in stacks.items()},
                   "experts": tree_stack(experts)},
        "head": {"ln_f": rms_norm_init(d, dtype),
                 "out": linear_init(k_head, d, cfg.vocab_size,
                                    use_bias=False, dtype=dtype)},
    }


_KINDS = tuple(f"{a}_{m}" for a in (FULL, SLIDING) for m in (DENSE, SPARSE))
WEIGHT_TARGETS = (
    *((kind, "attn", n) for kind in _KINDS
      for n in ("q", "k", "v", "o", "gate")),
    *((kind, "mlp", n) for kind in _KINDS for n in ("gate", "up", "down")),
    *((kind, "moe", "shared", n) for kind in _KINDS
      for n in ("gate", "up", "down")),
    *(("experts", n) for n in ("gate", "up", "down")))


def laguna_partition_specs(tp_axis: Optional[str] = None,
                           ep_axis: Optional[str] = None):
    raise NotImplementedError(
        "laguna has no partition specs yet: one device holds its layers "
        "whole. The two layer kinds' unequal head counts are not "
        "head-sharded (tp), the window store is not sharded, and the "
        "dropless router has no exchange over an ep axis (ROADMAP M1, "
        "M2, M5)")


# ---------------------------------------------------------------------
# embedding, head, rotary tables
# ---------------------------------------------------------------------
def laguna_embed(params, ids):
    with jax.named_scope("embed"):
        return jnp.take(params["embedding"]["tok"], ids, axis=0)


def laguna_logits(params, h, cfg: LagunaConfig):
    with jax.named_scope("final_norm"):
        h = rms_norm_apply(params["head"]["ln_f"], h, eps=cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.dot(h, params["head"]["out"]["w"]).astype(jnp.float32)


def laguna_rope_tables(positions, cfg: LagunaConfig, attn: str):
    """(cos, sin) [..., rot] of layer kind ``attn`` at ``positions``:
    ``rot`` = the features that rotate (``partial_rotary_factor *
    head_dim``; :func:`~quintnet_tpu.nn.attention.apply_rope` passes
    the rest through)."""
    r = cfg.rope_of(attn)
    rot = int(cfg.head_dim * r.partial_rotary_factor)
    if r.rope_type == "default":
        return rope_cos_sin(positions, rot, theta=r.rope_theta)
    if r.rope_type != "yarn":
        raise NotImplementedError(f"rope_type {r.rope_type!r}")
    inv = yarn_inv_freq(
        rot, theta=r.rope_theta, factor=r.factor,
        original_max=r.original_max_position_embeddings,
        beta_fast=r.beta_fast, beta_slow=r.beta_slow)
    return rope_cos_sin(positions, rot, inv_freq=inv,
                        scale=r.attention_factor)


# ---------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------
def laguna_attention(p, u, caches, layer, positions, lens, tables, row0,
                     block_size: int, cfg: LagunaConfig, cos, sin, *,
                     attn: str, chunk: bool):
    """The attention of one layer of kind ``attn`` over its cache: u
    [S, P, D] (normed) at ``positions`` [S, P] -> (y [S, P, D],
    caches). ``caches`` = (k_pool, v_pool, wk, wv): a full layer writes
    and reads ``layer`` of the block pool through ``tables``, a sliding
    one ``layer`` of the window store at rows ``row0 + arange(S)``.
    ``chunk``: the run is a prefill chunk (many tokens of one row: a
    global layer reads its cache a block of keys at a time, only as far
    as the chunk sees) and not a decode or verify run (few tokens of
    every row; the cached rows are contracted as stored, heads on the
    lane diagonal, for either head count)."""
    s, t, _ = u.shape
    hd, hkv = cfg.head_dim, cfg.num_key_value_heads
    g = cfg.heads_of(attn) // hkv
    k_pool, v_pool, wk, wv = caches
    with jax.named_scope(attn), jax.named_scope("attn"):
        with jax.named_scope("qkv"):
            q = quantized_matmul(u, p["q"]).reshape(s, t, hkv, g, hd)
            k, v = (quantized_matmul(u, p[n]).reshape(s, t, hkv, hd)
                    for n in ("k", "v"))
        with jax.named_scope("rope"):
            q = apply_rope(q, cos[:, :, None, None], sin[:, :, None, None])
            k = apply_rope(k, cos[:, :, None], sin[:, :, None])
        # the g query heads of a kv head as ROWS of its score matrix
        # (g-major): the cached rows are contracted once a kv head
        q = q.transpose(0, 2, 3, 1, 4).reshape(s, hkv, g * t, hd)
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        # decode and verify: the diagonal whatever the head count; a
        # prefill chunk: never
        diag_rows = 0 if chunk else hkv * g * t
        if attn == FULL:
            o, (k_pool, v_pool) = paged_attend(
                q, k, v, (k_pool, v_pool), layer, positions, lens, tables,
                block_size=block_size,
                key_block=PREFILL_KEY_BLOCK if chunk else None,
                max_diag_rows=diag_rows)
        else:
            o, (wk, wv) = window_attend(
                q, k, v, (wk, wv), layer, positions, lens, row0,
                window=cfg.sliding_window,
                ring=cfg.sliding_window + block_size,
                max_diag_rows=diag_rows)
        o = o.reshape(s, hkv, g, t, hd).transpose(0, 3, 1, 2, 4)
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(quantized_matmul(u, p["gate"]).astype(
                jnp.float32)).reshape(s, t, hkv, g, 1)
            o = (o * gate).astype(u.dtype)
        with jax.named_scope("proj"):
            y = quantized_matmul(o.reshape(s, t, hkv * g * hd), p["o"])
    return y, (k_pool, v_pool, wk, wv)


def laguna_block(p, x, caches, layer, positions, lens, tables, row0,
                 block_size: int, cfg: LagunaConfig, cos, sin, *, attn: str,
                 chunk: bool, experts=None, expert_layer=None):
    """One layer (module docstring): x [S, P, D] -> (x, caches[,
    moe_stats]); the stats where the layer's FFN is the mixture (``moe``
    in its params). A run's columns at or beyond ``lens`` are padding:
    the router sends them nowhere. ``experts``, ``expert_layer``: the
    routed experts of ALL sparse layers and this layer's index among
    them."""
    eps = cfg.rms_norm_eps
    y, caches = laguna_attention(
        p["attn"], rms_norm_apply(p["ln1"], x, eps=eps), caches, layer,
        positions, lens, tables, row0, block_size, cfg, cos, sin,
        attn=attn, chunk=chunk)
    x = x + y
    u = rms_norm_apply(p["ln2"], x, eps=eps)
    if "moe" not in p:
        with jax.named_scope("mlp"):
            return x + swiglu_apply(p["mlp"], u), caches
    with jax.named_scope("moe"):
        mask = jnp.arange(x.shape[1])[None, :] < lens[:, None]
        m, _, stats = moe_apply(
            {**p["moe"], "experts": experts}, u, cfg.moe_args,
            return_stats=True, token_mask=mask, expert_layer=expert_layer)
    return x + m, caches, stats
