"""Ling 3.0 (``bailing_hybrid``): Kimi-Delta-Attention layers with ONE
latent-attention (MLA) layer closing every group of ``layer_group_size``,
leading dense SwiGLU layers then a group-limited sigmoid mixture beside
a shared expert. Serving only.

With ``h`` the residual stream and every norm an RMSNorm (pre-norm)::

    h = h + mixer(N1(h))        KDA (nn/kda.py), or MLA where (i + 1) % group == 0
    h = h + FFN(N2(h))          SwiGLU in the first first_k_dense_replace layers
    logits = Nf(h) W_head       untied

``FFN`` after the dense layers (nn/moe.py, the dropless router): ``s =
sigmoid(x W_r)`` over all the router's experts in f32; selection score
``s + b`` (``e_score_correction_bias``); ``n_group`` groups of
consecutive experts, a group's score the sum of its two largest ``s +
b``; the ``topk_group`` best groups; the ``num_experts_per_tok`` largest
``s + b`` inside them; ``w = routed_scaling_factor * s / sum(s chosen)``
from the UNBIASED scores; ``y = SwiGLU_shared(x) + sum over chosen
experts HELD HERE of w_e SwiGLU_e(x)``.

MLA, the latent family's (models/pangu_moe.py) with ``q_lora_rank``
null: ``q = x W_q`` -> heads of ``[q_nope | q_rope]``, no down
projection and no query norm; ``[ckv | k_r] = x W_dkv``; ``c =
Nkv(ckv)``; ``k_rope = RoPE(k_r)``, ONE for all heads; ``[k_nope | v] =
c W_ukv`` per head; scores over ``nope + rope`` features; then a
per-head gate ``o_h <- sigmoid(x W_g)_h o_h`` before ``W_o``. The cache
holds ``[c | k_rope]``, one row a token; prefill MATERIALIZES keys and
values from the gathered rows, decode and verify ABSORB ``W_uk`` /
``W_uv`` and walk each row's live blocks in place (nn/attention.py,
"The LATENT paged cache"). A KDA layer caches nothing a position: it
keeps a fixed-size f32 state and a conv tail per slot
(serve/kv_pool.StateShapes).

The config's field names are the Hugging Face keys. ``num_experts``
counts the experts HELD here (``experts_first`` on) of the router's
``num_experts_published`` — one chip's share under expert parallelism;
``vocab_size`` likewise may be a slice. Assumed, where the published
config is silent (benchmarks/configs/ling-3.0-flash.json states each
with its ground): the MLA layer is the LAST of a group; the decay
gate's form; the head-wise output gate on both mixers; the per-head
output norm; rotary lanes paired half-split.

This module holds the config, the initialiser and the layer bodies;
the walk over the layer pattern is serve/families.ling_hybrid_family.
There is no training path: neither the chunked delta rule nor the
latent forms nor the grouped matmul has a backward here (ROADMAP M11).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from quintnet_tpu.core.pytree import tree_stack
from quintnet_tpu.models.pangu_moe import (ABSORBED, MATERIALIZED,
                                           latent_rows_attend)
from quintnet_tpu.nn.attention import apply_rope
from quintnet_tpu.nn.kda import KDADims, kda_chunk, kda_init, kda_step
from quintnet_tpu.nn.layers import (linear_init, quantized_matmul,
                                    rms_norm_apply, rms_norm_init,
                                    swiglu_apply, swiglu_init)
from quintnet_tpu.nn.moe import MoEArgs, moe_apply, moe_held_init

KDA, MLA = "kda", "mla"


@dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 128
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6000000.0
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    num_experts: int = 512               # experts HELD here
    num_experts_published: Optional[int] = None   # the router's
    experts_first: int = 0               # first expert held
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    num_nextn_predict_layers: int = 0
    score_function: str = "sigmoid"
    topk_method: str = "noaux_tc"
    moe_router_enable_expert_bias: bool = True
    gated_attention_proj_granularity_type: str = "head_wise"
    group_norm_size: int = 1
    num_kv_heads_for_linear_attn: int = 0
    kda_safe_gate: bool = True
    no_kda_lora: bool = True
    use_kda_lora: bool = False
    linear_silu: bool = True
    use_qk_norm: bool = True
    use_qkv_bias: bool = False
    use_bias: bool = False
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    rope_scaling: Optional[Any] = None

    def __post_init__(self):
        if self.num_experts_published is None:
            object.__setattr__(self, "num_experts_published",
                               self.num_experts)
        only = {"q_lora_rank": None, "num_shared_experts": 1,
                "num_nextn_predict_layers": 0, "score_function": "sigmoid",
                "topk_method": "noaux_tc",
                "moe_router_enable_expert_bias": True,
                "gated_attention_proj_granularity_type": "head_wise",
                "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
                "kda_safe_gate": True, "no_kda_lora": True,
                "use_kda_lora": False, "linear_silu": True,
                "use_qk_norm": True, "use_qkv_bias": False,
                "use_bias": False, "tie_word_embeddings": False,
                "hidden_act": "silu", "rope_scaling": None}
        for key, want in only.items():
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"bailing_hybrid: {key}={getattr(self, key)!r} is not "
                    f"implemented (only {want!r})")
        if self.num_key_value_heads != self.num_attention_heads:
            raise NotImplementedError(
                "latent attention shares one row among all heads; "
                "num_key_value_heads must equal num_attention_heads")
        g = self.layer_group_size
        if g < 2 or self.num_hidden_layers % g:
            raise NotImplementedError(
                f"num_hidden_layers={self.num_hidden_layers} must be "
                f"whole groups of layer_group_size={g} (its last layer "
                f"the latent one)")
        if not 0 <= self.first_k_dense_replace < g:
            raise NotImplementedError(
                f"the dense layers (first_k_dense_replace="
                f"{self.first_k_dense_replace}) must be KDA layers of "
                f"the first group of {g}")
        if not (0 <= self.experts_first and self.experts_first
                + self.num_experts <= self.num_experts_published):
            raise ValueError(
                f"experts [{self.experts_first}, {self.experts_first} + "
                f"{self.num_experts}) are not among the router's "
                f"{self.num_experts_published}")

    @property
    def periods(self) -> int:
        return self.num_hidden_layers // self.layer_group_size

    @property
    def kda_per_period(self) -> int:
        return self.layer_group_size - 1

    @property
    def n_kda_layers(self) -> int:
        return self.periods * self.kda_per_period

    @property
    def n_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """Every layer's mixer and FFN in model order."""
        return tuple(
            (MLA if (i + 1) % self.layer_group_size == 0 else KDA)
            + ("_dense" if i < self.first_k_dense_replace else "_moe")
            for i in range(self.num_hidden_layers))

    @property
    def latent_width(self) -> int:
        """Features of one cached row: ``[c | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kda(self) -> KDADims:
        return KDADims(
            n_heads=self.num_attention_heads, d_k=self.head_dim,
            d_v=self.head_dim, d_conv=self.short_conv_kernel_size,
            lower_bound=float(self.kda_lower_bound), eps=self.rms_norm_eps)

    @property
    def moe_args(self) -> MoEArgs:
        return MoEArgs(
            n_experts=self.num_experts_published,
            top_k=self.num_experts_per_tok,
            normalize_gates=self.norm_topk_prob, dropless=True,
            scoring="sigmoid", routed_scale=self.routed_scaling_factor,
            experts_held=(self.experts_first, self.num_experts),
            n_group=self.n_group, topk_group=self.topk_group)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "LingHybridConfig":
        names = {f.name for f in dataclasses.fields(LingHybridConfig)}
        return LingHybridConfig(**{k: v for k, v in d.items()
                                   if k in names})

    @staticmethod
    def tiny(**kw) -> "LingHybridConfig":
        """Two groups of ``[kda, kda, mla]``, the first layer dense, at
        toy widths (CPU tests): 16 experts in 4 groups of which the
        first two groups' 8 are held, 2 groups kept, top-4."""
        d = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32,
                 moe_shared_expert_intermediate_size=32,
                 num_hidden_layers=6, layer_group_size=3,
                 first_k_dense_replace=1, num_attention_heads=4,
                 num_key_value_heads=4, head_dim=16, kv_lora_rank=16,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 rope_theta=10000.0, num_experts=8,
                 num_experts_published=16, experts_first=0,
                 num_experts_per_tok=4, n_group=4, topk_group=2,
                 max_position_embeddings=256)
        d.update(kw)
        return LingHybridConfig(**d)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------
def _mla_init(key, cfg: LingHybridConfig, dtype):
    kq, kd, kv, kg, ko = jax.random.split(key, 5)
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim

    def lin(k, fin, fout):
        return linear_init(k, fin, fout, use_bias=False, dtype=dtype)

    return {
        "q": lin(kq, d, h * qk),
        "kv_down": lin(kd, d, cfg.latent_width),
        "kv_norm": rms_norm_init(cfg.kv_lora_rank, dtype),
        "kv_up": lin(kv, cfg.kv_lora_rank,
                     h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "gate": lin(kg, d, h),
        "o": lin(ko, h * cfg.v_head_dim, d)}


def ling_hybrid_init(key, cfg: LingHybridConfig, *, dtype=jnp.float32):
    """Four uniform stacks, each in model order: ``blocks.kda`` (ln1 +
    the KDA mixer of every KDA layer), ``blocks.mla`` (ln1 + the latent
    attention of every group's last layer), ``blocks.dense`` (ln2 + the
    SwiGLU of the leading layers) and ``blocks.moe`` (ln2 + the mixture
    of every layer after them). A layer is one entry of a mixer stack
    and one of an FFN stack (:attr:`LingHybridConfig.layer_types`)."""
    k_emb, k_head, k_k, k_a, k_d, k_m = jax.random.split(key, 6)
    d = cfg.hidden_size

    def each(k, n, make):
        return tree_stack([make(kk) for kk in jax.random.split(k, n)])

    blocks = {
        "kda": each(k_k, cfg.n_kda_layers, lambda k: {
            "ln1": rms_norm_init(d, dtype),
            "mixer": kda_init(k, d, cfg.kda, dtype=dtype)}),
        "mla": each(k_a, cfg.periods, lambda k: {
            "ln1": rms_norm_init(d, dtype),
            "attn": _mla_init(k, cfg, dtype)}),
        "moe": each(k_m, cfg.n_moe_layers, lambda k: {
            "ln2": rms_norm_init(d, dtype),
            "moe": moe_held_init(
                k, d, cfg.moe_intermediate_size, cfg.num_experts_published,
                held=cfg.num_experts,
                shared_hidden=cfg.num_shared_experts
                * cfg.moe_shared_expert_intermediate_size,
                dtype=dtype, selection_bias=True)})}
    if cfg.n_dense_layers:
        blocks["dense"] = each(k_d, cfg.n_dense_layers, lambda k: {
            "ln2": rms_norm_init(d, dtype),
            "mlp": swiglu_init(k, d, cfg.intermediate_size, dtype=dtype)})
    return {
        "embedding": {"tok": jax.random.normal(
            k_emb, (cfg.vocab_size, d), dtype) * 0.02},
        "blocks": blocks,
        "head": {"ln_f": rms_norm_init(d, dtype),
                 "out": linear_init(k_head, d, cfg.vocab_size,
                                    use_bias=False, dtype=dtype)},
    }


WEIGHT_TARGETS = (
    *(("kda", "mixer", n)
      for n in ("q", "k", "v", "decay", "beta", "gate", "o")),
    *(("mla", "attn", n) for n in ("q", "kv_down", "kv_up", "gate", "o")),
    *(("dense", "mlp", n) for n in ("gate", "up", "down")),
    *(("moe", "moe", "shared", n) for n in ("gate", "up", "down")),
    *(("moe", "moe", "experts", n) for n in ("gate", "up", "down")))


def ling_hybrid_partition_specs(tp_axis: Optional[str] = None,
                                ep_axis: Optional[str] = None):
    raise NotImplementedError(
        "bailing_hybrid has no partition specs yet: one device holds its "
        "share whole. tp: the KDA state and projections and the latent "
        "row are not head-sharded; ep: the dropless router has no "
        "exchange over an ep axis (ROADMAP M1, M4)")


# ---------------------------------------------------------------------
# embedding, head
# ---------------------------------------------------------------------
def ling_embed(params, ids):
    with jax.named_scope("embed"):
        return jnp.take(params["embedding"]["tok"], ids, axis=0)


def ling_logits(params, h, cfg: LingHybridConfig):
    with jax.named_scope("final_norm"):
        h = rms_norm_apply(params["head"]["ln_f"], h, eps=cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.dot(h, params["head"]["out"]["w"]).astype(jnp.float32)


# ---------------------------------------------------------------------
# the mixers
# ---------------------------------------------------------------------
def kda_mixer_chunk(p, x, state, tail, lens, cfg: LingHybridConfig):
    """A run of tokens a row: x [R, T, D], the rows' state [R, H, dk,
    dv] and conv tail, ``lens`` [R] real tokens -> (x, state, tail)."""
    with jax.named_scope("kda"):
        u = rms_norm_apply(p["ln1"], x, eps=cfg.rms_norm_eps)
        y, state, tail = kda_chunk(p["mixer"], u, state, tail, lens,
                                   cfg.kda)
        return x + y, state, tail


def kda_mixer_step(p, x, state, tail, cfg: LingHybridConfig):
    """One token a row: x [R, 1, D] -> (x, state, tail)."""
    with jax.named_scope("kda"):
        u = rms_norm_apply(p["ln1"], x[:, 0], eps=cfg.rms_norm_eps)
        y, state, tail = kda_step(p["mixer"], u, state, tail, cfg.kda)
        return x + y[:, None, :], state, tail


def mla_mixer(p, x, pool, layer, positions, lens, tables, block_size: int,
              cfg: LingHybridConfig, cos, sin, *, form: str):
    """The latent layer of a group over the paged latent pool: x [S, P,
    D] at ``positions`` [S, P] -> (x, pool). The run's rows ``[c |
    k_rope]`` are written into ``layer`` first; ``form`` as in
    models/pangu_moe.mla_paged; ``cos``/``sin`` [S, P, rope]."""
    s, t, _ = x.shape
    h, vd = cfg.num_attention_heads, cfg.v_head_dim
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    a = p["attn"]
    with jax.named_scope("mla"):
        u = rms_norm_apply(p["ln1"], x, eps=cfg.rms_norm_eps)
        with jax.named_scope("q_up"):
            q = quantized_matmul(u, a["q"]).reshape(s, t, h, nope + rope)
            q_nope = q[..., :nope]
            q_rope = apply_rope(q[..., nope:], cos[:, :, None],
                                sin[:, :, None])
        o, pool = latent_rows_attend(
            a, u, q_nope, q_rope, pool, layer, positions, lens, tables,
            block_size, cfg, cos, sin, form=form)
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(
                quantized_matmul(u, a["gate"]).astype(jnp.float32))
            o = (o * gate[..., None]).astype(x.dtype)
        with jax.named_scope("proj"):
            y = quantized_matmul(o.reshape(s, t, h * vd), a["o"])
    return x + y, pool


# ---------------------------------------------------------------------
# the feed-forward halves
# ---------------------------------------------------------------------
def ffn_dense(p, x, cfg: LingHybridConfig):
    with jax.named_scope("mlp"):
        u = rms_norm_apply(p["ln2"], x, eps=cfg.rms_norm_eps)
        return x + swiglu_apply(p["mlp"], u)


def ffn_moe(p, x, lens, cfg: LingHybridConfig, experts, expert_layer):
    """The mixture half of a layer: x [S, P, D] -> (x, moe_stats). A
    run's columns at or beyond ``lens`` are padding: the router sends
    them nowhere. ``experts``: the routed experts' weights of the WHOLE
    MoE stack, ``expert_layer`` this layer's index in it (nn/moe.py says
    why a layer loop keeps them whole)."""
    with jax.named_scope("moe"):
        u = rms_norm_apply(p["ln2"], x, eps=cfg.rms_norm_eps)
        mask = jnp.arange(x.shape[1])[None, :] < lens[:, None]
        m, _, stats = moe_apply(
            {**p["moe"], "experts": experts}, u, cfg.moe_args,
            return_stats=True, token_mask=mask, expert_layer=expert_layer)
        return x + m, stats
