"""openPangu-Ultra-MoE (``pangu_ultra_moe``): multi-head latent
attention, leading dense layers then routed experts beside a shared
one, sandwich norms. Serving only.

With ``h`` the residual stream and every norm an RMSNorm::

    a = MLA(N1(h));  h = h + N2(a)          sandwich_norm: a norm before
    m = FFN(N3(h));  h = h + N4(m)          AND after each sub-layer

``FFN`` is a SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers and the mixture after them
(nn/moe.py, the dropless router): ``s = sigmoid(x W_g)`` over all the
router's experts in f32, the ``num_experts_per_tok`` largest, ``w =
routed_scaling_factor * s / sum(s chosen)``, ``y = SwiGLU_shared(x) +
sum over chosen experts HELD HERE of w_e SwiGLU_e(x)``.

MLA: ``cq = Nq(x W_dq)``; ``q = cq W_uq`` -> heads of ``[q_nope |
q_rope]``; ``[ckv | k_r] = x W_dkv``; ``c = Nkv(ckv)``; ``k_rope =
RoPE(k_r)``, ONE for all heads; ``q_rope = RoPE(q_rope)``; ``[k_nope |
v] = c W_ukv`` per head; scores ``(q_nope . k_nope + q_rope . k_rope)
/ sqrt(nope + rope)``, causal f32 softmax; ``o = concat_h(P v) W_o``.
The cache holds ``[c | k_rope]``, one row a token (nn/attention.py,
"The LATENT paged cache"). Two forms that are the same mathematics:
prefill MATERIALIZES ``k_nope`` and ``v`` from the gathered rows;
decode and verify ABSORB ``W_uk`` into the query (``q_lat = q_nope
W_uk^T``) and ``W_uv`` into the output, and contract the rows as
stored: each sequence's live blocks of the pool, in place.

The config's field names are the Hugging Face keys. ``n_routed_experts``
counts the experts HELD here (``experts_first`` on), of the router's
``n_routed_experts_published`` — one chip's share under expert
parallelism; ``vocab_size`` likewise may be a slice. Assumed, where the
published config is silent: sigmoid scoring without groups or bias;
the rotary pairing is the half-split one of this repo (a column
permutation of seeded weights); norm scales start at 1.

This module holds the config, the initialiser and the layer body; the
scan over the two stacks is serve/families.pangu_moe_family. There is
no training path: neither form has a backward here, nor the grouped
matmul (ROADMAP M1, M3).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from quintnet_tpu.core.pytree import tree_stack
from quintnet_tpu.nn.attention import (apply_rope, latent_attend_absorbed,
                                       latent_attend_materialized,
                                       latent_write, paged_gather)
from quintnet_tpu.nn.layers import (linear_init, quantized_matmul,
                                    rms_norm_apply, rms_norm_init,
                                    swiglu_apply, swiglu_init)
from quintnet_tpu.nn.moe import MoEArgs, moe_apply, moe_held_init

ABSORBED, MATERIALIZED = "absorbed", "materialized"

# heads a materialized prefill rebuilds and scores at a time: at the
# published shapes (a 1,024-token bucket against 5,120 cached rows) 8
# heads' f32 scores are 168 MB where all 128 would be 2.7 GB
HEAD_GROUP = 8


@dataclass(frozen=True)
class PanguMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    n_routed_experts: int = 256          # experts HELD here
    n_routed_experts_published: Optional[int] = None   # the router's
    experts_first: int = 0               # first expert held
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    sandwich_norm: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    num_nextn_predict_layers: int = 0
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"

    def __post_init__(self):
        if self.n_routed_experts_published is None:
            object.__setattr__(self, "n_routed_experts_published",
                               self.n_routed_experts)
        only = {"sandwich_norm": True, "attention_bias": False,
                "tie_word_embeddings": False, "hidden_act": "silu",
                "n_shared_experts": 1, "num_nextn_predict_layers": 0}
        for key, want in only.items():
            if getattr(self, key) != want:
                raise NotImplementedError(
                    f"pangu_ultra_moe: {key}={getattr(self, key)!r} is "
                    f"not implemented (only {want!r})")
        if self.num_key_value_heads != self.num_attention_heads:
            raise NotImplementedError(
                "latent attention shares one row among all heads; "
                "num_key_value_heads must equal num_attention_heads")
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError(
                "need at least one leading dense layer and one MoE "
                "layer after it")
        if not (0 <= self.experts_first and self.experts_first
                + self.n_routed_experts <= self.n_routed_experts_published):
            raise ValueError(
                f"experts [{self.experts_first}, {self.experts_first} + "
                f"{self.n_routed_experts}) are not among the router's "
                f"{self.n_routed_experts_published}")

    @property
    def n_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def latent_width(self) -> int:
        """Features of one cached row: ``[c | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def moe_args(self) -> MoEArgs:
        return MoEArgs(
            n_experts=self.n_routed_experts_published,
            top_k=self.num_experts_per_tok,
            normalize_gates=self.norm_topk_prob, dropless=True,
            scoring="sigmoid", routed_scale=self.routed_scaling_factor,
            experts_held=(self.experts_first, self.n_routed_experts))

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "PanguMoEConfig":
        names = {f.name for f in dataclasses.fields(PanguMoEConfig)}
        return PanguMoEConfig(**{k: v for k, v in d.items() if k in names})

    @staticmethod
    def tiny(**kw) -> "PanguMoEConfig":
        """2 dense + 3 MoE layers at toy widths (CPU tests): 16 experts
        of which 8 are held, top-4."""
        d = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_hidden_layers=5,
                 first_k_dense_replace=2, num_attention_heads=4,
                 num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 rope_theta=10000.0, n_routed_experts=8,
                 n_routed_experts_published=16, experts_first=4,
                 num_experts_per_tok=4, max_position_embeddings=256)
        d.update(kw)
        return PanguMoEConfig(**d)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------
def _mla_init(key, cfg: PanguMoEConfig, dtype):
    kq, ku, kd, kv, ko = jax.random.split(key, 5)
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim

    def lin(k, fin, fout):
        return linear_init(k, fin, fout, use_bias=False, dtype=dtype)

    return {
        "q_down": lin(kq, d, cfg.q_lora_rank),
        "q_norm": rms_norm_init(cfg.q_lora_rank, dtype),
        "q_up": lin(ku, cfg.q_lora_rank, h * qk),
        "kv_down": lin(kd, d, cfg.latent_width),
        "kv_norm": rms_norm_init(cfg.kv_lora_rank, dtype),
        "kv_up": lin(kv, cfg.kv_lora_rank,
                     h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o": lin(ko, h * cfg.v_head_dim, d)}


def _block_init(key, cfg: PanguMoEConfig, dtype, *, dense: bool):
    k_attn, k_ffn = jax.random.split(key)
    d = cfg.hidden_size
    p = {"attn": _mla_init(k_attn, cfg, dtype),
         **{n: rms_norm_init(d, dtype)
            for n in ("ln1", "ln1_post", "ln2", "ln2_post")}}
    if dense:
        p["mlp"] = swiglu_init(k_ffn, d, cfg.intermediate_size, dtype=dtype)
    else:
        p["moe"] = moe_held_init(
            k_ffn, d, cfg.moe_intermediate_size,
            cfg.n_routed_experts_published, held=cfg.n_routed_experts,
            shared_hidden=cfg.n_shared_experts * cfg.moe_intermediate_size,
            dtype=dtype)
    return p


def pangu_moe_init(key, cfg: PanguMoEConfig, *, dtype=jnp.float32):
    """``blocks.dense`` stacks the leading dense layers, ``blocks.moe``
    the MoE layers after them: two uniform stacks, scanned in turn."""
    k_emb, k_head, k_d, k_m = jax.random.split(key, 4)
    d = cfg.hidden_size
    return {
        "embedding": {"tok": jax.random.normal(
            k_emb, (cfg.vocab_size, d), dtype) * 0.02},
        "blocks": {
            "dense": tree_stack([
                _block_init(k, cfg, dtype, dense=True)
                for k in jax.random.split(k_d, cfg.n_dense_layers)]),
            "moe": tree_stack([
                _block_init(k, cfg, dtype, dense=False)
                for k in jax.random.split(k_m, cfg.n_moe_layers)])},
        "head": {"ln_f": rms_norm_init(d, dtype),
                 "out": linear_init(k_head, d, cfg.vocab_size,
                                    use_bias=False, dtype=dtype)},
    }


_MLA_TARGETS = ("q_down", "q_up", "kv_down", "kv_up", "o")
WEIGHT_TARGETS = (
    *(("dense", "attn", n) for n in _MLA_TARGETS),
    *(("dense", "mlp", n) for n in ("gate", "up", "down")),
    *(("moe", "attn", n) for n in _MLA_TARGETS),
    *(("moe", "moe", "shared", n) for n in ("gate", "up", "down")),
    *(("moe", "moe", "experts", n) for n in ("gate", "up", "down")))


def pangu_moe_partition_specs(tp_axis: Optional[str] = None,
                              ep_axis: Optional[str] = None):
    raise NotImplementedError(
        "pangu_ultra_moe has no partition specs yet: one device holds "
        "its share whole. Latent attention is not head-sharded (tp), "
        "and the dropless router has no exchange over an ep axis "
        "(ROADMAP M1, M3)")


# ---------------------------------------------------------------------
# embedding, head
# ---------------------------------------------------------------------
def pangu_embed(params, ids):
    with jax.named_scope("embed"):
        return jnp.take(params["embedding"]["tok"], ids, axis=0)


def pangu_logits(params, h, cfg: PanguMoEConfig):
    with jax.named_scope("final_norm"):
        h = rms_norm_apply(params["head"]["ln_f"], h, eps=cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.dot(h, params["head"]["out"]["w"]).astype(jnp.float32)


# ---------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------
def latent_rows_attend(p, x, q_nope, q_rope, pool, layer, positions, lens,
                       tables, block_size: int, cfg, cos, sin, *, form: str):
    """What every latent-attention layer does once it has its queries
    (this family's come through a low-rank projection, Ling 3.0's
    straight off ``W_q``): the run's rows ``[c | k_rope]`` from
    ``p["kv_down"]`` / ``p["kv_norm"]`` written into ``(pool, layer)``,
    then the attention in ``form`` (module docstring) with
    ``p["kv_up"]``. ``x`` [S, P, D] normed, ``q_nope`` [S, P, H, nope],
    ``q_rope`` [S, P, H, rope] rotated; ``cfg`` any config with the
    latent widths under the Hugging Face names. Returns (o [S, P, H,
    v_head_dim], pool). Opened inside the caller's ``mla`` scope."""
    h = cfg.num_attention_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
    scale = 1.0 / math.sqrt(nope + rope)
    with jax.named_scope("kv_down"):
        ckv = quantized_matmul(x, p["kv_down"])
        c = rms_norm_apply(p["kv_norm"], ckv[..., :rank],
                           eps=cfg.rms_norm_eps)
        k_rope = apply_rope(ckv[..., rank:], cos, sin)
        rows = jnp.concatenate([c, k_rope], axis=-1)
    pool = latent_write(pool, layer, rows, positions, lens,
                        block_tables=tables, block_size=block_size)
    kv_up = p["kv_up"]["w"].reshape(rank, h, nope + vd)
    if form == ABSORBED:
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("sphn,chn->sphc", q_nope, kv_up[..., :nope])
        o_lat = latent_attend_absorbed(
            q_lat, q_rope, pool, layer, positions, tables,
            block_size=block_size, scale=scale)
        with jax.named_scope("kv_up"):
            o = jnp.einsum("sphc,chv->sphv", o_lat, kv_up[..., nope:])
    elif form == MATERIALIZED:
        with jax.named_scope("kv_gather"):
            view = paged_gather(pool, layer, tables, block_size=block_size)
        o = latent_attend_materialized(
            q_nope, q_rope, view, kv_up, positions, scale=scale, v_dim=vd,
            head_group=min(HEAD_GROUP, h))
    else:
        raise ValueError(f"unknown latent-attention form {form!r}")
    return o, pool


def mla_paged(p, x, pool, layer, positions, lens, tables, block_size: int,
              cfg: PanguMoEConfig, cos, sin, *, form: str):
    """Latent attention of a run of tokens a row over the paged latent
    pool: x [S, P, D] (normed) at ``positions`` [S, P] -> (y [S, P, D],
    pool). The run's rows ``[c | k_rope]`` are written into ``layer``
    first, so the pool holds them: the ABSORBED form reads each row's
    live blocks of it in place, the MATERIALIZED one a gathered view
    (``form``: module docstring); ``cos``/``sin`` [S, P, rope]."""
    s, t, _ = x.shape
    h, vd = cfg.num_attention_heads, cfg.v_head_dim
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla"):
        with jax.named_scope("q_down"):
            cq = rms_norm_apply(p["q_norm"],
                                quantized_matmul(x, p["q_down"]),
                                eps=cfg.rms_norm_eps)
        with jax.named_scope("q_up"):
            q = quantized_matmul(cq, p["q_up"]).reshape(s, t, h,
                                                        nope + rope)
            q_nope = q[..., :nope]
            q_rope = apply_rope(q[..., nope:], cos[:, :, None],
                                sin[:, :, None])
        o, pool = latent_rows_attend(
            p, x, q_nope, q_rope, pool, layer, positions, lens, tables,
            block_size, cfg, cos, sin, form=form)
        with jax.named_scope("proj"):
            y = quantized_matmul(o.reshape(s, t, h * vd).astype(x.dtype),
                                 p["o"])
    return y, pool


def pangu_block(p, x, pool, layer, positions, lens, tables,
                block_size: int, cfg: PanguMoEConfig, cos, sin, *,
                form: str, experts=None, expert_layer=None):
    """One layer (module docstring) over the paged latent pool: x
    [S, P, D] -> (x, pool[, moe_stats]); the stats where the block's
    FFN is the mixture (``moe`` in its params). A run's columns at or
    beyond ``lens`` are padding: the router sends them nowhere.
    ``experts``, ``expert_layer``: the routed experts' weights where
    they are not in ``p`` but the whole MoE stack's, with this layer's
    index in it (nn/moe.py says why a layer scan keeps them whole)."""
    eps = cfg.rms_norm_eps
    a, pool = mla_paged(
        p["attn"], rms_norm_apply(p["ln1"], x, eps=eps), pool, layer,
        positions, lens, tables, block_size, cfg, cos, sin, form=form)
    x = x + rms_norm_apply(p["ln1_post"], a, eps=eps)
    u = rms_norm_apply(p["ln2"], x, eps=eps)
    if "moe" not in p:
        with jax.named_scope("mlp"):
            m = swiglu_apply(p["mlp"], u)
        return x + rms_norm_apply(p["ln2_post"], m, eps=eps), pool
    with jax.named_scope("moe"):
        mask = jnp.arange(x.shape[1])[None, :] < lens[:, None]
        moe = p["moe"] if experts is None else {**p["moe"],
                                                 "experts": experts}
        m, _, stats = moe_apply(moe, u, cfg.moe_args, return_stats=True,
                                token_mask=mask, expert_layer=expert_layer)
    return x + rms_norm_apply(p["ln2_post"], m, eps=eps), pool, stats
