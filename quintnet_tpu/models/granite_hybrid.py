"""Granite 4.0-H (``granitemoehybrid``, dense): Mamba-2 layers with a
GQA attention layer among every few, a shared SwiGLU MLP in every
layer, RMSNorm, NO positional encoding, and four scalar multipliers.

With ``x`` the residual stream and ``r = residual_multiplier``::

    x0     = E[ids] * embedding_multiplier
    x      = x + r * mixer(rmsnorm_1(x))       mixer: Mamba-2 | attention
    x      = x + r * mlp(rmsnorm_2(x))         mlp(u) = W_d (silu(W_g u) * W_u u)
    logits = (rmsnorm_f(x) E^T) / logits_scaling

Attention layers: ``q = W_q u`` (heads x head_dim), ``k``/``v`` of
``num_key_value_heads`` each serving ``heads / kv_heads`` query heads,
no bias, no rotation; ``scores = q k^T * attention_multiplier``, causal,
f32 softmax. Mamba-2 layers: nn/ssm.py.

The config's field names are the Hugging Face keys, read as they are
(:meth:`GraniteHybridConfig.from_dict`). Departure in layout, not in
mathematics: HF fuses ``W_g | W_u`` as one ``input_linear``; here they
are two leaves (``mlp.gate``, ``mlp.up``), like every SwiGLU of this
repo; and the Mamba-2 ``in_proj`` is three leaves (nn/ssm.py says why).

This module holds the config, the initialiser and the per-layer
bodies in the two forms the serving engine runs (a run of tokens per
row / one token per row, both against the paged pool and the per-slot
recurrent state); the scan over the layer pattern is
serve/families.granite_hybrid_family. There is no training path: the
chunked scan has no backward here (ROADMAP M4).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from quintnet_tpu.nn.attention import paged_attend
from quintnet_tpu.nn.layers import (linear_init, quantized_matmul,
                                    rms_norm_apply, rms_norm_init,
                                    swiglu_apply, swiglu_init)
from quintnet_tpu.nn.ssm import (Mamba2Dims, mamba2_chunk, mamba2_init,
                                 mamba2_step)
from quintnet_tpu.core.pytree import tree_stack

MAMBA, ATTENTION = "mamba", "attention"


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ((MAMBA,) * 5 + (ATTENTION,)
                                    + (MAMBA,) * 4) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 131072
    position_embedding_type: str = "nope"
    attention_bias: bool = False
    tie_word_embeddings: bool = True
    num_local_experts: int = 0
    hidden_act: str = "silu"
    normalization_function: str = "rmsnorm"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unsupported = {
            "position_embedding_type": "nope", "attention_bias": False,
            "tie_word_embeddings": True, "num_local_experts": 0,
            "hidden_act": "silu", "normalization_function": "rmsnorm",
            "mamba_n_groups": 1, "mamba_conv_bias": True,
            "mamba_proj_bias": False}
        for key, only in unsupported.items():
            if getattr(self, key) != only:
                raise NotImplementedError(
                    f"granite hybrid: {key}={getattr(self, key)!r} is "
                    f"not implemented (only {only!r})")
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        if (self.mamba_n_heads * self.mamba_d_head
                != self.mamba_expand * self.hidden_size):
            raise ValueError(
                "mamba_n_heads * mamba_d_head must equal mamba_expand * "
                "hidden_size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        _ = self.pattern   # raises where the layers are not periodic

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def moe_args(self):
        return None        # dense: what the engine asks every family

    @property
    def mamba(self) -> Mamba2Dims:
        return Mamba2Dims(
            n_heads=self.mamba_n_heads, d_head=self.mamba_d_head,
            d_state=self.mamba_d_state, d_conv=self.mamba_d_conv,
            chunk=self.mamba_chunk_size, eps=self.rms_norm_eps)

    @property
    def pattern(self) -> Tuple[int, int, int]:
        """(periods, Mamba layers before, Mamba layers after the one
        attention layer of a period): 40 layers of ``[m x5, a, m x4]``
        are (4, 5, 4). The serving scan runs over periods."""
        kinds = self.layer_types
        n_attn = sum(k == ATTENTION for k in kinds)
        if (n_attn == 0 or len(kinds) % n_attn
                or set(kinds) - {MAMBA, ATTENTION}):
            raise NotImplementedError(
                f"layer_types must be '{MAMBA}' and '{ATTENTION}' in "
                f"whole periods of one attention layer each: {kinds}")
        per = len(kinds) // n_attn
        period = kinds[:per]
        if kinds != period * n_attn or period.count(ATTENTION) != 1:
            raise NotImplementedError(
                f"layer_types is not a repeated period with one "
                f"attention layer: {kinds}")
        before = period.index(ATTENTION)
        return n_attn, before, per - 1 - before

    @property
    def n_mamba_layers(self) -> int:
        periods, before, after = self.pattern
        return periods * (before + after)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "GraniteHybridConfig":
        names = {f.name for f in dataclasses.fields(GraniteHybridConfig)}
        return GraniteHybridConfig(
            **{k: v for k, v in d.items() if k in names})

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        """Two periods of ``[m, m, a, m]`` at toy widths (CPU tests)."""
        d = dict(vocab_size=128, hidden_size=64,
                 shared_intermediate_size=96, num_hidden_layers=8,
                 layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA) * 2,
                 num_attention_heads=4, num_key_value_heads=2,
                 attention_multiplier=0.0625, mamba_n_heads=8,
                 mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
                 max_position_embeddings=256)
        d.update(kw)
        return GraniteHybridConfig(**d)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------
def _mlp_norms(key, cfg, dtype):
    return {"ln1": rms_norm_init(cfg.hidden_size, dtype),
            "ln2": rms_norm_init(cfg.hidden_size, dtype),
            "mlp": swiglu_init(key, cfg.hidden_size,
                               cfg.shared_intermediate_size, dtype=dtype)}


def _mamba_block_init(key, cfg, dtype):
    k_mix, k_mlp = jax.random.split(key)
    return {"mixer": mamba2_init(k_mix, cfg.hidden_size, cfg.mamba,
                                 dtype=dtype),
            **_mlp_norms(k_mlp, cfg, dtype)}


def _attn_block_init(key, cfg, dtype):
    kq, kk, kv, ko, k_mlp = jax.random.split(key, 5)
    d, hd = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads

    def lin(k, fin, fout):
        return linear_init(k, fin, fout, use_bias=False, dtype=dtype)

    return {"attn": {"q": lin(kq, d, hq * hd), "k": lin(kk, d, hkv * hd),
                     "v": lin(kv, d, hkv * hd), "o": lin(ko, hq * hd, d)},
            **_mlp_norms(k_mlp, cfg, dtype)}


def granite_hybrid_init(key, cfg: GraniteHybridConfig, *,
                        dtype=jnp.float32):
    """``blocks.mamba`` stacks the Mamba layers in model order (leading
    ``n_mamba_layers``), ``blocks.attn`` the attention layers (leading
    periods): two uniform stacks, which the serving scan interleaves
    by the config's pattern."""
    k_emb, k_m, k_a = jax.random.split(key, 3)
    periods = cfg.pattern[0]
    return {
        "embedding": {"tok": jax.random.normal(
            k_emb, (cfg.vocab_size, cfg.hidden_size), dtype) * 0.02},
        "blocks": {
            "mamba": tree_stack([
                _mamba_block_init(k, cfg, dtype)
                for k in jax.random.split(k_m, cfg.n_mamba_layers)]),
            "attn": tree_stack([
                _attn_block_init(k, cfg, dtype)
                for k in jax.random.split(k_a, periods)])},
        "head": {"ln_f": rms_norm_init(cfg.hidden_size, dtype)},
    }


WEIGHT_TARGETS = (
    ("mamba", "mixer", "in_z"), ("mamba", "mixer", "in_xbc"),
    ("mamba", "mixer", "in_dt"), ("mamba", "mixer", "out_proj"),
    ("mamba", "mlp", "gate"), ("mamba", "mlp", "up"),
    ("mamba", "mlp", "down"),
    ("attn", "attn", "q"), ("attn", "attn", "k"), ("attn", "attn", "v"),
    ("attn", "attn", "o"),
    ("attn", "mlp", "gate"), ("attn", "mlp", "up"), ("attn", "mlp", "down"))


# ---------------------------------------------------------------------
# embedding, head, the MLP half of every layer
# ---------------------------------------------------------------------
def granite_embed(params, ids, cfg: GraniteHybridConfig):
    with jax.named_scope("embed"):
        return (jnp.take(params["embedding"]["tok"], ids, axis=0)
                * cfg.embedding_multiplier)


def granite_logits(params, h, cfg: GraniteHybridConfig):
    with jax.named_scope("final_norm"):
        h = rms_norm_apply(params["head"]["ln_f"], h,
                           eps=cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        logits = jnp.dot(h, params["embedding"]["tok"].T)
        return logits.astype(jnp.float32) / cfg.logits_scaling


def _mlp_residual(p, x, cfg: GraniteHybridConfig):
    with jax.named_scope("mlp"):
        u = rms_norm_apply(p["ln2"], x, eps=cfg.rms_norm_eps)
        return x + cfg.residual_multiplier * swiglu_apply(p["mlp"], u)


# ---------------------------------------------------------------------
# Mamba-2 layers
# ---------------------------------------------------------------------
def mamba_block_chunk(p, x, state, tail, lens, cfg: GraniteHybridConfig):
    """A run of tokens a row: x [R, T, D], the rows' state [R, H, P, N]
    and conv tail, ``lens`` [R] real tokens -> (x, state, tail)."""
    with jax.named_scope("mamba"):
        u = rms_norm_apply(p["ln1"], x, eps=cfg.rms_norm_eps)
        y, state, tail = mamba2_chunk(p["mixer"], u, state, tail, lens,
                                      cfg.mamba)
        x = x + cfg.residual_multiplier * y
    return _mlp_residual(p, x, cfg), state, tail


def mamba_block_step(p, x, state, tail, cfg: GraniteHybridConfig):
    """One token a row: x [R, 1, D] -> (x, state, tail)."""
    with jax.named_scope("mamba"):
        u = rms_norm_apply(p["ln1"], x[:, 0], eps=cfg.rms_norm_eps)
        y, state, tail = mamba2_step(p["mixer"], u, state, tail,
                                     cfg.mamba)
        x = x + cfg.residual_multiplier * y[:, None, :]
    return _mlp_residual(p, x, cfg), state, tail


# ---------------------------------------------------------------------
# attention layers: the GQA paged path without the rotation
# ---------------------------------------------------------------------
def _qkv(p, u, cfg: GraniteHybridConfig):
    """u [R, T, D] -> q [R, Hkv, G*T, hd] — the ``G = heads/kv_heads``
    query heads that share a KV head laid out as ROWS of that head's
    score matrix, so the cached view is contracted once per KV head and
    never repeated — and k, v [R, Hkv, T, hd]."""
    r, t, _ = u.shape
    hd, hkv = cfg.head_dim, cfg.num_key_value_heads
    g = cfg.num_attention_heads // hkv
    with jax.named_scope("qkv"):
        q = quantized_matmul(u, p["q"]).reshape(r, t, hkv, g, hd)
        q = q.transpose(0, 2, 3, 1, 4).reshape(r, hkv, g * t, hd)
        k, v = (quantized_matmul(u, p[n]).reshape(r, t, hkv, hd)
                .transpose(0, 2, 1, 3) for n in ("k", "v"))
    return q, k, v


def _attn_out(p, o, x, t: int, cfg: GraniteHybridConfig):
    """o [R, Hkv, G*T, hd] back to [R, T, heads*hd], W_o, residual."""
    r, hkv, _, hd = o.shape
    g = cfg.num_attention_heads // hkv
    with jax.named_scope("proj"):
        o = o.reshape(r, hkv, g, t, hd).transpose(0, 3, 1, 2, 4)
        y = quantized_matmul(o.reshape(r, t, hkv * g * hd), p["o"])
    return x + cfg.residual_multiplier * y


def attn_block_chunk(p, x, k_pool, v_pool, layer, positions, lens, tables,
                     block_size, cfg: GraniteHybridConfig, policy=None):
    """A run of tokens a row over the paged pool (one, for a decode
    step): x [R, T, D] at absolute ``positions`` [R, T]; each row's
    (k, v) scatter into ``layer`` of the whole pool through its
    ``tables`` row (columns at or past ``lens`` go to the null block),
    attention gathers the row's whole history back and masks causally
    against absolute positions (nn/attention.paged_attend)."""
    with jax.named_scope("attn"):
        u = rms_norm_apply(p["ln1"], x, eps=cfg.rms_norm_eps)
        q, k, v = _qkv(p["attn"], u, cfg)
        o, (k_pool, v_pool) = paged_attend(
            q, k, v, (k_pool, v_pool), layer, positions, lens, tables,
            block_size=block_size, policy=policy,
            scale=cfg.attention_multiplier)
        x = _attn_out(p["attn"], o, x, x.shape[1], cfg)
    return _mlp_residual(p, x, cfg), k_pool, v_pool


def granite_hybrid_partition_specs(tp_axis: Optional[str] = None,
                                   ep_axis: Optional[str] = None):
    raise NotImplementedError(
        "granite hybrid has no partition specs: the recurrent state "
        "and the Mamba-2 projections are not head-sharded (ROADMAP M4)")
