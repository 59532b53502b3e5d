"""Llama-family causal LM: RMSNorm + rotary + SwiGLU + GQA.

Beyond the reference (its model zoo is ViT + GPT-2 only,
SURVEY.md §2.4) — this is the "another model family" extension, built to
demonstrate that the framework's machinery is model-agnostic: the block
plugs into the SAME stacked-scan runner (nn/transformer.py
stacked_blocks_apply via ``body_fn``), the same strategies, trainers,
LoRA, ZeRO and flash/ring attention paths GPT-2 uses.

Weights are stored [in, out] (x @ w). HF Llama checkpoints store torch
Linear [out, in]; the import path transposes
(:func:`llama_from_hf_state`). Logits verified against HF
``LlamaForCausalLM`` on identical weights (tests/test_llama.py).

TP sharding: q/k/v column-sharded by (kv-)heads, o row-sharded with one
psum; gate/up column- and down row-sharded (one psum) — the same
Megatron pattern as GPT-2. Requires ``n_kv_heads % tp == 0``.

SP: rope uses GLOBAL positions (sp-offset like gpt2_embed's wpe
lookup), and since rope is applied to q/k BEFORE attention, the
ring/zigzag/ulysses paths run unchanged on the rotated tensors.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from quintnet_tpu.core.pytree import tree_stack
from quintnet_tpu.nn.attention import (_masked_sdpa, apply_rope,
                                       local_attention, repeat_kv,
                                       rope_cos_sin, sdpa)
from quintnet_tpu.nn.layers import (cast_floating, linear_init,
                                    quantized_matmul, rms_norm_apply,
                                    rms_norm_init, swiglu_apply,
                                    swiglu_init)
from quintnet_tpu.nn.moe import moe_apply, moe_init, moe_specs
from quintnet_tpu.nn.transformer import stacked_blocks_apply

from quintnet_tpu.models.gpt2 import (clm_loss, clm_loss_sp,  # shared CLM
                                      clm_loss_vp, mask_padded_cols)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_positions: int = 2048          # max_position_embeddings
    dim: int = 2048                  # hidden_size
    n_layers: int = 16
    n_heads: int = 32
    n_kv_heads: int = 8              # GQA groups (== n_heads -> MHA)
    intermediate_size: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = True      # Llama-3.2-1B ties; 7B+ do not
    scan_unroll: int = 1
    # --- MoE (0 = dense): every block's SwiGLU becomes a top-k routed
    # mixture of SwiGLU experts (Mixtral-style; nn/moe.py swiglu expert
    # type), shardable over the ``ep`` mesh axis
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    expert_capacity: Optional[int] = None
    aux_loss_weight: float = 1e-2
    router_type: str = "topk"  # or "expert_choice" (nn/moe.py)
    # --- vocab parallelism: shard the token table (and untied lm head)
    # over tp — at Llama-3's 128256-token vocab the replicated table is
    # the single largest tensor, and the vp loss (models/gpt2.py
    # clm_loss_vp) never materialises full [B, S, V] logits on any
    # rank. Same semantics as GPT2Config.vocab_parallel; requires
    # (padded_)vocab_size % tp == 0 (use padded_vocab_size to round up;
    # padded columns are masked out of the softmax).
    vocab_parallel: bool = False
    padded_vocab_size: Optional[int] = None
    # packed-document isolation: derive attention segment ids from
    # input_ids (new segment after each occurrence of this token) and
    # mask cross-document attention — models/gpt2.py segment_ids_from_input
    # semantics. None = cross-document attention (pretraining default).
    segment_eos_id: Optional[int] = None
    # llama3-style rope scaling (None = unscaled). Tuple (hashable — the
    # config is a jit static arg): (factor, low_freq_factor,
    # high_freq_factor, original_max_position). HF applies this when
    # config.rope_scaling["rope_type"] == "llama3"; real 3.1/3.2
    # checkpoints SHIP with it, so ignoring it silently rotates q/k by
    # wrong angles (round-4 review finding).
    rope_scaling: Optional[Tuple[float, float, float, int]] = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def table_vocab_size(self) -> int:
        """tok table rows (padded vocab when padding is configured)."""
        return self.padded_vocab_size or self.vocab_size

    @property
    def moe_args(self):
        if self.n_experts <= 0:
            return None
        if self.router_type == "expert_choice":
            raise ValueError(
                "expert_choice routing is non-causal and unsupported "
                "for the causal LM families; use router_type='topk' "
                "(see nn/moe.py MoEArgs.router)")
        from quintnet_tpu.nn.moe import MoEArgs

        return MoEArgs(n_experts=self.n_experts, top_k=self.expert_top_k,
                       capacity_factor=self.capacity_factor,
                       capacity=self.expert_capacity,
                       aux_weight=self.aux_loss_weight,
                       router=self.router_type)

    @staticmethod
    def llama32_1b() -> "LlamaConfig":
        # vocab_size matches the real Llama-3.2-1B checkpoint (128256);
        # tie_embeddings=True (the default above) also matches 3.2-1B.
        return LlamaConfig(vocab_size=128256, n_positions=131072,
                           rope_scaling=(32.0, 1.0, 4.0, 8192))

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, n_positions=8192, dim=4096,
                           n_layers=32, n_heads=32, n_kv_heads=8,
                           intermediate_size=14336, rope_theta=500000.0,
                           tie_embeddings=False)

    @staticmethod
    def llama_160m() -> "LlamaConfig":
        """GPT-2-base-comparable geometry for cross-family benchmarking
        (not a released Llama size)."""
        return LlamaConfig(vocab_size=32000, n_positions=2048, dim=768,
                           n_layers=12, n_heads=12, n_kv_heads=4,
                           intermediate_size=2048, rope_theta=10000.0,
                           tie_embeddings=True)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        d = dict(vocab_size=128, n_positions=64, dim=32, n_layers=2,
                 n_heads=4, n_kv_heads=2, intermediate_size=64,
                 rope_theta=10000.0, tie_embeddings=False)
        d.update(kw)
        return LlamaConfig(**d)

    @staticmethod
    def from_hf_config(hf) -> "LlamaConfig":
        """Map a transformers LlamaConfig (incl. llama3 rope scaling;
        other rope_type values are rejected loudly rather than silently
        producing wrong rotations)."""
        scaling = None
        rs = getattr(hf, "rope_scaling", None)
        if rs:
            kind = rs.get("rope_type", rs.get("type"))
            if kind != "llama3":
                raise NotImplementedError(
                    f"rope_scaling type {kind!r} not supported "
                    "(llama3 only)")
            scaling = (float(rs["factor"]),
                       float(rs.get("low_freq_factor", 1.0)),
                       float(rs.get("high_freq_factor", 4.0)),
                       int(rs.get("original_max_position_embeddings",
                                  8192)))
        return LlamaConfig(
            vocab_size=hf.vocab_size,
            n_positions=hf.max_position_embeddings,
            dim=hf.hidden_size,
            n_layers=hf.num_hidden_layers,
            n_heads=hf.num_attention_heads,
            n_kv_heads=hf.num_key_value_heads,
            intermediate_size=hf.intermediate_size,
            rope_theta=hf.rope_theta,
            rms_eps=hf.rms_norm_eps,
            tie_embeddings=hf.tie_word_embeddings,
            rope_scaling=scaling,
        )


def llama_upcycle_to_moe(params, cfg: LlamaConfig, key=None):
    """Sparse upcycling: dense Llama params -> SwiGLU-MoE params for a
    config with ``n_experts > 0``. Every expert starts as a copy of the
    dense SwiGLU; routers start near-zero so initial routing is
    ~uniform (same recipe as gpt2_upcycle_to_moe)."""
    if cfg.n_experts <= 0 or "moe" in params["blocks"]:
        return params
    key = key if key is not None else jax.random.key(0)
    E = cfg.n_experts
    blocks = dict(params["blocks"])
    mlp = blocks.pop("mlp")
    L = mlp["gate"]["w"].shape[0]

    def per_expert(x):  # [L, D, H] -> [L, E, D, H]
        return jnp.repeat(x[:, None], E, axis=1)

    blocks["moe"] = {
        "router": {"w": 1e-2 * jax.random.normal(
            key, (L, cfg.dim, E), jnp.float32)},
        "wg": per_expert(mlp["gate"]["w"]),
        "wu": per_expert(mlp["up"]["w"]),
        "wd": per_expert(mlp["down"]["w"]),
    }
    return {**params, "blocks": blocks}


def llama_to_hf_state(params, cfg: LlamaConfig):
    """Inverse of :func:`llama_from_hf_state`: this layout -> an HF
    LlamaForCausalLM state dict of numpy arrays ([out, in] Linear
    weights), loadable via ``model.load_state_dict`` after wrapping in
    torch tensors. Dense configs only (HF has no SwiGLU-MoE Llama)."""
    import numpy as np

    if "moe" in params["blocks"]:
        raise ValueError("HF export supports dense Llama only")
    out = {"model.embed_tokens.weight":
           np.asarray(params["embedding"]["tok"]),
           "model.norm.weight":
           np.asarray(params["head"]["ln_f"]["scale"])}
    if not cfg.tie_embeddings:
        out["lm_head.weight"] = np.asarray(params["head"]["lm"]["w"]).T
    b = params["blocks"]
    for i in range(cfg.n_layers):
        pre = f"model.layers.{i}."
        out[pre + "input_layernorm.weight"] = \
            np.asarray(b["ln1"]["scale"][i])
        out[pre + "post_attention_layernorm.weight"] = \
            np.asarray(b["ln2"]["scale"][i])
        for src, dst in (("q", "self_attn.q_proj"),
                         ("k", "self_attn.k_proj"),
                         ("v", "self_attn.v_proj"),
                         ("o", "self_attn.o_proj")):
            out[pre + dst + ".weight"] = \
                np.asarray(b["attn"][src]["w"][i]).T
        for src, dst in (("gate", "mlp.gate_proj"), ("up", "mlp.up_proj"),
                         ("down", "mlp.down_proj")):
            out[pre + dst + ".weight"] = \
                np.asarray(b["mlp"][src]["w"][i]).T
    return out


def llama3_scaled_inv_freq(cfg: LlamaConfig):
    """Rope inverse frequencies with the llama3 wavelength-dependent
    scaling (HF _compute_llama3_parameters): high-frequency lanes keep
    their period, low-frequency lanes stretch by ``factor``, the band in
    between interpolates smoothly. None scaling -> plain 1/theta^(2i/d).
    Trace-time constant."""
    hd = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta
                 ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    if cfg.rope_scaling is None:
        return inv
    factor, low_f, high_f, orig_max = cfg.rope_scaling
    low_wavelen = orig_max / low_f
    high_wavelen = orig_max / high_f
    wavelen = 2.0 * math.pi / inv
    smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    scaled = (1.0 - smooth) * inv / factor + smooth * inv
    out = jnp.where(wavelen > low_wavelen, inv / factor, inv)
    return jnp.where((wavelen <= low_wavelen) & (wavelen >= high_wavelen),
                     scaled, out)


def llama_rope_tables(positions, cfg: LlamaConfig):
    """(cos, sin) for this config at ``positions`` — the single place
    every path (training forward, prefill, decode) gets rope from."""
    return rope_cos_sin(positions, cfg.head_dim, theta=cfg.rope_theta,
                        inv_freq=llama3_scaled_inv_freq(cfg))


def _block_init(key, cfg: LlamaConfig, dtype):
    kq, kk, kv, ko, km = jax.random.split(key, 5)
    d, hd = cfg.dim, cfg.head_dim
    return {
        "ln1": rms_norm_init(d, dtype),
        "attn": {
            "q": linear_init(kq, d, cfg.n_heads * hd, use_bias=False,
                             dtype=dtype),
            "k": linear_init(kk, d, cfg.n_kv_heads * hd, use_bias=False,
                             dtype=dtype),
            "v": linear_init(kv, d, cfg.n_kv_heads * hd, use_bias=False,
                             dtype=dtype),
            "o": linear_init(ko, cfg.n_heads * hd, d, use_bias=False,
                             dtype=dtype),
        },
        "ln2": rms_norm_init(d, dtype),
        **({"moe": moe_init(km, d, cfg.intermediate_size, cfg.n_experts,
                            dtype=dtype, expert_type="swiglu")}
           if cfg.n_experts > 0 else
           {"mlp": swiglu_init(km, d, cfg.intermediate_size, dtype=dtype)}),
    }


def llama_init(key, cfg: LlamaConfig, *, dtype=jnp.float32):
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    blocks = tree_stack([
        _block_init(bk, cfg, dtype)
        for bk in jax.random.split(k_blocks, cfg.n_layers)])
    params: Dict[str, Any] = {
        "embedding": {"tok": jax.random.normal(
            k_emb, (cfg.table_vocab_size, cfg.dim), dtype) * 0.02},
        "blocks": blocks,
        "head": {"ln_f": rms_norm_init(cfg.dim, dtype)},
    }
    if not cfg.tie_embeddings:
        params["head"]["lm"] = linear_init(
            k_head, cfg.dim, cfg.table_vocab_size, use_bias=False,
            dtype=dtype)
    return params


def llama_qkv(p_attn, a_in, cfg: LlamaConfig, cos, sin, *, tp: int = 1,
              lora=None, lora_scale=None):
    """Projections + rope, shared by training forward, prefill and
    decode: normalized input [B, S, D] -> (q [B, Hq/tp, S, hd] rotated,
    k [B, Hkv/tp, S, hd] rotated, v) — k/v UNrepeated (GQA).

    ``lora``/``lora_scale``: per-slot packed adapters for the serving
    multi-LoRA path (nn/layers.lora_delta) — each present q/k/v target
    adds its low-rank delta on the projection, BEFORE the head reshape
    and rope (exactly where a merged weight would land)."""
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads} and "
            f"n_kv_heads={cfg.n_kv_heads} (Megatron head sharding)")
    b, s, _ = a_in.shape
    hd = cfg.head_dim

    def heads(name, n):
        y = quantized_matmul(a_in, p_attn[name])
        if lora is not None and name in lora:
            from quintnet_tpu.nn.layers import lora_delta

            y = y + lora_delta(a_in, lora[name], lora_scale)
        return y.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = apply_rope(heads("q", cfg.n_heads // tp), cos, sin)
    k = apply_rope(heads("k", cfg.n_kv_heads // tp), cos, sin)
    return q, k, heads("v", cfg.n_kv_heads // tp)


def llama_attn_residual(p_attn, x, o, *, tp_axis: Optional[str] = None,
                        lora=None, lora_scale=None):
    """[B, H, S, hd] attention output -> o-proj (+tp psum) + residual.
    ``lora``: an ``o`` target adds its per-slot delta before the psum
    (row-parallel partial sums compose — nn/layers.lora_delta)."""
    b = o.shape[0]
    o = o.transpose(0, 2, 1, 3).reshape(b, o.shape[2], -1)
    y = quantized_matmul(o, p_attn["o"])
    if lora is not None and "o" in lora:
        from quintnet_tpu.nn.layers import lora_delta

        y = y + lora_delta(o, lora["o"], lora_scale)
    if tp_axis is not None:
        y = lax.psum(y, tp_axis)
    return x + y


def llama_mlp_residual(p, x, cfg: LlamaConfig, *,
                       tp_axis: Optional[str] = None,
                       ep_axis: Optional[str] = None,
                       lora=None, lora_scale=None,
                       return_stats: bool = False):
    """-> (x + FFN(ln2(x)), moe_aux) — aux is 0.0 for dense blocks.
    THE one FFN-residual implementation for training forward, prefill
    and decode (a fix here fixes all three). ``lora``: per-slot packed
    gate/up/down adapters (serving multi-LoRA; MoE blocks have no LoRA
    targets and ignore it). ``return_stats`` (serving): widen the
    return to (x, aux, routing_stats_or_None) — the MoE routing-stats
    dict (nn/moe.py moe_apply) the engine's metrics ledger reads."""
    h = rms_norm_apply(p["ln2"], x, eps=cfg.rms_eps)
    if "moe" in p:
        if return_stats:
            y, aux, stats = moe_apply(p["moe"], h, cfg.moe_args,
                                      ep_axis=ep_axis, tp_axis=tp_axis,
                                      return_stats=True)
            return x + y, aux, stats
        y, aux = moe_apply(p["moe"], h, cfg.moe_args, ep_axis=ep_axis,
                           tp_axis=tp_axis)
        return x + y, aux
    out = x + swiglu_apply(p["mlp"], h, tp_axis=tp_axis, lora=lora,
                           lora_scale=lora_scale)
    if return_stats:
        return out, jnp.zeros((), jnp.float32), None
    return out, jnp.zeros((), jnp.float32)


def llama_block_apply(p, x, cfg: LlamaConfig, *, cos, sin,
                      tp_axis: Optional[str] = None,
                      sp_axis: Optional[str] = None, sp_mode: str = "ring",
                      ep_axis: Optional[str] = None,
                      key=None, segment_ids=None):
    """Returns ``x`` for dense configs, ``(x, aux)`` for MoE (the
    stacked-scan runner's moe path accumulates aux per layer)."""
    del key  # llama has no dropout
    tp = 1 if tp_axis is None else lax.axis_size(tp_axis)
    a_in = rms_norm_apply(p["ln1"], x, eps=cfg.rms_eps)
    q, k, v = llama_qkv(p["attn"], a_in, cfg, cos, sin, tp=tp)
    rep = q.shape[1] // k.shape[1]
    k, v = repeat_kv(k, rep), repeat_kv(v, rep)

    if sp_axis is not None:
        from quintnet_tpu.ops.ring_attention import (ring_attention,
                                                     zigzag_ring_attention)
        from quintnet_tpu.ops.ulysses_attention import ulysses_attention

        if sp_mode == "ulysses":
            o = ulysses_attention(q, k, v, axis=sp_axis, causal=True,
                                  segment_ids=segment_ids)
        elif sp_mode == "zigzag":
            o = zigzag_ring_attention(q, k, v, axis=sp_axis, causal=True,
                                      segment_ids=segment_ids)
        else:
            o = ring_attention(q, k, v, axis=sp_axis, causal=True,
                               segment_ids=segment_ids)
    else:
        o = local_attention(q, k, v, causal=True, segment_ids=segment_ids)

    x = llama_attn_residual(p["attn"], x, o, tp_axis=tp_axis)
    x, aux = llama_mlp_residual(p, x, cfg, tp_axis=tp_axis,
                                ep_axis=ep_axis)
    # runner pmeans the aux sum over sp (stacked_blocks_apply moe path)
    return (x, aux) if cfg.n_experts > 0 else x


def llama_block_prefill(p, x, cfg: LlamaConfig, cos, sin,
                        tp_axis: Optional[str] = None):
    """Causal block forward that also returns this layer's UNrepeated
    (k, v) [B, Hkv(/tp), S, hd] for the decode cache. Under ``tp_axis``
    heads are LOCAL (head-sharded cache) with the RowParallel psum in
    the residual."""
    tp = 1 if tp_axis is None else lax.axis_size(tp_axis)
    a_in = rms_norm_apply(p["ln1"], x, eps=cfg.rms_eps)
    q, k, v = llama_qkv(p["attn"], a_in, cfg, cos, sin, tp=tp)
    rep = q.shape[1] // k.shape[1]
    o = sdpa(q, repeat_kv(k, rep), repeat_kv(v, rep), causal=True)
    x = llama_attn_residual(p["attn"], x, o, tp_axis=tp_axis)
    x, _aux = llama_mlp_residual(p, x, cfg, tp_axis=tp_axis)
    return x, (k, v)


def llama_block_prefill_paged_sp(p, x, kc, vc, start, t0,
                                 cfg: LlamaConfig, cos, sin, *,
                                 sp_axis: str, layer,
                                 tp_axis: Optional[str] = None,
                                 block_tables=None,
                                 block_size: Optional[int] = None,
                                 kv_scales=None, policy=None):
    """Sequence-parallel chunked prefill block (the serve engine's
    long-context path): x [1, Pl, D] is this sp rank's slice of the
    chunk's hidden states; ``cos``/``sin`` [Pl, hd] must be built from
    the rank's LOCAL absolute positions (``start + rank*Pl +
    arange(Pl)``) so rope lands exactly where the dense path puts it.
    Attention runs through nn/attention.ring_paged_prefill — K/V
    sharded over ``sp_axis`` during the score pass (GQA UNrepeated on
    the wire), reassembled by one all_gather for the scatter into
    ``layer`` of the sp-replicated pool. Returns
    (x, (kc, vc[, k_scale, v_scale]))."""
    from quintnet_tpu.nn.attention import _pool_tuple, ring_paged_prefill

    tp = 1 if tp_axis is None else lax.axis_size(tp_axis)
    a_in = rms_norm_apply(p["ln1"], x, eps=cfg.rms_eps)
    q, k, v = llama_qkv(p["attn"], a_in, cfg, cos, sin, tp=tp)
    o, pools = ring_paged_prefill(
        q, k, v, start, t0, _pool_tuple(kc, vc, kv_scales), layer,
        sp_axis=sp_axis, block_tables=block_tables, block_size=block_size,
        policy=policy)
    x = llama_attn_residual(p["attn"], x, o, tp_axis=tp_axis)
    x, _aux = llama_mlp_residual(p, x, cfg, tp_axis=tp_axis)
    return x, pools


def llama_block_verify_paged(p, x, kc, vc, positions, tail_lens,
                             cfg: LlamaConfig, cos, sin, *, layer,
                             tp_axis: Optional[str] = None,
                             ep_axis: Optional[str] = None,
                             block_tables=None,
                             block_size: Optional[int] = None,
                             lora=None, lora_scale=None,
                             kv_scales=None, policy=None,
                             attn_kernel: str = "xla"):
    """The paged block step of every serving program
    (nn/attention.paged_attend): x [S, P, D] per-slot token runs at
    absolute ``positions`` [S, P] — a decode step at P == 1, a
    (chunked) prefill at S == 1, speculative decoding's scoring run
    between (serve/spec.py). ``kc``/``vc`` are the WHOLE pool
    [L, N_blocks*block_size, F] (UNrepeated kv heads, LOCAL under tp),
    written and read at ``layer``. Every row's (k, v) run scatters
    through its ``block_tables`` row (pad columns masked to the null
    block by ``tail_lens``); attention gathers each row's whole history
    back, repeats the kv heads on the gathered view and masks causally
    against absolute positions. ``cos``/``sin`` (broadcastable to
    [S, 1, P, hd]) must be built from the SAME absolute positions.
    ``lora``/``lora_scale``: this layer's packed per-slot adapters.
    ``kv_scales``/``policy``: scaled KV layout (serve/kv_quant.py).
    Returns (x, (kc, vc[, k_scale, v_scale][, moe_stats])).
    ``attn_kernel="pallas"``: the fused block-table-walking kernel
    (ops/paged_attention.py)."""
    from quintnet_tpu.nn.attention import _pool_tuple, paged_attend

    tp = 1 if tp_axis is None else lax.axis_size(tp_axis)
    attn_lora = lora.get("attn") if lora is not None else None
    a_in = rms_norm_apply(p["ln1"], x, eps=cfg.rms_eps)
    q, k, v = llama_qkv(p["attn"], a_in, cfg, cos, sin, tp=tp,
                        lora=attn_lora, lora_scale=lora_scale)
    o, pools = paged_attend(
        q, k, v, _pool_tuple(kc, vc, kv_scales), layer, positions,
        tail_lens, block_tables, block_size=block_size, policy=policy,
        attn_kernel=attn_kernel)
    x = llama_attn_residual(p["attn"], x, o, tp_axis=tp_axis,
                            lora=attn_lora, lora_scale=lora_scale)
    x, _aux, stats = llama_mlp_residual(
        p, x, cfg, tp_axis=tp_axis, ep_axis=ep_axis,
        lora=lora.get("mlp") if lora is not None else None,
        lora_scale=lora_scale, return_stats=True)
    if "moe" in p:
        return x, (*pools, stats)
    return x, pools


def llama_block_decode(p, x, kc, vc, pos, cfg: LlamaConfig, cos, sin,
                       tp_axis: Optional[str] = None):
    """One cached token on the dense single-request cache
    (models/llama_generate.py): x [B, 1, D], caches [B, Hkv(/tp), T, hd]
    -> (x, (kc, vc)). Masked attention over cache[:pos]; the cache stays
    UNrepeated — kv-head repeat happens on the read. The
    continuous-batching decode step is :func:`llama_block_verify_paged`
    at one token a row."""
    tp = 1 if tp_axis is None else lax.axis_size(tp_axis)
    a_in = rms_norm_apply(p["ln1"], x, eps=cfg.rms_eps)
    q, k, v = llama_qkv(p["attn"], a_in, cfg, cos, sin, tp=tp)
    kc = lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), pos,
                                         axis=2)
    vc = lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), pos,
                                         axis=2)
    rep = q.shape[1] // kc.shape[1]
    kf, vf = repeat_kv(kc, rep), repeat_kv(vc, rep)
    valid = jnp.arange(kf.shape[2])[None, None, None, :] <= pos
    o = _masked_sdpa(q, kf, vf, valid)
    x = llama_attn_residual(p["attn"], x, o, tp_axis=tp_axis)
    x, _aux = llama_mlp_residual(p, x, cfg, tp_axis=tp_axis)
    return x, (kc, vc)


def _positions(b, s, sp_axis: Optional[str]):
    """Global position ids for the local sequence shard (sp offsets the
    shard like gpt2_embed's wpe lookup; rope must see global positions)."""
    pos = jnp.arange(s)
    if sp_axis is not None:
        pos = pos + lax.axis_index(sp_axis) * s
    return pos


def llama_hidden(params, input_ids, cfg: LlamaConfig, *,
                 tp_axis: Optional[str] = None,
                 sp_axis: Optional[str] = None, sp_mode: str = "ring",
                 ep_axis: Optional[str] = None,
                 remat: "bool | str" = False,
                 fsdp=None):
    """-> (final hidden states, moe aux total — 0.0 for dense)."""
    b, s = input_ids.shape
    if cfg.vocab_parallel and tp_axis is not None:
        from quintnet_tpu.parallel.tp import vocab_parallel_embedding

        h = vocab_parallel_embedding(
            {"table": params["embedding"]["tok"]}, input_ids,
            axis=tp_axis)
    else:
        h = jnp.take(params["embedding"]["tok"], input_ids, axis=0)
    cos, sin = llama_rope_tables(_positions(b, s, sp_axis), cfg)
    import functools

    from quintnet_tpu.models.gpt2 import segment_ids_from_input

    seg = segment_ids_from_input(input_ids, cfg, sp_axis=sp_axis)
    body = functools.partial(llama_block_apply, cfg=cfg, cos=cos, sin=sin,
                             tp_axis=tp_axis, sp_axis=sp_axis,
                             sp_mode=sp_mode,
                             ep_axis=ep_axis, segment_ids=seg)
    out = stacked_blocks_apply(
        params["blocks"], h, num_heads=0, body_fn=body, remat=remat,
        moe_args=cfg.moe_args, sp_axis=sp_axis,
        scan_unroll=cfg.scan_unroll, fsdp=fsdp)
    return out if cfg.n_experts > 0 else (out, jnp.zeros((), jnp.float32))


def llama_logits(params, h, cfg: LlamaConfig):
    """ln_f + lm head (tied: tok.T). With a padded vocab and a
    FULL-width table the padding columns are -inf-masked (single-device
    / no-tp fallback of a vocab_parallel config); vocab-SHARDED tables
    are masked inside clm_loss_vp, which knows the shard offset (same
    split of responsibilities as models/gpt2.py gpt2_logits)."""
    h = rms_norm_apply(params["head"]["ln_f"], h, eps=cfg.rms_eps)
    w = (params["embedding"]["tok"].T if cfg.tie_embeddings
         else params["head"]["lm"]["w"])
    logits = jnp.dot(h, w).astype(jnp.float32)
    if (cfg.padded_vocab_size
            and logits.shape[-1] == cfg.table_vocab_size):
        logits = mask_padded_cols(logits, cfg)
    return logits


def llama_apply(params, input_ids, cfg: LlamaConfig, *,
                tp_axis: Optional[str] = None,
                sp_axis: Optional[str] = None, sp_mode: str = "ring",
                ep_axis: Optional[str] = None,
                remat: "bool | str" = False):
    h, _aux = llama_hidden(params, input_ids, cfg, tp_axis=tp_axis,
                           sp_axis=sp_axis, sp_mode=sp_mode,
                           ep_axis=ep_axis, remat=remat)
    return llama_logits(params, h, cfg)


# ---------------------------------------------------------------------------
# sharding / strategy integration

def llama_partition_specs(cfg: Optional[LlamaConfig] = None, *,
                          tp_axis: Optional[str] = "tp",
                          pp_axis: Optional[str] = None,
                          ep_axis: Optional[str] = None,
                          fsdp_axis: Optional[str] = None):
    from jax.sharding import PartitionSpec as P

    t = tp_axis
    col = P(pp_axis, None, t)     # [L, in, out/tp]
    row = P(pp_axis, t, None)     # [L, in/tp, out]
    rep = P(pp_axis, None)
    blocks = {
        "ln1": {"scale": rep},
        "attn": {"q": {"w": col}, "k": {"w": col}, "v": {"w": col},
                 "o": {"w": row}},
        "ln2": {"scale": rep},
    }
    if cfg is not None and cfg.n_experts > 0:
        blocks["moe"] = moe_specs(ep_axis=ep_axis, tp_axis=t,
                                  stacked=True, pp_axis=pp_axis,
                                  expert_type="swiglu")
    else:
        blocks["mlp"] = {"gate": {"w": col}, "up": {"w": col},
                         "down": {"w": row}}
    if fsdp_axis is not None:
        from quintnet_tpu.parallel.tp import fsdp_shard_specs

        blocks = fsdp_shard_specs(blocks, fsdp_axis)
    vp = cfg is not None and cfg.vocab_parallel and tp_axis is not None
    specs = {
        # vp: vocab dim sharded over tp; grads stay un-psummed over tp
        # (train_step.py reduce_grads spec rule) — the vp loss/embed
        # psums supply the tp cotangent factor exactly once
        "embedding": {"tok": P(t, None) if vp else P()},
        "blocks": blocks,
        "head": {"ln_f": {"scale": P()}},
    }
    if cfg is None or not cfg.tie_embeddings:
        specs["head"]["lm"] = {"w": P(None, t) if vp else P()}
    return specs


def _validate_tp(cfg: LlamaConfig, tp: int, params):
    """Separate q/k/v need no qkv re-blocking (identity layout); this
    hook just validates the vp divisibility constraint with a clear
    message before shard_params hits an opaque partition error."""
    if cfg.vocab_parallel and tp > 1 and cfg.table_vocab_size % tp != 0:
        raise ValueError(
            f"vocab_parallel needs (padded_)vocab_size % tp == 0; got "
            f"{cfg.table_vocab_size} % {tp}. Set padded_vocab_size; "
            f"padded columns are masked out of the loss.")
    return params


def llama_model_spec(cfg: LlamaConfig, *, remat: "bool | str" = False,
                     sp_mode: str = "ring",
                     compute_dtype=None):
    from jax.sharding import PartitionSpec as P

    from quintnet_tpu.parallel.strategy import ModelSpec

    def cast(p):
        return cast_floating(p, compute_dtype) if compute_dtype else p

    def loss_fn(params, batch, tp_axis=None, sp_axis=None, ep_axis=None,
                key=None, fsdp_axis=None):
        del key
        input_ids, labels = batch
        import functools as _ft

        from quintnet_tpu.parallel.tp import fsdp_info

        fsdp = fsdp_info(_ft.partial(llama_partition_specs, cfg),
                         fsdp_axis, tp_axis=tp_axis, ep_axis=ep_axis)
        h, aux = llama_hidden(cast(params), input_ids, cfg,
                              tp_axis=tp_axis, sp_axis=sp_axis,
                              sp_mode=sp_mode, ep_axis=ep_axis,
                              remat=remat, fsdp=fsdp)
        logits = llama_logits(cast(params), h, cfg)
        if cfg.vocab_parallel and tp_axis is not None:
            return clm_loss_vp(
                logits, labels, tp_axis=tp_axis, sp_axis=sp_axis,
                vocab_size=(cfg.vocab_size if cfg.padded_vocab_size
                            else None)) + aux
        if sp_axis is not None:
            return clm_loss_sp(logits, labels, sp_axis=sp_axis) + aux
        return clm_loss(logits, labels) + aux

    def pipeline_fns(tp_axis=None, sp_axis=None, ep_axis=None):
        if cfg.segment_eos_id is not None:
            raise NotImplementedError(
                "segment_eos_id under pipeline parallelism is not wired "
                "(stage fns receive hidden states, not token ids); use "
                "dp/tp/ep meshes for packed-document isolation")

        def embed_fn(params, input_ids, key=None):
            del key
            tok = cast(params)["embedding"]["tok"]
            if cfg.vocab_parallel and tp_axis is not None:
                from quintnet_tpu.parallel.tp import \
                    vocab_parallel_embedding

                return vocab_parallel_embedding({"table": tok}, input_ids,
                                                axis=tp_axis)
            return jnp.take(tok, input_ids, axis=0)

        def stage_fn(blocks_local, h, key=None):
            del key
            b, s = h.shape[:2]
            cos, sin = llama_rope_tables(_positions(b, s, sp_axis),
                                         cfg)
            import functools

            body = functools.partial(
                llama_block_apply, cfg=cfg, cos=cos, sin=sin,
                tp_axis=tp_axis, sp_axis=sp_axis, sp_mode=sp_mode,
                ep_axis=ep_axis)
            return stacked_blocks_apply(cast(blocks_local), h, num_heads=0,
                                        body_fn=body, remat=remat,
                                        moe_args=cfg.moe_args,
                                        sp_axis=sp_axis,
                                        scan_unroll=cfg.scan_unroll)

        vp = cfg.vocab_parallel and tp_axis is not None
        if vp or sp_axis is not None:
            # the loss contains collectives (vp lse psums / sp
            # shift+psum), which may not sit inside the schedules'
            # lax.cond gate — split as gpt2_pipeline_fns does
            from quintnet_tpu.parallel.pp import SplitHead

            def head_reduce_fn(logits, labels, valid):
                if vp:
                    loss = clm_loss_vp(
                        logits, labels, tp_axis=tp_axis, sp_axis=sp_axis,
                        vocab_size=(cfg.vocab_size if cfg.padded_vocab_size
                                    else None))
                else:
                    loss = clm_loss_sp(logits, labels, sp_axis=sp_axis)
                return jnp.where(valid, loss, 0.0)

            return embed_fn, stage_fn, SplitHead(
                lambda params, h, labels: llama_logits(cast(params), h, cfg),
                head_reduce_fn)

        def head_loss_fn(params, h, labels):
            return clm_loss(llama_logits(cast(params), h, cfg), labels)

        return embed_fn, stage_fn, head_loss_fn

    def batch_specs(batch_axes, sp_axis=None):
        spec = P(tuple(batch_axes) if batch_axes else None, sp_axis)
        return (spec, spec)

    return ModelSpec(
        init=lambda key: llama_init(key, cfg),
        loss_fn=loss_fn,
        partition_specs=lambda tp_axis=None, pp_axis=None, ep_axis=None, \
                fsdp_axis=None:
            llama_partition_specs(cfg, tp_axis=tp_axis, pp_axis=pp_axis,
                                  ep_axis=ep_axis, fsdp_axis=fsdp_axis),
        pipeline_fns=pipeline_fns,
        to_tp_layout=lambda p, tp: _validate_tp(cfg, tp, p),
        depth=cfg.n_layers,
        batch_specs=batch_specs,
        needs_rng=False,
    )


# ---------------------------------------------------------------------------
# HF interop

def llama_from_hf_state(state: Dict[str, Any], cfg: LlamaConfig):
    """HF LlamaForCausalLM state dict (torch tensors or arrays, Linear
    weights [out, in]) -> this layout ([in, out], stacked blocks)."""
    import numpy as np

    def t(name):
        return np.asarray(state[name].detach().cpu().numpy()
                          if hasattr(state[name], "detach")
                          else state[name])

    blocks = []
    for i in range(cfg.n_layers):
        pre = f"model.layers.{i}."
        blocks.append({
            "ln1": {"scale": t(pre + "input_layernorm.weight")},
            "attn": {
                "q": {"w": t(pre + "self_attn.q_proj.weight").T},
                "k": {"w": t(pre + "self_attn.k_proj.weight").T},
                "v": {"w": t(pre + "self_attn.v_proj.weight").T},
                "o": {"w": t(pre + "self_attn.o_proj.weight").T},
            },
            "ln2": {"scale": t(pre + "post_attention_layernorm.weight")},
            "mlp": {
                "gate": {"w": t(pre + "mlp.gate_proj.weight").T},
                "up": {"w": t(pre + "mlp.up_proj.weight").T},
                "down": {"w": t(pre + "mlp.down_proj.weight").T},
            },
        })
    params = {
        "embedding": {"tok": t("model.embed_tokens.weight")},
        "blocks": tree_stack([jax.tree.map(jnp.asarray, b)
                              for b in blocks]),
        "head": {"ln_f": {"scale": t("model.norm.weight")}},
    }
    if not cfg.tie_embeddings:
        params["head"]["lm"] = {"w": t("lm_head.weight").T}
    return jax.tree.map(jnp.asarray, params)
