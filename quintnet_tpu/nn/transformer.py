"""Pre-LN transformer block shared by ViT and GPT-2.

Reference: utils/model.py:197-233 (ViT TransformerBlock, ReLU MLP) and
utils/GPT2/gpt2_block.py:57-188 (GPT-2, GELU MLP, causal). Both are
pre-LN residual blocks; LayerNorms are replicated across TP while
attention/MLP weights are column/row sharded.

Block params are designed to be STACKED along a leading ``depth`` axis
(core/pytree.py:tree_stack) so a model runs them with ``lax.scan`` —
one compiled block body regardless of depth — and pipeline parallelism
becomes a reshape of that axis to [pp, depth/pp, ...].
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from quintnet_tpu.nn.attention import (mha_apply, mha_decode, mha_init,
                                       mha_prefill_paged_sp,
                                       mha_verify_paged)
from quintnet_tpu.nn.layers import (
    gelu,
    layer_norm_apply,
    layer_norm_init,
    mlp_apply,
    mlp_init,
)
from quintnet_tpu.nn.moe import MoEArgs, moe_apply, moe_init


def block_init(key, dim: int, *, mlp_hidden: int, dtype=jnp.float32,
               moe: Optional[MoEArgs] = None):
    """``moe``: replace the dense MLP with a Mixture-of-Experts FFN
    (every block — Switch-Transformer style; nn/moe.py)."""
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": layer_norm_init(dim, dtype),
        "attn": mha_init(k1, dim, dtype=dtype),
        "ln2": layer_norm_init(dim, dtype),
    }
    if moe is not None:
        p["moe"] = moe_init(k2, dim, mlp_hidden, moe.n_experts, dtype=dtype)
    else:
        p["mlp"] = mlp_init(k2, dim, mlp_hidden, dtype=dtype)
    return p


def block_apply(
    p,
    x,
    *,
    num_heads: int,
    causal: bool = False,
    act: Callable = gelu,
    tp_axis: Optional[str] = None,
    sp_axis: Optional[str] = None,
    sp_mode: str = "ring",
    moe_args: Optional[MoEArgs] = None,
    ep_axis: Optional[str] = None,
    attn_pdrop: float = 0.0,
    resid_pdrop: float = 0.0,
    key=None,
    segment_ids=None,
):
    """Returns ``x`` for dense blocks, ``(x, aux_loss)`` when
    ``moe_args`` is given (the MoE load-balance term, device-local).

    ``key``: per-layer dropout key (training); None disables dropout
    (eval / the deterministic default)."""
    k_attn = k_mlp = None
    if key is not None:
        k_attn, k_mlp = jax.random.split(key)
    with jax.named_scope("attn"):
        x = x + mha_apply(
            p["attn"],
            layer_norm_apply(p["ln1"], x),
            num_heads=num_heads,
            causal=causal,
            tp_axis=tp_axis,
            sp_axis=sp_axis,
            sp_mode=sp_mode,
            attn_pdrop=attn_pdrop,
            resid_pdrop=resid_pdrop,
            key=k_attn,
            segment_ids=segment_ids,
        )
    with jax.named_scope("mlp"):
        h = layer_norm_apply(p["ln2"], x)
        if moe_args is not None:
            y, aux = moe_apply(p["moe"], h, moe_args, ep_axis=ep_axis,
                               tp_axis=tp_axis, act=act)
            if k_mlp is not None and resid_pdrop > 0.0:
                # Same resid_pdrop as the dense branch so MoE and dense
                # configs with identical dropout settings regularize
                # alike. Safe post-psum: the combined output is
                # replicated across tp ranks, so the mask agrees on
                # every rank.
                from quintnet_tpu.nn.layers import dropout

                y = dropout(k_mlp, y, resid_pdrop, deterministic=False)
            return x + y, aux
        return x + mlp_apply(p["mlp"], h, act=act, tp_axis=tp_axis,
                             pdrop=resid_pdrop, key=k_mlp)


def stacked_blocks_apply(
    stacked_params,
    x,
    *,
    num_heads: int,
    causal: bool = False,
    act: Callable = gelu,
    tp_axis: Optional[str] = None,
    sp_axis: Optional[str] = None,
    sp_mode: str = "ring",
    remat: "bool | str" = False,
    moe_args: Optional[MoEArgs] = None,
    ep_axis: Optional[str] = None,
    attn_pdrop: float = 0.0,
    resid_pdrop: float = 0.0,
    key=None,
    scan_unroll: int = 1,
    body_fn: Optional[Callable] = None,
    segment_ids=None,
    fsdp=None,
):
    """Run a [depth, ...]-stacked block pytree with lax.scan.

    ``body_fn(block_params, h, key=...)``: override the per-layer body
    (models/llama.py plugs its RMSNorm/rope/SwiGLU block in here and
    inherits the scan/remat/unroll machinery); default is the GPT-2/ViT
    pre-LN ``block_apply`` configured by the kwargs below.

    Replaces the reference's Python loop over ``model.blocks``
    (utils/model.py:325-380) — one traced block body, depth iterations,
    constant compile time in depth. ``remat=True`` rematerialises each
    block in backward (jax.checkpoint), trading FLOPs for HBM;
    ``remat="dots"`` checkpoints with the ``dots_saveable`` policy —
    matmul outputs are kept, only elementwise work is recomputed
    (more live memory than full remat, less backward recompute).

    ``scan_unroll``: lax.scan unroll factor — >1 lets XLA software-
    pipeline across adjacent layer iterations at the cost of code size.

    With ``moe_args`` every block's MLP is a MoE FFN and the return is
    ``(out, aux_total)`` — the summed load-balance loss across layers
    (pmeaned over ``sp_axis`` so its value is sequence-replication
    consistent with the main loss).

    ``key``: dropout base key; split into one key per layer (rides the
    scan alongside the params). None -> deterministic.

    ``fsdp``: ``(axis_name, gather_dims_tree)`` — ZeRO-3/FSDP: the
    stacked block params arrive SHARDED over the axis (one dim per
    leaf, parallel/tp.py fsdp_shard_specs) and each layer is
    all-gathered HERE, inside the scan body, just before use — O(one
    layer) transient full weights instead of the whole stack. The
    all_gather's vjp is a reduce-scatter, so gradients leave the body
    already sharded (train_step's reduce rule divides the dp sum back
    to a mean) and optimizer state shards for free. The gather sits
    INSIDE the remat boundary, so backward re-gathers rather than
    storing full layers. ``gather_dims_tree``: per-leaf PER-LAYER dim
    to gather (-1 = leaf not sharded; parallel/tp.py
    fsdp_gather_dims).
    """
    depth = jax.tree.leaves(stacked_params)[0].shape[0]
    body = body_fn if body_fn is not None else partial(
        block_apply,
        num_heads=num_heads,
        causal=causal,
        act=act,
        tp_axis=tp_axis,
        sp_axis=sp_axis,
        sp_mode=sp_mode,
        moe_args=moe_args,
        ep_axis=ep_axis,
        attn_pdrop=attn_pdrop,
        resid_pdrop=resid_pdrop,
        segment_ids=segment_ids,
    )
    if fsdp is not None:
        from quintnet_tpu.core import collectives as cc

        f_axis, f_dims = fsdp
        inner_body = body

        def body(blk_p, h, key=None):
            blk_p = jax.tree.map(
                lambda x, dim: (cc.all_gather(x, f_axis, gather_dim=dim)
                                if dim >= 0 else x),
                blk_p, f_dims)
            return inner_body(blk_p, h, key=key)

    if remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    elif remat:
        body = jax.checkpoint(body)

    layer_keys = (jax.random.split(key, depth)
                  if key is not None else jnp.zeros((depth, 2), jnp.uint32))
    use_key = key is not None

    if moe_args is not None:
        def scan_moe(h, xs):
            blk_p, lk = xs
            h, aux = body(blk_p, h, key=lk if use_key else None)
            return h, aux

        out, auxes = jax.lax.scan(scan_moe, x, (stacked_params, layer_keys),
                                  unroll=scan_unroll)
        aux = jnp.sum(auxes)
        if sp_axis is not None:
            aux = jax.lax.pmean(aux, sp_axis)
        return out, aux

    def scan_fn(h, xs):
        blk_p, lk = xs
        return body(blk_p, h, key=lk if use_key else None), None

    out, _ = jax.lax.scan(scan_fn, x, (stacked_params, layer_keys),
                          unroll=scan_unroll)
    return out


def _block_mlp(p, x, *, act, moe_args, ep_axis, tp_axis, lora=None,
               lora_scale=None):
    """The MLP half of a block -> ``(x, routing_stats_or_None)``. The
    serving helpers append the MoE stats (per-expert routed counts,
    capacity drops, router entropy — nn/moe.py moe_apply) to their
    return tuple so the engine's metrics ledger reads the program's own
    numbers instead of re-deriving routing host-side; the training-side
    aux loss has no serving consumer and stays dropped here. ``lora``:
    this layer's packed per-slot mlp adapters (fc/proj targets; serving
    multi-LoRA) — MoE blocks have no LoRA targets and ignore it."""
    with jax.named_scope("mlp"):
        h = layer_norm_apply(p["ln2"], x)
        if moe_args is not None:
            y, _aux, stats = moe_apply(p["moe"], h, moe_args,
                                       ep_axis=ep_axis, tp_axis=tp_axis,
                                       act=act, return_stats=True)
            return x + y, stats
        return x + mlp_apply(p["mlp"], h, act=act, tp_axis=tp_axis,
                             lora=lora, lora_scale=lora_scale), None


def block_prefill(p, x, *, num_heads: int, act: Callable = gelu,
                  moe_args: Optional[MoEArgs] = None,
                  tp_axis: Optional[str] = None):
    """Causal block forward that also returns this layer's (k, v)
    [B, H, S, Dh] — the prefill half of KV-cache generation.
    ``tp_axis``: head-sharded — ``num_heads`` is LOCAL heads and the
    returned cache holds only this rank's heads."""
    with jax.named_scope("attn"):
        a, (k, v) = mha_apply(p["attn"], layer_norm_apply(p["ln1"], x),
                              num_heads=num_heads, causal=True,
                              return_kv=True, tp_axis=tp_axis)
        x = x + a
    x, _stats = _block_mlp(p, x, act=act, moe_args=moe_args, ep_axis=None,
                           tp_axis=tp_axis)
    return x, (k, v)


def block_prefill_paged_sp(p, x, k_cache, v_cache, start, t0, *,
                           num_heads: int, sp_axis: str, layer,
                           act: Callable = gelu,
                           moe_args: Optional[MoEArgs] = None,
                           tp_axis: Optional[str] = None,
                           block_tables=None,
                           block_size: Optional[int] = None,
                           kv_scales=None, policy=None):
    """Sequence-parallel chunked-prefill block step (nn/attention.py
    mha_prefill_paged_sp): x [1, Pl, D] is this sp rank's slice of the
    chunk's hidden states at positions ``start + rank*Pl + arange(Pl)``;
    the attention rides ring_paged_prefill over ``sp_axis`` while the
    LN/MLP halves are position-wise and stay local. Returns
    (x, k_cache, v_cache[, k_scale, v_scale]) with the whole chunk's
    K/V scattered into ``layer`` of the (sp-replicated) pool."""
    with jax.named_scope("attn"):
        out = mha_prefill_paged_sp(
            p["attn"], layer_norm_apply(p["ln1"], x), k_cache, v_cache,
            start, t0, num_heads=num_heads, sp_axis=sp_axis, layer=layer,
            tp_axis=tp_axis, block_tables=block_tables,
            block_size=block_size, kv_scales=kv_scales, policy=policy)
        x = x + out[0]
    # sp prefill never composes with MoE (the engine rejects the pair
    # at construction), so the stats-free return shape is invariant
    x, _stats = _block_mlp(p, x, act=act, moe_args=moe_args,
                           ep_axis=None, tp_axis=tp_axis)
    return (x, *out[1:])


def block_verify_paged(p, x, k_cache, v_cache, positions, tail_lens, *,
                       num_heads: int, layer, act: Callable = gelu,
                       moe_args: Optional[MoEArgs] = None,
                       ep_axis: Optional[str] = None,
                       tp_axis: Optional[str] = None,
                       block_tables=None,
                       block_size: Optional[int] = None,
                       lora=None, lora_scale=None,
                       kv_scales=None, policy=None):
    """The paged block step of every serving program
    (nn/attention.mha_verify_paged): x [S, P, D] per-slot token runs at
    absolute ``positions`` [S, P] — a decode step at P == 1, a
    (chunked) prefill at S == 1, speculative decoding's scoring run
    between (serve/spec.py). The caches are the WHOLE pool, written and
    read at ``layer``. ``lora``/``lora_scale``: this layer's packed
    per-slot adapters (serving multi-LoRA; serve/adapters.py).
    ``kv_scales``/``policy``: scaled KV layout (serve/kv_quant.py) —
    the whole (k_scale, v_scale) arrays ride along and come back.
    ``ep_axis``: MoE expert parallelism — experts sharded over the
    axis, one all_to_all each way inside the FFN (nn/moe.py). Returns
    (x, k_cache, v_cache[, k_scale, v_scale][, moe_stats]) — MoE
    blocks append their routing-stats dict."""
    attn_lora = lora.get("attn") if lora is not None else None
    with jax.named_scope("attn"):
        out = mha_verify_paged(
            p["attn"], layer_norm_apply(p["ln1"], x), k_cache, v_cache,
            positions, tail_lens, num_heads=num_heads, layer=layer,
            tp_axis=tp_axis, block_tables=block_tables,
            block_size=block_size, lora=attn_lora, lora_scale=lora_scale,
            kv_scales=kv_scales, policy=policy)
        x = x + out[0]
    x, stats = _block_mlp(
        p, x, act=act, moe_args=moe_args, ep_axis=ep_axis,
        tp_axis=tp_axis,
        lora=lora.get("mlp") if lora is not None else None,
        lora_scale=lora_scale)
    if moe_args is not None:
        return (x, *out[1:], stats)
    return (x, *out[1:])


def block_decode(p, x, k_cache, v_cache, pos, *, num_heads: int,
                 act: Callable = gelu,
                 moe_args: Optional[MoEArgs] = None,
                 tp_axis: Optional[str] = None):
    """Single-token cached block step on the dense single-request
    cache (nn/attention.py mha_decode; models/gpt2_generate.py). The
    continuous-batching decode step is :func:`block_verify_paged` at
    one token a row. Returns (x, k_cache, v_cache)."""
    with jax.named_scope("attn"):
        a, k_cache, v_cache = mha_decode(
            p["attn"], layer_norm_apply(p["ln1"], x), k_cache, v_cache,
            pos, num_heads=num_heads, tp_axis=tp_axis)
        x = x + a
    x, _stats = _block_mlp(p, x, act=act, moe_args=moe_args, ep_axis=None,
                           tp_axis=tp_axis)
    return x, k_cache, v_cache
