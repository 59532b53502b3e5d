"""Multi-head attention with head-sharded tensor parallelism.

Reference semantics: fused QKV as ColumnParallelLinear with
``gather_output=False`` so each TP rank keeps H/tp heads, local scaled
dot-product attention, then RowParallel output projection with a single
all-reduce (reference: utils/GPT2/gpt2_attention.py:80-175; ViT variant
utils/model.py:45-110 without the causal mask).

Under shard_map the qkv weight arrives column-sharded [D, 3D/tp] and the
proj weight row-sharded [D/tp, D]; with ``tp_axis=None`` the same code is
plain single-device MHA. The inner attention dispatches to a Pallas flash
kernel on TPU for long sequences (ops/flash_attention.py) and to the
reference-equivalent jnp softmax path otherwise.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from einops import rearrange

from quintnet_tpu.core import collectives as cc
from quintnet_tpu.nn.layers import (linear_init, linear_apply, lora_delta,
                                    quantized_matmul)


def mha_init(key, dim: int, *, qkv_bias: bool = True, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "qkv": linear_init(k1, dim, 3 * dim, use_bias=qkv_bias, dtype=dtype),
        "proj": linear_init(k2, dim, dim, dtype=dtype),
    }


def rope_cos_sin(positions, head_dim: int, *, theta: float = 10000.0,
                 inv_freq=None):
    """Rotary tables for integer ``positions`` [...]: (cos, sin), each
    [..., head_dim] with the half-dim frequencies duplicated (HF Llama
    layout: the i-th and (i+d/2)-th lanes share a frequency).
    ``inv_freq`` overrides the plain 1/theta^(2i/d) frequencies (rope
    scaling — models/llama.py llama3_scaled_inv_freq)."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                                    / head_dim))            # [d/2]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)              # [..., d]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """Rotate [B, H, S, Dh] by per-position tables [S, Dh] (or any
    broadcastable shape). HF rotate_half convention."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos + rotated.astype(jnp.float32)
            * sin).astype(x.dtype)


def repeat_kv(x, n_rep: int):
    """[B, Hkv, S, Dh] -> [B, Hkv*n_rep, S, Dh] (GQA: share each kv head
    across n_rep query heads; groups stay contiguous, HF order)."""
    if n_rep == 1:
        return x
    b, h, s, d = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, h, n_rep, s, d)
                            ).reshape(b, h * n_rep, s, d)


def _qkv_heads(p, x, num_heads: int, lora=None, lora_scale=None):
    """The fused qkv projection of ``x`` [B, S, D] (plus the per-slot
    LoRA delta, landing before the head split), split into per-head
    q, k, v [B, H, S, Dh]. Scope ``qkv`` on a device trace."""
    with jax.named_scope("qkv"):
        qkv = linear_apply(p["qkv"], x)  # [B, S, 3*D_local]
        if lora is not None and "qkv" in lora:
            qkv = qkv + lora_delta(x, lora["qkv"], lora_scale)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = rearrange(q, "b s (h d) -> b h s d", h=num_heads)
        k = rearrange(k, "b s (h d) -> b h s d", h=num_heads)
        v = rearrange(v, "b s (h d) -> b h s d", h=num_heads)
    return q, k, v


def _proj_out(p, o, tp_axis: Optional[str], lora=None, lora_scale=None):
    """Heads merged, output projection (plus the per-slot LoRA delta,
    landing before the psum), the RowParallel all-reduce under
    ``tp_axis`` (reference: layers.py:216 -> All_Reduce), then the
    bias: [B, H, S, Dh] -> [B, S, D]. Scope ``proj``."""
    with jax.named_scope("proj"):
        o = rearrange(o, "b h s d -> b s (h d)")
        y = quantized_matmul(o, p["proj"])
        if lora is not None and "proj" in lora:
            y = y + lora_delta(o, lora["proj"], lora_scale)
        if tp_axis is not None:
            y = cc.all_reduce(y, tp_axis)
        if "b" in p["proj"]:
            y = y + p["proj"]["b"]
    return y


def _as_stored(x, view):
    """``x`` in the dtype a cached ``view`` is STORED in, where that is
    a float narrower than ``x``'s that a dot takes natively (bf16,
    f16); ``x`` itself otherwise. The view is the big operand — a
    mixed-dtype dot makes the compiler widen IT, a second copy of every
    row in HBM each layer — so the small operand goes down instead."""
    if (view.dtype in (jnp.bfloat16, jnp.float16)
            and jnp.issubdtype(x.dtype, jnp.floating)
            and x.dtype.itemsize > view.dtype.itemsize):
        return x.astype(view.dtype)
    return x


# rows a lone query is padded to: one sublane tile of the matrix unit
_MIN_DOT_ROWS = 8


def _masked_sdpa(q, k_all, v_all, valid, *, page: Optional[int] = None,
                 scale: Optional[float] = None):
    """The score math every cached path shares: q [B, H, S, Dh] against
    a whole row's keys and values [B, H, T, Dh], softmax in f32 over
    the columns ``valid`` (broadcastable to [B, H, S, T]) allows.
    Scope ``sdpa``. ``scale`` multiplies the scores where a model
    states its own (``attention_multiplier``); None divides them by
    ``sqrt(Dh)``.

    Both contractions take the view in the dtype it is STORED in and
    accumulate in f32 (:func:`_as_stored`): ``q`` and the probabilities
    are rounded to a bf16 view's dtype — the arithmetic the matrix unit
    gives every other f32 x bf16 product at default precision. An f32
    view (every f32 pool, every dequantized one) takes the plain branch
    it always took. Two more steps make the stored bytes the only copy
    of the view a TPU program reads (each was measured on the v5e:
    PERF.md, PR 26):

    - a contraction with ONE query row is a vector-matrix product,
      which the compiler runs as an f32 multiply-reduce over an f32
      copy of the view whatever the operands' dtype; a lone row is
      padded with zero rows to :data:`_MIN_DOT_ROWS` and stays a matmul
      (the pad rows see the same mask, and are dropped);
    - ``page``: the view is a gather of pool blocks of ``page``
      positions each (:func:`paged_gather`; the paged callers pass
      their ``block_size``). A matmul over ``[T, Dh]`` wants a block's
      positions beside the next block's, so the compiler transposes
      the gathered view in HBM first; contracted block by block,
      ``[T // page, page, Dh]``, it is read as the gather left it.

    The output keeps the dtype ``q`` and the view promote to (``q``'s,
    for a narrower view)."""
    with jax.named_scope("sdpa"):
        b, h, s, dh = q.shape
        t = k_all.shape[2]
        out_dtype = jnp.result_type(q, v_all)
        qs = _as_stored(q, k_all)
        stored = qs is not q
        by_page = stored and bool(page)
        if stored and s == 1:
            qs = jnp.pad(qs, ((0, 0), (0, 0), (0, _MIN_DOT_ROWS - s), (0, 0)))
        if by_page:
            k_all = k_all.reshape(b, h, t // page, page, dh)
            v_all = v_all.reshape(b, h, t // page, page, dh)
            scores = jnp.einsum("bhsd,bhmtd->bhsmt", qs, k_all,
                                preferred_element_type=jnp.float32
                                ).reshape(b, h, -1, t)
        else:
            scores = jnp.einsum("bhsd,bhtd->bhst", qs, k_all,
                                preferred_element_type=jnp.float32)
        scores = (scores / math.sqrt(dh) if scale is None
                  else scores * scale)
        scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
        probs = _as_stored(
            jax.nn.softmax(scores, axis=-1).astype(q.dtype), v_all)
        if by_page:
            o = jnp.einsum("bhsmt,bhmtd->bhsd",
                           probs.reshape(b, h, -1, t // page, page), v_all,
                           preferred_element_type=jnp.float32)
        else:
            o = jnp.einsum("bhst,bhtd->bhsd", probs, v_all,
                           preferred_element_type=jnp.float32)
        return o[:, :, :s].astype(out_dtype)


def sdpa(q, k, v, *, causal: bool, softmax_dtype=jnp.float32,
         pdrop: float = 0.0, key=None, segment_ids=None):
    """Plain scaled-dot-product attention: [B, H, S, Dh] -> [B, H, S, Dh].

    Matches the reference's F.scaled_dot_product_attention call
    (gpt2_attention.py:156-161), including its ``dropout_p`` on the
    attention probabilities when ``key`` is given. Softmax in f32
    regardless of input dtype. ``segment_ids`` [B, S]: cross-segment
    pairs are masked (packed-document isolation).
    """
    dh = q.shape[-1]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(softmax_dtype)
    scores = scores / math.sqrt(dh)
    if causal:
        s, t = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), dtype=bool))
        scores = jnp.where(mask, scores, jnp.finfo(softmax_dtype).min)
    if segment_ids is not None:
        same = (segment_ids[:, None, :, None]
                == segment_ids[:, None, None, :])  # [B, 1, S, S]
        scores = jnp.where(same, scores, jnp.finfo(softmax_dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if key is not None and pdrop > 0.0:
        from quintnet_tpu.nn.layers import dropout

        probs = dropout(key, probs, pdrop, deterministic=False)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v)


def mha_apply(
    p,
    x,
    *,
    num_heads: int,
    causal: bool = False,
    tp_axis: Optional[str] = None,
    sp_axis: Optional[str] = None,
    sp_mode: str = "ring",
    use_flash: bool = False,
    return_kv: bool = False,
    attn_pdrop: float = 0.0,
    resid_pdrop: float = 0.0,
    key=None,
    segment_ids=None,
):
    """x: [B, S_local, D] -> [B, S_local, D].

    ``num_heads`` is the number of LOCAL heads (global heads / tp_size when
    sharded — head-sharding exactly as gpt2_attention.py:89-95).
    With ``sp_axis`` the sequence dim is sharded and the inner attention
    runs sequence-parallel — long-context support the reference does not
    have. ``sp_mode`` picks the algorithm: 'ring' (K/V rotation via
    ppermute, ops/ring_attention.py), 'zigzag' (load-balanced causal
    ring — ~2x less compute at high sp) or 'ulysses' (head-scatter
    all-to-all, ops/ulysses_attention.py; composes with flash).

    ``return_kv=True`` additionally returns the per-head (k, v)
    projections [B, H, S, Dh] — the prefill half of KV-cache decoding
    (models/gpt2_generate.py).

    Dropout (training only — pass ``key``): ``attn_pdrop`` on the
    attention probabilities — supported on EVERY path (plain sdpa, the
    flash blockwise fallback, ring, ulysses; the reference gets the
    same coverage from sdpa's dropout_p, gpt2_attention.py:156-161) —
    and ``resid_pdrop`` after the output projection, applied post-psum
    so the mask agrees across tp ranks (gpt2_attention.py:156-180).
    Under tp the SAME prob-dropout mask pattern is reused on each rank's
    head block — head-group correlation, accepted for mask/key locality.

    ``segment_ids``: packed-document isolation masking on every path.
    Local paths (sdpa + flash incl. the Pallas kernel) take [B, S]
    directly; under ``sp_axis`` pass this rank's [B, S_local] slice of
    the GLOBAL id vector (models/gpt2.py segment_ids_from_input
    derives it sp-aware) — ring/zigzag rotate the ids alongside their
    K/V chunks and Ulysses all-gathers them for its full-sequence
    inner attention.
    """
    k_attn = k_resid = None
    if key is not None:
        k_attn, k_resid = jax.random.split(key)
    drop_kw = dict(pdrop=attn_pdrop, key=k_attn)

    q, k, v = _qkv_heads(p, x, num_heads)

    with jax.named_scope("sdpa"):
        if sp_axis is not None and sp_mode == "ulysses":
            from quintnet_tpu.ops.ulysses_attention import \
                ulysses_attention

            o = ulysses_attention(q, k, v, axis=sp_axis, causal=causal,
                                  use_flash=use_flash,
                                  segment_ids=segment_ids, **drop_kw)
        elif sp_axis is not None and sp_mode == "zigzag":
            from quintnet_tpu.ops.ring_attention import \
                zigzag_ring_attention

            o = zigzag_ring_attention(q, k, v, axis=sp_axis,
                                      causal=causal,
                                      segment_ids=segment_ids, **drop_kw)
        elif sp_axis is not None:
            if sp_mode != "ring":
                raise ValueError(
                    f"unknown sp_mode {sp_mode!r}; expected 'ring', "
                    "'zigzag' or 'ulysses'")
            from quintnet_tpu.ops.ring_attention import ring_attention

            o = ring_attention(q, k, v, axis=sp_axis, causal=causal,
                               segment_ids=segment_ids, **drop_kw)
        elif use_flash:
            from quintnet_tpu.ops.flash_attention import flash_attention

            o = flash_attention(q, k, v, causal=causal,
                                segment_ids=segment_ids, **drop_kw)
        else:
            o = sdpa(q, k, v, causal=causal, pdrop=attn_pdrop,
                     key=k_attn, segment_ids=segment_ids)

    y = _proj_out(p, o, tp_axis)
    if k_resid is not None and resid_pdrop > 0.0:
        from quintnet_tpu.nn.layers import dropout

        y = dropout(k_resid, y, resid_pdrop, deterministic=False)
    if return_kv:
        return y, (k, v)
    return y


def paged_cache_update(k_cache, v_cache, k, v, pos, *, block_tables,
                       block_size: int):
    """Write one token's (k, v) into a PAGED pool at each row's own
    position. ``k_cache``/``v_cache``: [N_blocks*block_size, H, Dh] flat
    pool views shared by every request; ``k``/``v``: [B, H, Dh];
    ``pos``: [B] per-row write positions; ``block_tables``: [B, M]
    logical-block -> pool-block indirection (serve/kv_pool.py).

    Block 0 is the pool's reserved null block: inactive rows carry an
    all-zero table row and pos 0, so their writes land at flat index 0
    — garbage nobody reads (their scores are masked and the engine
    drops their outputs). Duplicate index-0 scatters are benign for the
    same reason."""
    with jax.named_scope("kv_write"):
        blk = jnp.take_along_axis(
            block_tables, (pos // block_size)[:, None], axis=1)[:, 0]
        idx = blk * block_size + pos % block_size        # [B] flat slots
        return (k_cache.at[idx].set(k.astype(k_cache.dtype)),
                v_cache.at[idx].set(v.astype(v_cache.dtype)))


def paged_gather(cache, block_tables, *, block_size: int):
    """[N_blocks*block_size, H, Dh] pool + [B, M] tables -> the
    position-ordered per-row view [B, H, M*block_size, Dh]. Token
    position t of a row lives at (table[t // bs], t % bs), so the
    gathered view is exactly position-ordered and the usual
    ``arange <= pos`` length mask applies unchanged."""
    nb = cache.shape[0] // block_size
    pages = cache.reshape(nb, block_size, *cache.shape[1:])[block_tables]
    # [B, M, bs, H, Dh] -> [B, H, M*bs, Dh]
    b, m, bs, h, dh = pages.shape
    return pages.transpose(0, 3, 1, 2, 4).reshape(b, h, m * bs, dh)


def paged_gather_scales(scales, block_tables, *, block_size: int):
    """Per-block-per-head scales [num_blocks, H] + tables [B, M] -> the
    position-ordered broadcast view [B, H, M*block_size, 1] matching
    :func:`paged_gather`'s output: every slot of a block shares its
    block's per-head scale."""
    sc = scales[block_tables]                       # [B, M, H]
    b, m, h = sc.shape
    sc = jnp.broadcast_to(sc.transpose(0, 2, 1)[:, :, :, None],
                          (b, h, m, block_size))
    return sc.reshape(b, h, m * block_size)[..., None]


def paged_gather_dequant(policy, cache, scales, block_tables, *,
                         block_size: int):
    """The DEQUANT-INSIDE-THE-KERNEL read: gather a row's blocks into
    the position-ordered view and dequantize with their block scales —
    [B, H, M*bs, Dh] f32, ready for the existing f32-softmax math.
    With ``scales=None`` (passthrough policies) this IS
    :func:`paged_gather`."""
    view = paged_gather(cache, block_tables, block_size=block_size)
    if scales is None:
        # float8 pools (unscaled fp8 policy) upcast HERE — float8 has no
        # implicit-promotion path in jax, so the view must be widened
        # before the softmax math. f32/bf16 views pass through
        # untouched (bit-identical to the pre-policy read).
        if str(view.dtype).startswith("float8"):
            return view.astype(jnp.float32)
        return view
    return policy.dequant(
        view, paged_gather_scales(scales, block_tables,
                                  block_size=block_size))


def _gather_kv(k_cache, v_cache, kv_scales, policy, block_tables, *,
               block_size: int):
    """THE paired gathered-view read every paged attention entry point
    shares (prefill / ring / verify / decode had four verbatim copies):
    gather both pools' rows position-ordered and — under a scaled
    layout policy — dequantize with their block scales
    (:func:`paged_gather_dequant`; ``kv_scales=None`` is the plain
    :func:`paged_gather` pair). Also the single seam the fused-kernel
    dispatch (``attn_kernel="pallas"``, ops/paged_attention.py) plugs
    into INSTEAD of — the Pallas path never calls this."""
    ks, vs = kv_scales if kv_scales is not None else (None, None)
    with jax.named_scope("kv_gather"):
        k_all = paged_gather_dequant(policy, k_cache, ks, block_tables,
                                     block_size=block_size)
        v_all = paged_gather_dequant(policy, v_cache, vs, block_tables,
                                     block_size=block_size)
    return k_all, v_all


def _paged_attention_scaled(policy, k_cache, v_cache, ks, vs, q, k, v,
                            positions, lens, block_tables, *,
                            block_size: int, max_blocks: int):
    """The scaled-policy fused-kernel step every pallas branch shares
    (gpt2 + llama, decode/verify/prefill — six call sites, one calling
    convention): score the exact f32 fresh run against the PRE-write
    pool (ops/paged_attention.paged_attention with the fresh-kv
    override — the oracle's post-insert view), then requantize only
    the run's touched blocks, k and v symmetrically
    (paged_quant_window_update — pool bytes byte-identical to the
    gathered-view oracle's). ``positions`` [S, P] contiguous runs;
    ``lens`` [S]. Returns (o, k_cache, v_cache, ks, vs) — a future
    kernel-convention change (the Flash-Decoding evolution) edits
    exactly here."""
    from quintnet_tpu.ops.paged_attention import (
        paged_attention, paged_quant_window_update)

    o = paged_attention(q, k_cache, v_cache, block_tables,
                        positions[:, 0], block_size=block_size,
                        kv_scales=(ks, vs), policy=policy,
                        fresh_kv=(k, v))
    with jax.named_scope("kv_write"):
        k_cache, ks = paged_quant_window_update(
            policy, k_cache, ks, k, positions, lens,
            block_tables=block_tables, block_size=block_size,
            max_blocks=max_blocks)
        v_cache, vs = paged_quant_window_update(
            policy, v_cache, vs, v, positions, lens,
            block_tables=block_tables, block_size=block_size,
            max_blocks=max_blocks)
    return o, k_cache, v_cache, ks, vs


def paged_requant_scatter(policy, cache, scales, row_view, block_tables,
                          first_blk, last_pos, *, block_size: int,
                          max_blocks: int):
    """Quantize-on-scatter: requantize each row's TOUCHED logical
    blocks ``[first_blk[s], last_pos[s] // bs]`` from its f32 gathered
    view ``row_view`` [S, H, M*bs, Dh] — fresh per-block-per-head
    absmax scales — and write blocks + scales back into the pool.

    ``last_pos`` [S] is each row's last WRITTEN token position: block
    slots beyond it are zeroed before the absmax, so recycled blocks'
    stale bytes (a previous owner's values, dequantized under a
    leftover scale the allocator never resets) can neither inflate the
    scale — which would coarsen the new tokens' quantization — nor
    survive in storage. Those slots are unreadable until rewritten
    (the attention mask stops at each row's position), so zeroing them
    is inert.

    ``max_blocks`` is the STATIC window width (the most blocks one
    row's write run can span); window slots past a row's dynamic last
    block (and rows with ``last_pos < first_blk * bs``, i.e. nothing
    written) scatter into the null block — memory nobody reads, the
    same convention as every paged update. Touched blocks are private
    to their row by the COW discipline, so no two rows' REAL writes
    ever collide; a published (shared) chain's bytes are never
    rewritten, which is what keeps requantization drift out of
    blocks other requests read."""
    S, H, T, Dh = row_view.shape
    bs = block_size
    M = block_tables.shape[1]
    rowb = row_view.reshape(S, H, M, bs, Dh)
    j = first_blk[:, None] + jnp.arange(max_blocks)[None, :]   # [S, K]
    touched = (j <= last_pos[:, None] // bs) & (j < M)
    j_c = jnp.clip(j, 0, M - 1)
    blk = jnp.take_along_axis(
        rowb, j_c[:, None, :, None, None], axis=2)     # [S, H, K, bs, Dh]
    live = (j_c[:, :, None] * bs + jnp.arange(bs)[None, None, :]
            <= last_pos[:, None, None])                # [S, K, bs]
    blk = jnp.where(live[:, None, :, :, None], blk, 0.0)
    sc = policy.compute_scale(blk, axes=(3, 4))        # [S, H, K]
    q = policy.quant(blk, sc[..., None, None])
    tgt = jnp.where(touched,
                    jnp.take_along_axis(block_tables, j_c, axis=1), 0)
    flat = tgt.reshape(-1)
    nb = cache.shape[0] // bs
    K = max_blocks
    q = q.transpose(0, 2, 3, 1, 4).reshape(S * K, bs, H, Dh)
    cache = cache.reshape(nb, bs, H, Dh).at[flat].set(q)
    cache = cache.reshape(nb * bs, H, Dh)
    scales = scales.at[flat].set(sc.transpose(0, 2, 1).reshape(S * K, H))
    return cache, scales


def paged_quant_update(policy, cache, scales, row_view, vals, positions,
                       lens, *, block_tables, block_size: int,
                       max_blocks: int):
    """The quantized pool WRITE all three paged kernels share: insert
    each row's fresh values into its dequantized f32 gathered view,
    then requantize + scatter back exactly the touched blocks
    (:func:`paged_requant_scatter`).

    ``row_view`` [S, H, T, Dh]: the row's dequantized view BEFORE this
    write; ``vals`` [S, H, P, Dh]: the fresh k or v run; ``positions``
    [S, P] absolute CONTIGUOUS write positions (``start_s +
    arange(P)``); ``lens`` [S]: columns at or beyond a row's len are
    pad. Returns (cache, scales, the post-insert f32 view — what the
    attention scores read, so the math on it matches the passthrough
    scatter-then-gather path exactly).

    The insert is one dynamic slice per row (the run is contiguous by
    contract), into a view padded by P slots so a run whose pad tail
    crosses the end of the table can never clamp-shift onto valid
    slots. Pad columns DO land in the view — at positions past the
    row's ``lens``, which no causal mask ever exposes to a real query
    and which the scatter below zeroes past ``last_pos`` — so they are
    inert in both the scores and the pool."""
    S, H, T, Dh = row_view.shape
    P = positions.shape[1]
    with jax.named_scope("kv_write"):
        padded = jnp.concatenate(
            [row_view, jnp.zeros((S, H, P, Dh), row_view.dtype)], axis=2)
        padded = jax.vmap(
            lambda row, val, st: lax.dynamic_update_slice_in_dim(
                row, val, st, axis=1)
        )(padded, vals.astype(jnp.float32), positions[:, 0])
        row_view = padded[:, :, :T]
        first = positions[:, 0] // block_size
        last_pos = positions[:, 0] + lens - 1       # < first*bs if len 0
        cache, scales = paged_requant_scatter(
            policy, cache, scales, row_view, block_tables, first,
            last_pos, block_size=block_size, max_blocks=max_blocks)
    return cache, scales, row_view


def _quant_span(p_tokens: int, block_size: int, table_width: int) -> int:
    """Static window width for :func:`paged_requant_scatter`: the most
    blocks a ``p_tokens``-long write run can touch."""
    return min(-(-p_tokens // block_size) + 1, table_width)


def paged_prefill_update(k_cache, v_cache, k, v, positions, tail_len, *,
                         block_tables, block_size: int):
    """Write one request's TAIL of (k, v) projections into the paged
    pool. ``k``/``v``: [H, P, Dh] (P = padded tail bucket);
    ``positions``: [P] absolute token positions (``start + arange(P)``
    — the chunked-prefill offset); ``block_tables``: [M] this request's
    table row. Rows at or beyond ``tail_len`` (pad columns, plus any
    position past the table) scatter into the null block — memory
    nobody reads, the same convention as :func:`paged_cache_update`."""
    P = positions.shape[0]
    with jax.named_scope("kv_write"):
        blk_idx = jnp.clip(positions // block_size, 0,
                           block_tables.shape[0] - 1)
        idx = jnp.where(jnp.arange(P) < tail_len,
                        block_tables[blk_idx] * block_size
                        + positions % block_size, 0)
        kin = k.transpose(1, 0, 2).astype(k_cache.dtype)   # [P, H, Dh]
        vin = v.transpose(1, 0, 2).astype(v_cache.dtype)
        return k_cache.at[idx].set(kin), v_cache.at[idx].set(vin)


def mha_prefill_paged(p, x, k_cache, v_cache, positions, tail_len, *,
                      num_heads: int, tp_axis: Optional[str] = None,
                      block_tables=None, block_size: Optional[int] = None,
                      lora=None, lora_scale=None,
                      kv_scales=None, policy=None,
                      attn_kernel: str = "xla"):
    """Chunked prefill over the paged pool: attention for ONE request's
    uncached tail, reading the cached prefix from pool blocks.

    ``x``: [1, P, D] tail hidden states (positions ``start ..
    start + P``); the tail's (k, v) are scattered through the block
    table first (:func:`paged_prefill_update`), then the WHOLE row —
    cached prefix + fresh tail — is gathered back position-ordered
    (:func:`paged_gather`) and each tail query attends causally against
    it: column t is valid iff ``t <= positions[i]``. With ``start == 0``
    this is ordinary causal prefill expressed on the paged layout
    (the serve engine's single prefill family — cache-off and cache-on
    run the SAME program, only ``start`` differs), and the math on the
    gathered view matches :func:`mha_decode`'s paged path exactly.

    Returns (y [1, P, D], k_cache, v_cache). ``num_heads`` is LOCAL
    heads under ``tp_axis`` (head-sharded pool + RowParallel psum, same
    as the decode path).

    ``lora``/``lora_scale``: per-slot packed adapters (serving
    multi-LoRA; nn/layers.lora_delta) — qkv's delta lands before the
    head split, proj's before the psum.

    ``kv_scales``/``policy`` (serve/kv_quant.py): a scaled layout
    policy reads the row via gather + DEQUANT, inserts the tail into
    the f32 view, runs the identical score math, and quantizes the
    touched blocks back on scatter; the return grows to
    (y, k_cache, v_cache, k_scale, v_scale).

    ``attn_kernel``: "xla" (default) is the gathered-view math above;
    "pallas" routes the attention through the fused block-table-walking
    kernel (ops/paged_attention.py) — same mask, same softmax sequence,
    bit-parity-pinned against this path — and under a scaled policy the
    pool write requantizes only the touched blocks
    (paged_quant_window_update) so the [H, M*bs, Dh] gathered view is
    never materialized."""
    q, k, v = _qkv_heads(p, x, num_heads, lora, lora_scale)
    ks = vs = None
    if attn_kernel == "pallas":
        tables = block_tables[None]
        if kv_scales is None:
            from quintnet_tpu.ops.paged_attention import paged_attention

            k_cache, v_cache = paged_prefill_update(
                k_cache, v_cache, k[0], v[0], positions, tail_len,
                block_tables=block_tables, block_size=block_size)
            o = paged_attention(q, k_cache, v_cache, tables,
                                positions[:1], block_size=block_size)
        else:
            ks, vs = kv_scales
            o, k_cache, v_cache, ks, vs = _paged_attention_scaled(
                policy, k_cache, v_cache, ks, vs, q, k, v,
                positions[None, :], jnp.reshape(tail_len, (1,)),
                tables, block_size=block_size,
                max_blocks=_quant_span(positions.shape[0], block_size,
                                       block_tables.shape[0]))
    else:
        if kv_scales is None:
            k_cache, v_cache = paged_prefill_update(
                k_cache, v_cache, k[0], v[0], positions, tail_len,
                block_tables=block_tables, block_size=block_size)
            k_all, v_all = _gather_kv(
                k_cache, v_cache, None, policy, block_tables[None],
                block_size=block_size)            # [1, H, M*bs, Dh]
        else:
            ks, vs = kv_scales
            tables = block_tables[None]
            k_all, v_all = _gather_kv(k_cache, v_cache, (ks, vs),
                                      policy, tables,
                                      block_size=block_size)
            span = _quant_span(positions.shape[0], block_size,
                               block_tables.shape[0])
            pos2 = positions[None, :]
            lens = jnp.reshape(tail_len, (1,))
            k_cache, ks, k_all = paged_quant_update(
                policy, k_cache, ks, k_all, k, pos2, lens,
                block_tables=tables, block_size=block_size,
                max_blocks=span)
            v_cache, vs, v_all = paged_quant_update(
                policy, v_cache, vs, v_all, v, pos2, lens,
                block_tables=tables, block_size=block_size,
                max_blocks=span)
        valid = (jnp.arange(k_all.shape[2])[None, :]
                 <= positions[:, None])               # [P, M*bs]
        o = _masked_sdpa(q, k_all, v_all, valid[None, None],
                         page=block_size)

    y = _proj_out(p, o, tp_axis, lora, lora_scale)
    if kv_scales is not None:
        return y, k_cache, v_cache, ks, vs
    return y, k_cache, v_cache


def _online_merge(m, l, acc, m_new, l_new, o_new):
    """Fold one chunk's (row-max, prob-sum, weighted-V) into running
    online-softmax accumulators; identity element (-inf, 0, 0). The
    same recurrence ops/ring_attention.py uses — duplicated here (it is
    ten lines) because nn/ must not import ops/ (ops/ulysses_attention
    already imports this module)."""
    m_tot = jnp.maximum(m, m_new)
    m_base = jnp.where(jnp.isfinite(m_tot), m_tot, 0.0)
    c_old = jnp.exp(jnp.where(jnp.isfinite(m), m - m_base, -jnp.inf))
    c_old = jnp.where(jnp.isfinite(c_old), c_old, 0.0)
    c_new = jnp.exp(jnp.where(jnp.isfinite(m_new), m_new - m_base,
                              -jnp.inf))
    c_new = jnp.where(jnp.isfinite(c_new), c_new, 0.0)
    return (m_tot, l * c_old + l_new * c_new,
            acc * c_old[..., None] + o_new * c_new[..., None])


def ring_paged_prefill(q, k, v, start, t0, k_cache, v_cache, *,
                       sp_axis: str, block_tables, block_size: int,
                       kv_scales=None, policy=None):
    """Sequence-parallel chunk attention over the paged pool: ring
    attention (Liu et al., RingAttention — PAPERS.md) across mesh axis
    ``sp_axis`` for the chunk's own K/V, merged online with each local
    query's attention over the already-resident pool prefix, then ONE
    all_gather reassembles the full chunk K/V for the (replica-local,
    sp-replicated) pool scatter.

    Inside a shard_map over ``sp_axis``: ``q`` [1, Hq, Pl, Dh] is this
    rank's slice of the chunk's queries (rank i owns global positions
    ``start + i*Pl .. start + (i+1)*Pl``), ``k``/``v`` [1, Hkv, Pl, Dh]
    the matching UNrepeated K/V slice (GQA repeats locally, never on
    the wire). ``start``/``t0`` are the chunk's dynamic token bounds:
    positions at or beyond ``t0`` are bucket pad — their keys are
    masked out of every score and their pool writes land in the null
    block, exactly :func:`paged_prefill_update`'s convention.

    Per call the sp wire carries ``2*sp`` ppermutes (the stacked K/V
    pair and its position vector rotate ``sp`` scan steps) plus one
    all_gather — the census analysis/specs.expected_serve_sp_prefill
    pins. Peak score memory is O(Pl * pool_row) per device instead of
    O(P * pool_row): the chunk's [P, P] score block never exists on any
    one rank, which is the RingAttention point — context length scales
    with device count, not one chip's memory.

    Returns (o [1, Hq, Pl, Dh] normalized local attention output,
    k_cache, v_cache with the WHOLE chunk scattered)."""
    sp = lax.axis_size(sp_axis)
    idx = lax.axis_index(sp_axis)
    b, hq, pl, dh = q.shape
    rep = hq // k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    q_pos = start + idx * pl + jnp.arange(pl, dtype=jnp.int32)   # [Pl]
    qf = q.astype(jnp.float32)

    def contrib(k_in, v_in, mask):
        """(m, l, o) of local queries vs one K/V chunk under ``mask``
        [Pl, T] — fully-masked rows yield the merge identity."""
        kf = repeat_kv(k_in, rep).astype(jnp.float32)
        vf = repeat_kv(v_in, rep).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhtd->bhqt", qf, kf) * scale
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(mask[None, None], jnp.exp(s - m_safe[..., None]),
                      0.0)
        return m, jnp.sum(p, axis=-1), \
            jnp.einsum("bhqt,bhtd->bhqd", p, vf)

    # resident-prefix contribution: the pool BEFORE this chunk's
    # scatter holds exactly positions [0, start) of this request —
    # every local query sees all of them (they precede the chunk).
    # Scaled layout policies (serve/kv_quant.py) dequantize the
    # gathered prefix here — the sp pool is replicated, so every rank
    # dequantizes (and later requantizes) identically.
    ks = vs = None
    if kv_scales is not None:
        ks, vs = kv_scales
    k_pool, v_pool = _gather_kv(k_cache, v_cache, kv_scales, policy,
                                block_tables[None],
                                block_size=block_size)
    pool_mask = jnp.broadcast_to(
        jnp.arange(k_pool.shape[2])[None, :] < start,
        (pl, k_pool.shape[2]))
    m, l, acc = contrib(k_pool, v_pool, pool_mask)

    # ring over the chunk itself: K/V (stacked) + their positions
    # rotate sp times; causal masking is positional, so pad keys
    # (k_pos >= t0) drop out with the same predicate
    def body(carry, _):
        m, l, acc, kv, k_pos = carry
        mask = ((k_pos[None, :] <= q_pos[:, None])
                & (k_pos[None, :] < t0))
        m, l, acc = _online_merge(m, l, acc,
                                  *contrib(kv[0], kv[1], mask))
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        return (m, l, acc, lax.ppermute(kv, sp_axis, perm),
                lax.ppermute(k_pos, sp_axis, perm)), None

    (m, l, acc, _, _), _ = lax.scan(
        body, (m, l, acc, jnp.stack([k, v]), q_pos), None, length=sp)
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    # one all_gather reassembles the chunk's K/V in rank (= sequence)
    # order for the replicated pool scatter; positions need no wire —
    # they are start + arange(P) by construction
    kv_full = lax.all_gather(jnp.stack([k[0], v[0]]), sp_axis, axis=2,
                             tiled=True)               # [2, Hkv, P, Dh]
    positions = start + jnp.arange(pl * sp, dtype=jnp.int32)
    if kv_scales is None:
        k_cache, v_cache = paged_prefill_update(
            k_cache, v_cache, kv_full[0], kv_full[1], positions,
            t0 - start, block_tables=block_tables, block_size=block_size)
        return o, k_cache, v_cache
    # quantize-on-scatter (no extra collectives: the gathered prefix
    # views already hold the row, the chunk inserts into them and only
    # the touched private blocks requantize — every rank identically)
    span = _quant_span(pl * sp, block_size, block_tables.shape[0])
    pos2 = positions[None, :]
    lens = jnp.reshape(t0 - start, (1,))
    k_cache, ks, _ = paged_quant_update(
        policy, k_cache, ks, k_pool, kv_full[0][None], pos2, lens,
        block_tables=block_tables[None], block_size=block_size,
        max_blocks=span)
    v_cache, vs, _ = paged_quant_update(
        policy, v_cache, vs, v_pool, kv_full[1][None], pos2, lens,
        block_tables=block_tables[None], block_size=block_size,
        max_blocks=span)
    return o, k_cache, v_cache, ks, vs


def sp_last_hidden(h, start, t0, *, sp_axis: str):
    """Replicate the chunk's LAST true position's hidden row across
    the sp ranks: ``h`` [1, Pl, D] is a rank's slice of the chunk
    (global positions ``start + rank*Pl + arange(Pl)``); position
    ``t0 - 1`` lives on exactly one rank, so a masked psum (one
    all_reduce — far cheaper than gathering the whole [1, P, D] chunk
    for one row) hands every rank the [1, 1, D] row the logits head
    reads. Model-independent: both families' ``prefill_from_sp`` end
    with this."""
    pl = h.shape[1]
    j = t0 - 1 - start - lax.axis_index(sp_axis) * pl
    own = (j >= 0) & (j < pl)
    h_loc = lax.dynamic_slice_in_dim(h, jnp.clip(j, 0, pl - 1), 1,
                                     axis=1)
    return lax.psum(jnp.where(own, h_loc, jnp.zeros_like(h_loc)),
                    sp_axis)


def mha_prefill_paged_sp(p, x, k_cache, v_cache, start, t0, *,
                         num_heads: int, sp_axis: str,
                         tp_axis: Optional[str] = None,
                         block_tables=None,
                         block_size: Optional[int] = None,
                         kv_scales=None, policy=None):
    """:func:`mha_prefill_paged`'s sequence-parallel sibling: ``x``
    [1, Pl, D] is this sp rank's slice of the chunk's hidden states;
    the attention runs through :func:`ring_paged_prefill` (K/V sharded
    over ``sp_axis`` during the score pass, reassembled once for the
    pool write). The output projection is position-wise, so it stays
    local. LoRA is deliberately absent — the engine rejects the
    (adapters, sp) combination at construction."""
    q, k, v = _qkv_heads(p, x, num_heads)
    with jax.named_scope("sdpa"):
        out = ring_paged_prefill(
            q, k, v, start, t0, k_cache, v_cache, sp_axis=sp_axis,
            block_tables=block_tables, block_size=block_size,
            kv_scales=kv_scales, policy=policy)
    o, pools = out[0], out[1:]
    return (_proj_out(p, o, tp_axis), *pools)


def paged_verify_update(k_cache, v_cache, k, v, positions, tail_lens, *,
                        block_tables, block_size: int):
    """Write EVERY row's short token run into the paged pool in one
    scatter — the speculative-verify write (serve/spec.py). ``k``/``v``:
    [S, H, P, Dh] (P = draft bucket + 1); ``positions``: [S, P] absolute
    per-row positions (``start_s + arange(P)``); ``tail_lens``: [S] —
    row columns at or beyond a row's tail_len (draft pad, inactive
    slots) scatter into the null block, the same convention as
    :func:`paged_prefill_update` batched over rows."""
    S, P = positions.shape
    M = block_tables.shape[1]
    with jax.named_scope("kv_write"):
        blk_idx = jnp.clip(positions // block_size, 0, M - 1)    # [S, P]
        blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)
        idx = jnp.where(jnp.arange(P)[None, :] < tail_lens[:, None],
                        blk * block_size + positions % block_size, 0)
        H, Dh = k.shape[1], k.shape[3]
        kin = k.transpose(0, 2, 1, 3).reshape(S * P, H, Dh)
        vin = v.transpose(0, 2, 1, 3).reshape(S * P, H, Dh)
        flat = idx.reshape(S * P)
        return (k_cache.at[flat].set(kin.astype(k_cache.dtype)),
                v_cache.at[flat].set(vin.astype(v_cache.dtype)))


def mha_verify_paged(p, x, k_cache, v_cache, positions, tail_lens, *,
                     num_heads: int, tp_axis: Optional[str] = None,
                     block_tables=None, block_size: Optional[int] = None,
                     lora=None, lora_scale=None,
                     kv_scales=None, policy=None,
                     attn_kernel: str = "xla"):
    """Batched draft-verify attention over the paged pool: EVERY slot
    scores a short run of tokens (its last sampled token + up to k
    drafted continuations) against its own cached row in ONE forward —
    the decode path widened from 1 to P tokens per row (speculative
    decoding's target-scoring step, serve/spec.py).

    ``x``: [S, P, D] per-slot token runs at absolute ``positions``
    [S, P]; the runs' (k, v) scatter through each row's block table
    first (:func:`paged_verify_update`, pad columns masked to the null
    block by ``tail_lens``), then each row's whole history — cached
    prefix + fresh run — is gathered back position-ordered
    (:func:`paged_gather`) and each token attends causally against it:
    column t is valid iff ``t <= positions[s, i]``. With P == 1 this IS
    :func:`mha_decode`'s paged path; the math on the gathered view is
    identical, so verify-committed tokens are bit-equal to plain
    decoded ones.

    Returns (y [S, P, D], k_cache, v_cache). ``num_heads`` is LOCAL
    heads under ``tp_axis`` (head-sharded pool + RowParallel psum).
    ``lora``/``lora_scale``: per-slot packed adapters, exactly as in
    :func:`mha_decode`. ``attn_kernel="pallas"``: the fused
    block-table-walking kernel instead of the gathered view (exactly
    :func:`mha_prefill_paged`'s contract, batched over rows)."""
    q, k, v = _qkv_heads(p, x, num_heads, lora, lora_scale)
    ks = vs = None
    if attn_kernel == "pallas":
        if kv_scales is None:
            from quintnet_tpu.ops.paged_attention import paged_attention

            k_cache, v_cache = paged_verify_update(
                k_cache, v_cache, k, v, positions, tail_lens,
                block_tables=block_tables, block_size=block_size)
            o = paged_attention(q, k_cache, v_cache, block_tables,
                                positions[:, 0], block_size=block_size)
        else:
            ks, vs = kv_scales
            o, k_cache, v_cache, ks, vs = _paged_attention_scaled(
                policy, k_cache, v_cache, ks, vs, q, k, v,
                positions, tail_lens, block_tables,
                block_size=block_size,
                max_blocks=_quant_span(positions.shape[1], block_size,
                                       block_tables.shape[1]))
    else:
        if kv_scales is None:
            k_cache, v_cache = paged_verify_update(
                k_cache, v_cache, k, v, positions, tail_lens,
                block_tables=block_tables, block_size=block_size)
            k_all, v_all = _gather_kv(k_cache, v_cache, None, policy,
                                      block_tables,
                                      block_size=block_size)
        else:
            ks, vs = kv_scales
            k_all, v_all = _gather_kv(k_cache, v_cache, (ks, vs),
                                      policy, block_tables,
                                      block_size=block_size)
            span = _quant_span(positions.shape[1], block_size,
                               block_tables.shape[1])
            k_cache, ks, k_all = paged_quant_update(
                policy, k_cache, ks, k_all, k, positions, tail_lens,
                block_tables=block_tables, block_size=block_size,
                max_blocks=span)
            v_cache, vs, v_all = paged_quant_update(
                policy, v_cache, vs, v_all, v, positions, tail_lens,
                block_tables=block_tables, block_size=block_size,
                max_blocks=span)
        valid = (jnp.arange(k_all.shape[2])[None, None, :]
                 <= positions[:, :, None])                # [S, P, T]
        o = _masked_sdpa(q, k_all, v_all, valid[:, None], page=block_size)

    y = _proj_out(p, o, tp_axis, lora, lora_scale)
    if kv_scales is not None:
        return y, k_cache, v_cache, ks, vs
    return y, k_cache, v_cache


def mha_decode(p, x, k_cache, v_cache, pos, *, num_heads: int,
               tp_axis: Optional[str] = None,
               block_tables=None, block_size: Optional[int] = None,
               lora=None, lora_scale=None,
               kv_scales=None, policy=None,
               attn_kernel: str = "xla"):
    """Single-token cached attention. Returns (y, k_cache, v_cache).

    Dense (single-request fast path, ``block_tables=None``): x [B, 1, D],
    caches [B, H, T, Dh], ``pos`` the (dynamic, scalar) write position
    shared by the whole batch.

    Paged (continuous-batching path): caches are FLAT POOL VIEWS
    [N_blocks*block_size, H, Dh] shared by all requests, ``pos`` is a
    [B] vector (each row decodes at its own depth) and ``block_tables``
    [B, M] maps each row's logical blocks to pool blocks
    (serve/kv_pool.py). Writes scatter through the table
    (:func:`paged_cache_update`); reads gather the row's blocks back
    into a position-ordered view (:func:`paged_gather`). Same math as
    the dense path on the gathered view — tests/test_serve.py holds the
    two token-for-token equal.

    The reference's generation loop re-runs the full prefix every step
    (utils/metrics.py:74-149, O(T^2) per token); here one token attends
    against the cache — O(T) per token, fully jittable (static shapes,
    dynamic_update_slice / table-scatter for the cache write, masked
    softmax over the not-yet-written tail).

    ``tp_axis``: head-sharded decode — ``num_heads`` is LOCAL heads, the
    cache holds this rank's heads, and the output projection psums over
    the axis (RowParallel, same as mha_apply's training path). The
    reference skips generation entirely under any parallelism
    (GPT2_Trainer.py:509-555).

    ``lora``/``lora_scale``: per-slot packed adapters (multi-tenant
    LoRA serving, serve/adapters.py) — row s applies ITS adapter's
    low-rank delta on the qkv and proj matmuls (nn/layers.lora_delta);
    zero-adapter rows are base-model rows exactly.

    ``attn_kernel="pallas"`` (paged path only): the fused
    block-table-walking kernel (ops/paged_attention.py) instead of the
    gathered-view math — bit-parity-pinned, never materializes the
    [B, H, M*bs, Dh] view."""
    q, k, v = _qkv_heads(p, x, num_heads, lora, lora_scale)
    ks = vs = None
    if block_tables is None:
        if kv_scales is not None:
            raise ValueError(
                "scaled KV layout policies exist only for the paged "
                "pool (block_tables is required)")
        if attn_kernel != "xla":
            raise ValueError(
                "attn_kernel='pallas' exists only for the paged pool "
                "(block_tables is required)")
        with jax.named_scope("kv_write"):
            k_cache = lax.dynamic_update_slice(k_cache, k, (0, 0, pos, 0))
            v_cache = lax.dynamic_update_slice(v_cache, v, (0, 0, pos, 0))
        k_all, v_all = k_cache, v_cache
        valid = (jnp.arange(k_cache.shape[2]) <= pos)[None, :]  # [1, T]
    elif attn_kernel == "pallas":
        if kv_scales is None:
            from quintnet_tpu.ops.paged_attention import paged_attention

            k_cache, v_cache = paged_cache_update(
                k_cache, v_cache, k[:, :, 0], v[:, :, 0], pos,
                block_tables=block_tables, block_size=block_size)
            o = paged_attention(q, k_cache, v_cache, block_tables, pos,
                                block_size=block_size)
        else:
            ks, vs = kv_scales
            o, k_cache, v_cache, ks, vs = _paged_attention_scaled(
                policy, k_cache, v_cache, ks, vs, q, k, v,
                pos[:, None], jnp.ones(pos.shape, jnp.int32),
                block_tables, block_size=block_size, max_blocks=1)
        k_all = None
    elif kv_scales is None:
        # pool layout is [slot, H, Dh]: k here is [B, H, 1, Dh]
        k_cache, v_cache = paged_cache_update(
            k_cache, v_cache, k[:, :, 0], v[:, :, 0], pos,
            block_tables=block_tables, block_size=block_size)
        k_all, v_all = _gather_kv(k_cache, v_cache, None, policy,
                                  block_tables, block_size=block_size)
        valid = jnp.arange(k_all.shape[2])[None, :] <= pos[:, None]
    else:
        # scaled layout (serve/kv_quant.py): dequantized gathered view,
        # token inserted in f32, ONE touched block per row requantized
        # back — inactive rows (pos 0, null table) round-trip the null
        # block, which nobody reads
        ks, vs = kv_scales
        k_all, v_all = _gather_kv(k_cache, v_cache, (ks, vs), policy,
                                  block_tables, block_size=block_size)
        ones = jnp.ones(pos.shape, jnp.int32)
        k_cache, ks, k_all = paged_quant_update(
            policy, k_cache, ks, k_all, k, pos[:, None], ones,
            block_tables=block_tables, block_size=block_size,
            max_blocks=1)
        v_cache, vs, v_all = paged_quant_update(
            policy, v_cache, vs, v_all, v, pos[:, None], ones,
            block_tables=block_tables, block_size=block_size,
            max_blocks=1)
        valid = jnp.arange(k_all.shape[2])[None, :] <= pos[:, None]

    if k_all is not None:
        o = _masked_sdpa(q, k_all, v_all, valid[:, None, None, :],
                         page=block_size)

    y = _proj_out(p, o, tp_axis, lora, lora_scale)
    if kv_scales is not None:
        return y, k_cache, v_cache, ks, vs
    return y, k_cache, v_cache
