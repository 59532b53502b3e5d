"""Multi-head attention with head-sharded tensor parallelism.

Reference semantics: fused QKV as ColumnParallelLinear with
``gather_output=False`` so each TP rank keeps H/tp heads, local scaled
dot-product attention, then RowParallel output projection with a single
all-reduce (reference: utils/GPT2/gpt2_attention.py:80-175; ViT variant
utils/model.py:45-110 without the causal mask).

Under shard_map the qkv weight arrives column-sharded [D, 3D/tp] and the
proj weight row-sharded [D/tp, D]; with ``tp_axis=None`` the same code is
plain single-device MHA. The inner attention of a local (not sequence-
parallel) call is chosen from what the call can observe
(:func:`local_attention_path`): a fused Pallas kernel on a TPU at the
shapes it was measured to win, the reference-equivalent jnp softmax
(:func:`sdpa`) otherwise.
"""

from __future__ import annotations

import contextlib
import math
import threading
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
                 inv_freq=None, scale: float = 1.0):
    """Rotary tables for integer ``positions`` [...]: (cos, sin), each
    [..., head_dim] with the half-dim frequencies duplicated (HF Llama
    layout: the i-th and (i+d/2)-th lanes share a frequency).
    ``inv_freq`` overrides the plain 1/theta^(2i/d) frequencies (rope
    scaling — models/llama.py llama3_scaled_inv_freq,
    :func:`yarn_inv_freq`); ``scale`` multiplies both tables (YaRN's
    ``attention_factor``). ``head_dim`` is the number of features that
    ROTATE: fewer than the head has under partial rotation
    (:func:`apply_rope`)."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                                    / head_dim))            # [d/2]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)              # [..., d]
    if scale == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def yarn_inv_freq(dim: int, *, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's frequencies (Peng et al. 2023, as Hugging Face computes
    them) over the ``dim`` rotating features: ``extrap = theta^(-2i /
    dim)`` where a feature turns more than ``beta_fast`` times within
    the ``original_max`` positions, ``interp = extrap / factor`` where
    it turns fewer than ``beta_slow``, a linear ramp between the two
    correction dimensions (floor and ceil of ``dim ln(original_max /
    (2 pi beta)) / (2 ln theta)``, clamped to ``[0, dim - 1]``). [dim/2]
    f32; the tables' ``attention_factor`` is :func:`rope_cos_sin`'s
    ``scale``."""
    def correction_dim(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extrap = 1.0 / (theta ** (jnp.arange(0, dim, 2, jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (extrap / factor) * ramp + extrap * (1.0 - ramp)


def apply_rope(x, cos, sin):
    """Rotate [B, H, S, Dh] by per-position tables [S, Dh] (or any
    broadcastable shape). HF rotate_half convention. Tables NARROWER
    than ``Dh`` rotate the first ``rot`` features (lane i pairs with
    lane i + rot/2) and pass the rest through: partial rotation."""
    d, rot = x.shape[-1], cos.shape[-1]
    if rot < d:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
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


def _stored_narrower(x, view) -> bool:
    """Whether a cached ``view`` is STORED in a float narrower than
    ``x``'s that a dot takes natively (bf16, f16)."""
    return (view.dtype in (jnp.bfloat16, jnp.float16)
            and jnp.issubdtype(x.dtype, jnp.floating)
            and x.dtype.itemsize > view.dtype.itemsize)


def _as_stored(x, view):
    """``x`` in the dtype a cached ``view`` is STORED in, where that is
    a float narrower than ``x``'s that a dot takes natively (bf16,
    f16); ``x`` itself otherwise. The view is the big operand — a
    mixed-dtype dot makes the compiler widen IT, a second copy of every
    row in HBM each layer — so the small operand goes down instead."""
    return x.astype(view.dtype) if _stored_narrower(x, view) else x


# rows a lone query is padded to: one sublane tile of the matrix unit
_MIN_DOT_ROWS = 8

# The most query rows a paged program may have (query heads x tokens a
# row, ``Hq * P``) and still contract the gathered view AS GATHERED,
# heads on the lane diagonal (:func:`_lane_diag_sdpa`). That form pays
# ``Hkv`` times the attention's FLOPs, which grow with the rows, to
# save the view's head split, which does not. On the v5e, the engine's
# own GPT-2 XL programs (25 heads, 12 x 1,024-position rows; my chip
# run, PR 30), diagonal against split: decode (25 rows) 15.2 ms
# against 50.6; a 16-wide prefill bucket (400 rows) 11.17 against
# 11.21, a 32-wide (800) 12.7 against 11.8, a 64-wide (1,600) 17.3
# against 12.9. The forms cross near 400 rows; 256 keeps every decode
# step and a verify run of four drafts (XL 125 rows, an 8-kv-head GQA
# model's 160) on the diagonal and every prefill bucket of a model
# with 16 heads or more on the split.
_MAX_DIAG_ROWS = 256


def _as_gathered(q, store, max_diag_rows: Optional[int] = None) -> bool:
    """Whether a program contracts its cached rows as stored, heads on
    the lane diagonal (:func:`_lane_diag_sdpa` over a ring,
    :func:`_paged_attend_walk` over the pool): the rows are stored in
    a float narrower than ``q`` [S, Hq, rows, Dh] and the query rows a
    sequence are few (``max_diag_rows``; None: :data:`_MAX_DIAG_ROWS`,
    read when the program is traced)."""
    limit = _MAX_DIAG_ROWS if max_diag_rows is None else max_diag_rows
    return _stored_narrower(q, store) and q.shape[1] * q.shape[2] <= limit


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


def _lane_diag_sdpa(q, k_rows, v_rows, valid, *, kv_heads: int,
                    scale: Optional[float] = None):
    """:func:`_masked_sdpa` for a program with FEW query rows, on the
    view as :func:`paged_gather` leaves it: ``k_rows``/``v_rows``
    [B, M, bs, F], a position's kv heads side by side in the lanes of
    one pool row. The head structure goes into the SMALL operand: q
    [B, Hq, S, Dh] is spread to ``R = Hq * S`` rows of ``F`` lanes,
    each holding its ``Dh`` values in the lanes of the kv head it reads
    (``h // (Hq // kv_heads)``: GQA needs no repeat of the view) and
    exact zeros in every other lane, the pool's pad lanes included.
    Scores [R, F] x [T, F] and values [R, T] x [T, F] then read the
    stored bytes where they lie (``T = M * bs``: a row's blocks are
    adjacent in the gathered rows, so the flat view is the same
    bytes), and each query row takes its own head's lanes off the
    diagonal of the small [R, F] result.

    Splitting the view into heads instead is a copy of it a layer on
    the chip — a ``Dh``-wide minor dim is half a lane row, so the copy
    is written padded to twice the bytes and read back: 34 of a 48-ms
    decode step at GPT-2 XL (PERF.md, PR 30) — where this costs
    ``kv_heads`` times the FLOPs on rows that leave the matrix unit
    mostly idle anyway. The arithmetic is :func:`_masked_sdpa`'s stored
    branch: q and the probabilities rounded to the view's dtype, f32
    sums, f32 softmax over ``valid`` [B, 1 | Hq, S, T]; the extra
    products are exact zeros — given FINITE pad lanes, which every
    writer of the pool zeroes (docs/serving.md, "The arithmetic
    contract") — so a sum differs from the split form's by its order
    alone. Fewer than :data:`_MIN_DOT_ROWS` rows are padded with
    masked rows, and dropped. Scope ``sdpa``."""
    with jax.named_scope("sdpa"):
        b, hq, s, dh = q.shape
        _, m, bs, f = k_rows.shape
        rows, t = hq * s, m * bs
        qd, own = _diag_queries(q, k_rows, kv_heads,
                                max(_MIN_DOT_ROWS, rows))
        valid = jnp.pad(
            jnp.broadcast_to(valid, (b, hq, s, t)).reshape(b, rows, t),
            ((0, 0), (0, qd.shape[1] - rows), (0, 0)))
        scores = jnp.einsum("brf,btf->brt", qd, k_rows.reshape(b, t, f),
                            preferred_element_type=jnp.float32)
        scores = (scores / math.sqrt(dh) if scale is None
                  else scores * scale)
        scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
        probs = _as_stored(
            jax.nn.softmax(scores, axis=-1).astype(q.dtype), v_rows)
        o = jnp.einsum("brt,btf->brf", probs, v_rows.reshape(b, t, f),
                       preferred_element_type=jnp.float32)
        return _off_diagonal(o, own, q.shape).astype(
            jnp.result_type(q, v_rows))


def _diag_queries(q, store, kv_heads: int, padded_rows: int):
    """q [B, Hq, S, Dh] spread over the lanes of ``store``'s rows
    [..., F]: ``(qd, own)`` — ``qd`` [B, R, F] in the store's dtype, row
    ``h * S + i`` holding its ``Dh`` values in the lanes of the kv head
    it reads (``h // (Hq // kv_heads)``) and exact zeros in every other
    lane, the pad lanes included, with zero rows up to ``R =
    padded_rows``; ``own`` the [1, Hq, 1, kv_heads, 1] mask that placed
    them, which :func:`_off_diagonal` takes the result back with."""
    b, hq, s, dh = q.shape
    rows, f = hq * s, store.shape[-1]
    own = (jnp.arange(hq)[:, None] // (hq // kv_heads)
           == jnp.arange(kv_heads)[None, :])[None, :, None, :, None]
    qd = jnp.where(own, _as_stored(q, store)[:, :, :, None, :], 0)
    return jnp.pad(qd.reshape(b, rows, kv_heads * dh),
                   ((0, 0), (0, padded_rows - rows),
                    (0, f - kv_heads * dh))), own


def _off_diagonal(o, own, q_shape):
    """Each query row's own head off the diagonal of ``o`` [B, R, F]
    (:func:`_diag_queries`'s rows, the pad rows and lanes dropped):
    [B, Hq, S, Dh], f32 — the other heads' lanes are selected out, not
    summed."""
    b, hq, s, dh = q_shape
    kv_heads = own.shape[3]
    o = o[:, :hq * s, :kv_heads * dh].reshape(b, hq, s, kv_heads, dh)
    return jnp.where(own, o, 0).sum(axis=3)


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


# ---------------------------------------------------------------------
# Which local attention runs. The numbers are one v5e chip's, bf16,
# causal, Dh 64 unless said; "a layer" = forward + backward + the
# forward again (what a block under remat costs), from
# tools/attn_microbench.py (my chip runs, PR 32; PERF.md section 6).
# ---------------------------------------------------------------------
# At [32, 12, 1024, 64] a layer is 6.6 ms in the resident geometry
# against 20.9 for sdpa; at [64, 12, 512, 64] 5.8 against 11.0 (not
# causal: 6.4 against 11.0, and 8.7 against 20.8 at 1,024); at [128,
# 12, 256, 64] 6.3 against 5.9: sdpa keeps everything under 512.
KERNEL_MIN_SEQ = 512
# The head widths measured: 64 (the cells'), and 128 at [32, 6, 1024,
# 128] 2.1 ms against 10.9. A narrower head pads its lane row further;
# no model here has one, so none was measured and none is sent.
KERNEL_HEAD_DIMS = (64, 128)
# Resident beats streamed wherever both ran: 6.6 against 10.1 ms at
# 1,024 (streamed at its best tile, 1,024), 9.1 against 24.9 at [16, 12,
# 2048, 64], 7.5 against 18.9 at [4, 12, 4096, 64] (sdpa: 40.6, 67.2).
# At 8,192 the compiler refuses it (146 MiB of 128 MiB VMEM) and
# streamed runs a layer in 32 ms.
RESIDENT_MAX_SEQ = 4096
# Its tile, at the cells' shape: 256 6.57 ms, 128 7.37, 512 6.63, 1,024
# 7.77 (a wider tile wastes more of the diagonal, a narrower one pays
# the row statistics more often); within 2% of 512 at 2,048 and 4,096.
RESIDENT_TILE = 256
# The streamed geometry's: 512 divides every S it is sent; 1,024 is 13%
# faster at 8,192 (32.2 against 37.0 ms) and divides fewer.
STREAMED_TILE = 512
# Where sdpa's [B, H, S, S] f32 scores stop being affordable: 0.8 GB a
# batch row of 12 heads at 4,096, a layer at 67 ms for 4 rows. The
# blockwise path holds O(S) a row.
BLOCKWISE_MIN_SEQ = 4096


def local_attention_path(*, backend: str, seq: int, head_dim: int,
                         dropout: bool) -> str:
    """Which implementation a local [B, H, seq, head_dim] attention call
    takes: ``"resident"`` or ``"streamed"`` (the two geometries of the
    one fused kernel, ops/pallas_attention.py), ``"sdpa"``, or
    ``"blockwise"``. A pure function of what the call can observe; no
    caller passes a preference. (Causal or not decides nothing: the
    kernel won both ways where it was measured.)"""
    resident = seq <= RESIDENT_MAX_SEQ
    tile = RESIDENT_TILE if resident else STREAMED_TILE
    if (backend == "tpu" and not dropout and head_dim in KERNEL_HEAD_DIMS
            and seq >= KERNEL_MIN_SEQ and seq % tile == 0):
        return "resident" if resident else "streamed"
    return "blockwise" if seq >= BLOCKWISE_MIN_SEQ else "sdpa"


def _attend(path: str, q, k, v, segment_ids, *, causal, pdrop, key):
    if path == "sdpa":
        return sdpa(q, k, v, causal=causal, pdrop=pdrop, key=key,
                    segment_ids=segment_ids)
    if path == "blockwise":
        from quintnet_tpu.ops.flash_attention import blockwise_attention

        return blockwise_attention(q, k, v, causal=causal, pdrop=pdrop,
                                   key=key, segment_ids=segment_ids)
    from quintnet_tpu.ops import pallas_attention

    if path == "resident":
        return pallas_attention.resident_flash_attention(
            q, k, v, causal, RESIDENT_TILE, RESIDENT_TILE,
            segment_ids=segment_ids)
    return pallas_attention.pallas_flash_attention(
        q, k, v, causal, STREAMED_TILE, STREAMED_TILE,
        segment_ids=segment_ids)


def local_attention(q, k, v, *, causal: bool, pdrop: float = 0.0, key=None,
                    segment_ids=None):
    """[B, H, S, Dh] -> [B, H, S, Dh] over a whole (local) sequence, by
    :func:`local_attention_path`. Every path masks ``segment_ids``
    [B, S]; the kernels carry no PRNG, so a call that asks for
    probability dropout never takes them.

    The backend is the one the program is LOWERED for: where this
    process's default backend would choose otherwise than a TPU (a CPU
    process compiling for a described chip — tests/test_chip_bringup.py,
    benchmarks/tools/aot_sizes.py), both choices are traced under
    ``lax.platform_dependent`` and the lowering keeps its own."""
    observed = dict(seq=q.shape[-2], head_dim=q.shape[-1],
                    dropout=key is not None and pdrop > 0.0)
    here = local_attention_path(backend=jax.default_backend(), **observed)
    on_tpu = local_attention_path(backend="tpu", **observed)
    run = dict(causal=causal, pdrop=pdrop, key=key)
    if here == on_tpu:
        return _attend(here, q, k, v, segment_ids, **run)
    args = (q, k, v) if segment_ids is None else (q, k, v, segment_ids)

    def branch(path):
        return lambda q, k, v, seg=None: _attend(path, q, k, v, seg, **run)

    return lax.platform_dependent(*args, tpu=branch(on_tpu),
                                  default=branch(here))


def mha_apply(
    p,
    x,
    *,
    num_heads: int,
    causal: bool = False,
    tp_axis: Optional[str] = None,
    sp_axis: Optional[str] = None,
    sp_mode: str = "ring",
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
    all-to-all, ops/ulysses_attention.py). Without ``sp_axis`` the path
    is :func:`local_attention`'s choice.

    ``return_kv=True`` additionally returns the per-head (k, v)
    projections [B, H, S, Dh] — the prefill half of KV-cache decoding
    (models/gpt2_generate.py).

    Dropout (training only — pass ``key``): ``attn_pdrop`` on the
    attention probabilities — supported on EVERY path (plain sdpa,
    blockwise, ring, ulysses; the reference gets the
    same coverage from sdpa's dropout_p, gpt2_attention.py:156-161) —
    and ``resid_pdrop`` after the output projection, applied post-psum
    so the mask agrees across tp ranks (gpt2_attention.py:156-180).
    Under tp the SAME prob-dropout mask pattern is reused on each rank's
    head block — head-group correlation, accepted for mask/key locality.

    ``segment_ids``: packed-document isolation masking on every path.
    Local paths (sdpa, blockwise, the Pallas kernels) take [B, S]
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
        else:
            o = local_attention(q, k, v, causal=causal,
                                segment_ids=segment_ids, **drop_kw)

    y = _proj_out(p, o, tp_axis)
    if k_resid is not None and resid_pdrop > 0.0:
        from quintnet_tpu.nn.layers import dropout

        y = dropout(k_resid, y, resid_pdrop, deterministic=False)
    if return_kv:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------
# The paged pool on the device: ``[L, N_blocks * block_size, F]`` per k
# and v — every layer's token slots in ONE buffer, a token's heads
# flattened into the minor dim and padded with zero lanes up to the
# width serve/kv_pool.py allocates (``F >= H * Dh``, a whole number of
# 128-lane vregs, so the chip lays the buffer out row-major). Programs
# carry the pool WHOLE through their layer loop and address it by
# ``(layer, slot)``: one scatter a write, in place; a read is a walk of
# each row's live blocks where a program has few query rows
# (:data:`_MAX_DIAG_ROWS`: decode, verify) and one gather of the table's
# width, split back into heads, where it has many. ``F`` is read
# off the pool's shape, ``H`` and ``Dh`` off the fresh projections.
# ---------------------------------------------------------------------
def _pool_rows(x, width: int):
    """[..., H, Dh] -> pool rows [..., width]: heads flattened, zero
    lanes up to the pool's width."""
    rows = x.reshape(*x.shape[:-2], -1)
    pad = width - rows.shape[-1]
    if pad:
        rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])
    return rows


def _pool_heads(rows, heads: int, head_dim: int):
    """Pool rows [..., F] -> [..., H, Dh], the pad lanes cut off."""
    return rows[..., :heads * head_dim].reshape(*rows.shape[:-1], heads,
                                                head_dim)


def _write_index(positions, lens, block_tables, block_size: int):
    """Flat pool slots [S * P] of the rows' runs at ``positions``
    [S, P] through their ``block_tables`` [S, M]; columns at or beyond
    a row's ``lens`` go to slot 0, the null block's first."""
    S, P = positions.shape
    M = block_tables.shape[1]
    blk_idx = jnp.clip(positions // block_size, 0, M - 1)    # [S, P]
    blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)
    return jnp.where(jnp.arange(P)[None, :] < lens[:, None],
                     blk * block_size + positions % block_size,
                     0).reshape(S * P)


def _put_rows(pool, layer, idx, x):
    """Scatter a run ``x`` [S, H, P, Dh] into ``layer`` of a buffer of
    pool rows at the flat slots ``idx`` [S * P]: heads flattened, pad
    lanes zero, the buffer's dtype."""
    S, H, P, Dh = x.shape
    rows = _pool_rows(x.transpose(0, 2, 1, 3).reshape(S * P, H, Dh),
                      pool.shape[-1])
    return pool.at[layer, idx].set(rows.astype(pool.dtype))


def paged_write(k_pool, v_pool, layer, k, v, positions, lens, *,
                block_tables, block_size: int):
    """Write every row's run of (k, v) into the PAGED pool at ``layer``,
    one scatter a pool. ``k``/``v``: [S, H, P, Dh] (decode P = 1, verify
    P = drafts + 1, prefill S = 1 and P = the bucket); ``positions``:
    [S, P] absolute token positions; ``block_tables``: [S, M]
    logical-block -> pool-block indirection (serve/kv_pool.py).

    Block 0 is the pool's reserved null block: columns at or beyond a
    row's ``lens`` (bucket and draft pad) and inactive rows (an
    all-zero table row, position 0) land at flat slot 0 — garbage
    nobody reads (their scores are masked and the engine drops their
    outputs). Duplicate slot-0 scatters are benign for the same
    reason."""
    with jax.named_scope("kv_write"):
        idx = _write_index(positions, lens, block_tables, block_size)
        return (_put_rows(k_pool, layer, idx, k),
                _put_rows(v_pool, layer, idx, v))


def paged_gather(pool, layer, block_tables, *, block_size: int,
                 head_shape=None):
    """Pool [L, N_blocks*block_size, F] + [B, M] tables -> each row's
    blocks of ``layer``, gathered straight from the whole pool:
    ``[B, M, block_size, F]`` pool rows as stored. Token position t of
    a row lives at (table[t // bs], t % bs), so the gathered view is
    exactly position-ordered and the usual ``arange <= pos`` length
    mask applies unchanged. With ``head_shape`` = (H, Dh) the rows are
    then cut into the head-major view [B, H, M*block_size, Dh] — the
    form :func:`_masked_sdpa` takes, and on the chip a re-layout of
    the whole view that :func:`_lane_diag_sdpa` does without."""
    L, n, f = pool.shape
    pages = pool.reshape(L, n // block_size, block_size, f)[
        layer, block_tables]                          # [B, M, bs, F]
    if head_shape is None:
        return pages
    pages = _pool_heads(pages, *head_shape)           # [B, M, bs, H, Dh]
    b, m, bs, h, dh = pages.shape
    return pages.transpose(0, 3, 1, 2, 4).reshape(b, h, m * bs, dh)


def paged_gather_scales(scales, layer, block_tables, *, block_size: int):
    """Per-block-per-head scales [L, num_blocks, H] + tables [B, M] ->
    the position-ordered broadcast view [B, H, M*block_size, 1]
    matching :func:`paged_gather`'s output: every slot of a block
    shares its block's per-head scale."""
    sc = scales[layer, block_tables]                # [B, M, H]
    b, m, h = sc.shape
    sc = jnp.broadcast_to(sc.transpose(0, 2, 1)[:, :, :, None],
                          (b, h, m, block_size))
    return sc.reshape(b, h, m * block_size)[..., None]


def paged_gather_dequant(policy, pool, scales, layer, block_tables, *,
                         block_size: int, head_shape):
    """The DEQUANT-INSIDE-THE-KERNEL read: gather a row's blocks into
    the position-ordered view and dequantize with their block scales —
    [B, H, M*bs, Dh] f32, ready for the existing f32-softmax math.
    With ``scales=None`` (passthrough policies) this IS
    :func:`paged_gather`."""
    view = paged_gather(pool, layer, block_tables, block_size=block_size,
                        head_shape=head_shape)
    if scales is None:
        # float8 pools (unscaled fp8 policy) upcast HERE — float8 has no
        # implicit-promotion path in jax, so the view must be widened
        # before the softmax math. f32/bf16 views pass through
        # untouched (bit-identical to the pre-policy read).
        if str(view.dtype).startswith("float8"):
            return view.astype(jnp.float32)
        return view
    return policy.dequant(
        view, paged_gather_scales(scales, layer, block_tables,
                                  block_size=block_size))


def _gather_kv(pools, layer, policy, block_tables, *, block_size: int,
               head_shape):
    """THE paired gathered-view read every paged attention entry point
    shares: gather both pools' rows position-ordered and — under a
    scaled layout policy, ``pools`` = (k, v, k_scale, v_scale) —
    dequantize with their block scales (:func:`paged_gather_dequant`).
    ``head_shape`` None (passthrough pools only): the rows as gathered,
    [B, M, bs, F], unsplit (:func:`paged_gather`). Also the single seam
    the fused-kernel dispatch (``attn_kernel="pallas"``,
    ops/paged_attention.py) plugs into INSTEAD of — the Pallas path
    never calls this."""
    k_pool, v_pool, *sc = pools
    ks, vs = sc if sc else (None, None)
    with jax.named_scope("kv_gather"):
        k_all = paged_gather_dequant(policy, k_pool, ks, layer,
                                     block_tables, block_size=block_size,
                                     head_shape=head_shape)
        v_all = paged_gather_dequant(policy, v_pool, vs, layer,
                                     block_tables, block_size=block_size,
                                     head_shape=head_shape)
    return k_all, v_all


def paged_requant_scatter(policy, pool, scales, layer, row_view,
                          block_tables, first_blk, last_pos, *,
                          block_size: int, max_blocks: int):
    """Quantize-on-scatter: requantize each row's TOUCHED logical
    blocks ``[first_blk[s], last_pos[s] // bs]`` from its f32 gathered
    view ``row_view`` [S, H, M*bs, Dh] — fresh per-block-per-head
    absmax scales — and write blocks + scales back into the pool at
    ``layer``.

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
    L, n, f = pool.shape
    K = max_blocks
    q = q.transpose(0, 2, 3, 1, 4).reshape(S * K, bs, H, Dh)
    pool = pool.reshape(L, n // bs, bs, f).at[layer, flat].set(
        _pool_rows(q, f)).reshape(L, n, f)
    scales = scales.at[layer, flat].set(
        sc.transpose(0, 2, 1).reshape(S * K, H))
    return pool, scales


def paged_quant_update(policy, pool, scales, layer, row_view, vals,
                       positions, lens, *, block_tables, block_size: int,
                       max_blocks: int):
    """The quantized pool WRITE every paged program shares: insert
    each row's fresh values into its dequantized f32 gathered view,
    then requantize + scatter back exactly the touched blocks
    (:func:`paged_requant_scatter`).

    ``row_view`` [S, H, T, Dh]: the row's dequantized view BEFORE this
    write; ``vals`` [S, H, P, Dh]: the fresh k or v run; ``positions``
    [S, P] absolute CONTIGUOUS write positions (``start_s +
    arange(P)``); ``lens`` [S]: columns at or beyond a row's len are
    pad. Returns (pool, scales, the post-insert f32 view — what the
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
        pool, scales = paged_requant_scatter(
            policy, pool, scales, layer, row_view, block_tables, first,
            last_pos, block_size=block_size, max_blocks=max_blocks)
    return pool, scales, row_view


def _quant_span(p_tokens: int, block_size: int, table_width: int) -> int:
    """Static window width for :func:`paged_requant_scatter`: the most
    blocks a contiguous ``p_tokens``-long write run can touch (one for
    a single token, wherever it lands)."""
    return min((p_tokens + block_size - 2) // block_size + 1, table_width)


def paged_kv_step(pools, layer, k, v, positions, lens, block_tables, *,
                  block_size: int, policy=None):
    """Write the rows' fresh (k, v) runs into ``layer`` of the pool and
    read every row's whole history back: ``(k_all, v_all, pools)``,
    the views [S, H, M*bs, Dh] holding the runs just written.

    ``pools`` = (k, v) under a passthrough policy: scatter, then gather
    (:func:`paged_write`, :func:`_gather_kv`). ``pools`` = (k, v,
    k_scale, v_scale) under a scaled one (serve/kv_quant.py): gather +
    DEQUANT, insert the run into the f32 view, quantize exactly the
    touched blocks back (:func:`paged_quant_update`) — the scores read
    the exact f32 run, the pool its quantized bytes."""
    head_shape = (k.shape[1], k.shape[3])
    if len(pools) == 2:
        pools = paged_write(*pools, layer, k, v, positions, lens,
                            block_tables=block_tables,
                            block_size=block_size)
        k_all, v_all = _gather_kv(pools, layer, policy, block_tables,
                                  block_size=block_size,
                                  head_shape=head_shape)
        return k_all, v_all, pools
    k_pool, v_pool, ks, vs = pools
    k_all, v_all = _gather_kv(pools, layer, policy, block_tables,
                              block_size=block_size, head_shape=head_shape)
    span = _quant_span(positions.shape[1], block_size,
                       block_tables.shape[1])
    k_pool, ks, k_all = paged_quant_update(
        policy, k_pool, ks, layer, k_all, k, positions, lens,
        block_tables=block_tables, block_size=block_size, max_blocks=span)
    v_pool, vs, v_all = paged_quant_update(
        policy, v_pool, vs, layer, v_all, v, positions, lens,
        block_tables=block_tables, block_size=block_size, max_blocks=span)
    return k_all, v_all, (k_pool, v_pool, ks, vs)


def _paged_attend_pallas(q, k, v, pools, layer, positions, lens,
                         block_tables, *, block_size: int, policy):
    """``attn_kernel="pallas"``: the fused block-table-walking kernel
    (ops/paged_attention.py) on ONE layer's pool. SLICE-AND-RESHAPE:
    the kernel and its touched-block requantizer take a layer's
    ``[slots, H, Dh]`` view, cut out of the carried pool here (and,
    under a scaled policy, put back whole) — a layer-sized copy the
    gathered-view path does not make. No benchmark cell runs this
    backend (ROADMAP D3 decides its fate); its parity tests pin it to
    the gathered-view math.

    Passthrough: the pool is written first and the kernel reads the
    fresh run back like any other slot. Scaled: the kernel scores the
    exact f32 run against the PRE-write pool (the fresh-kv override —
    the oracle's post-insert view), then only the run's touched blocks
    requantize, k and v symmetrically (paged_quant_window_update —
    pool bytes byte-identical to the gathered-view oracle's)."""
    from quintnet_tpu.ops.paged_attention import (
        paged_attention, paged_quant_window_update)

    head_shape = (k.shape[1], k.shape[3])

    def view(pool):
        return _pool_heads(
            lax.dynamic_index_in_dim(pool, layer, keepdims=False),
            *head_shape)

    if len(pools) == 2:
        pools = paged_write(*pools, layer, k, v, positions, lens,
                            block_tables=block_tables,
                            block_size=block_size)
        o = paged_attention(q, view(pools[0]), view(pools[1]),
                            block_tables, positions[:, 0],
                            block_size=block_size)
        return o, pools
    k_pool, v_pool, ks, vs = pools
    kc, vc, ksl, vsl = view(k_pool), view(v_pool), ks[layer], vs[layer]
    o = paged_attention(q, kc, vc, block_tables, positions[:, 0],
                        block_size=block_size, kv_scales=(ksl, vsl),
                        policy=policy, fresh_kv=(k, v))
    span = _quant_span(positions.shape[1], block_size,
                       block_tables.shape[1])
    out = []
    with jax.named_scope("kv_write"):
        for pool, cache, sc, scl, vals in ((k_pool, kc, ks, ksl, k),
                                           (v_pool, vc, vs, vsl, v)):
            cache, scl = paged_quant_window_update(
                policy, cache, scl, vals, positions, lens,
                block_tables=block_tables, block_size=block_size,
                max_blocks=span)
            out.append((lax.dynamic_update_slice(
                pool, cache.reshape(1, cache.shape[0], -1), (layer, 0, 0)),
                sc.at[layer].set(scl)))
    (k_pool, ks), (v_pool, vs) = out
    return o, (k_pool, v_pool, ks, vs)


def paged_attend(q, k, v, pools, layer, positions, lens, block_tables, *,
                 block_size: int, policy=None, attn_kernel: str = "xla",
                 scale: Optional[float] = None,
                 max_diag_rows: Optional[int] = None,
                 key_block: Optional[int] = None):
    """THE paged attention of every serving program, family and layout
    policy: each row's run of queries against its own cached history,
    the run's keys and values written into ``layer`` of the carried
    pool on the way. Returns ``(o, pools)``.

    ``q``: [S, Hq, G*P, Dh] — P tokens a row (decode 1, verify drafts +
    1, prefill S = 1 and the bucket), for ``Hq`` a multiple of the pool's
    kv heads (those are repeated over the gathered view) and/or with the
    ``G`` query heads that share a kv head laid out as rows of one score
    matrix (the view is then contracted once per kv head). ``k``/``v``:
    [S, Hkv, P, Dh], the fresh UNrepeated projections at absolute
    ``positions`` [S, P]; ``lens`` [S]: a row's columns at or beyond
    its len are pad. Column t of a row's view is valid for query i iff
    ``t <= positions[s, i]``: a decode step, a verify run and a
    (chunked) prefill are this one mask at different widths, which is
    what makes their tokens bit-equal.

    ``attn_kernel``: "xla" is the code's own best path, chosen from
    shapes and dtypes alone: a bf16/f16 pool under at most
    :data:`_MAX_DIAG_ROWS` query rows — decode and verify — walks each
    row's LIVE key blocks of the pool in place, heads on the lane
    diagonal (:func:`_paged_attend_walk`); everything else gathers the
    table's width (:func:`paged_kv_step`) and scores the view split
    into heads (:func:`_masked_sdpa`). The same mask and rounding
    contract in both. "pallas" is the whole-row fused kernel
    (:func:`_paged_attend_pallas`), bit-parity-pinned against the
    gathered view.

    ``max_diag_rows`` overrides :data:`_MAX_DIAG_ROWS` where the caller
    knows its program's kind better than a row count does (a family
    with 64 query heads keeps a verify run on the walk, and a
    narrow prefill bucket off it); ``key_block`` (positions, a multiple
    of ``block_size``) scores the run a block of keys at a time, only
    the blocks it can see (:func:`_paged_attend_key_blocked`: a prefill
    chunk against a long table)."""
    if attn_kernel == "pallas":
        if scale is not None:
            raise NotImplementedError(
                "the fused paged kernel scales its scores by "
                "1/sqrt(head_dim) only; a stated score scale needs "
                "attn_kernel='xla'")
        return _paged_attend_pallas(
            q, k, v, pools, layer, positions, lens, block_tables,
            block_size=block_size, policy=policy)
    if key_block is not None:
        if len(pools) != 2:
            raise NotImplementedError(
                "key-blocked paged attention reads a passthrough pool")
        return _paged_attend_key_blocked(
            q, k, v, pools, layer, positions, lens, block_tables,
            block_size=block_size, key_block=key_block, scale=scale)
    # a row's live blocks, in place, iff the rows are stored in a float
    # narrower than q (what _masked_sdpa calls `stored`; a scaled or
    # float8 pool's view is a widened f32 one) and the program has few
    # query rows
    if len(pools) == 2 and _as_gathered(q, pools[0], max_diag_rows):
        return _paged_attend_walk(
            q, k, v, pools, layer, positions, lens, block_tables,
            block_size=block_size, scale=scale)
    _note_read(block_tables.shape[1] * block_size)
    k_all, v_all, pools = paged_kv_step(
        pools, layer, k, v, positions, lens, block_tables,
        block_size=block_size, policy=policy)
    rep = q.shape[1] // k.shape[1]
    o = _masked_sdpa(q, repeat_kv(k_all, rep), repeat_kv(v_all, rep),
                     _seen(positions, q, block_tables, block_size),
                     page=block_size, scale=scale)
    return o, pools


def _seen(positions, q, block_tables, block_size: int):
    """:func:`paged_attend`'s mask over a whole table: column ``t`` of a
    row's view is valid for the query at ``positions[s, i]`` iff ``t <=
    positions[s, i]``. [S, 1, rows of q a head, T]."""
    valid = (jnp.arange(block_tables.shape[1] * block_size)[None, None, :]
             <= positions[:, :, None])[:, None]            # [S, 1, P, T]
    groups = q.shape[2] // positions.shape[1]
    if groups > 1 and valid.shape[2] > 1:
        valid = jnp.tile(valid, (1, 1, groups, 1))
    return valid


# Positions a trip of the per-row walk reads: a row holding ``n``
# positions reads ``ceil(n / WALK_KEY_BLOCK)`` blocks of this many, so
# half a block a row is read for nothing, and a trip costs its DMA
# descriptors and the recurrence's rescale whatever it carries.
WALK_KEY_BLOCK = 256

# What the paged program being TRACED on this thread inside
# :func:`noted_reads` reads of a row's table, one entry a
# :func:`paged_attend` call: the positions a read is rounded up to (the
# walk's key block; the table's whole width where the view is still
# gathered).
_READS = threading.local()


def _note_read(granule: int) -> None:
    notes = getattr(_READS, "notes", None)
    if notes is not None:
        notes.append(granule)


@contextlib.contextmanager
def noted_reads():
    """Collect what every :func:`paged_attend` call traced inside, on
    this thread, reads of a row's table (the list yielded): a row at
    position ``p`` reads ``ceil((p + 1) / g) * g`` positions of a call
    that noted ``g``. The engine's ``attended_rows`` is counted from
    it. One program body at a time: it does not nest."""
    _READS.notes = notes = []
    try:
        yield notes
    finally:
        _READS.notes = None


def _note_window_read(granule: int) -> None:
    notes = getattr(_READS, "window", None)
    if notes is not None:
        notes.append(granule)


@contextlib.contextmanager
def noted_window_reads():
    """Collect what every :func:`window_attend` call traced inside, on
    this thread, reads of a row's ring of one layer (the list yielded):
    the granule ``g`` a read is rounded up to — a row that wrote up to
    position ``p`` reads ``max(ceil(min(p + 1, ring) / g), 1) x g`` ring
    rows, one granule for a row that wrote nothing. ``g`` is the walk's
    key block (:func:`_window_attend_walk`), or the ring itself where the
    rings are sliced out whole: then every row of the program, idle ones
    too, reads its ring. The engine's ``window_read_rows`` is counted
    from it. One program body at a time: it does not nest."""
    _READS.window = notes = []
    try:
        yield notes
    finally:
        _READS.window = None


def _walk_tiles(rows: int, pool, block_tables, block_size: int):
    """``(key_block, padded_rows)`` of a per-row walk over ``pool``: a
    trip's positions — whole pages, :data:`WALK_KEY_BLOCK` or the
    table's at most — and ``rows`` query rows a sequence rounded up to
    whole sublane tiles of the stored dtype (16 rows of bf16)."""
    key_block = block_size * max(
        min(WALK_KEY_BLOCK // block_size, block_tables.shape[1]), 1)
    tile = 8 * 4 // pool.dtype.itemsize
    return key_block, -(-rows // tile) * tile


def kernel_where_it_lowers(here: bool, on_tpu: bool, kernel, plain, *args):
    """``kernel(*args)`` where a TPU kernel lowers, ``plain(*args)``
    where it does not: decided now if this process's backend (``here``)
    and a TPU (``on_tpu``) agree, else at lowering — both traced under
    ``lax.platform_dependent``, as :func:`local_attention` does. The
    walk's choice against the gathered view, and the grouped matmul's
    against ``ragged_dot`` (nn/moe.py)."""
    if here == on_tpu:
        return (kernel if here else plain)(*args)
    return lax.platform_dependent(*args, tpu=kernel, default=plain)


def _paged_attend_walk(q, k, v, pools, layer, positions, lens,
                       block_tables, *, block_size: int,
                       scale: Optional[float]):
    """:func:`paged_attend` for a program with FEW query rows on a
    bf16/f16 pool (decode, verify): the run is written, then each row
    walks its OWN live key blocks of ``(pool, layer)`` where they lie —
    ``max(positions[s]) // WALK_KEY_BLOCK + 1`` of them, one for a row
    at position 0 — and folds them into a running softmax
    (ops/paged_attention.paged_walk_attention). No view of the table's
    width exists, a block's rows cross HBM once, and a short or empty
    row costs a block where a full one costs the table. The arithmetic
    is :func:`_lane_diag_sdpa`'s — pool rows as stored, heads on the
    lane diagonal of the small operand (GQA without a repeat, ``scale``
    honoured), q and the probabilities rounded to the pool's dtype, f32
    sums, f32 softmax, the mask ``t <= positions[s, i]`` — with the
    softmax's sums in key-block order.

    The kernel is the TPU's, for pages of whole HBM tiles
    (ops/paged_attention.walk_lowers_for): a program lowered for another
    platform with no interpreter asked for, or over narrower pages,
    keeps :func:`_lane_diag_sdpa` on the gathered view — both traced
    under ``lax.platform_dependent``, as :func:`local_attention` does,
    where this process's backend and the chip would choose apart."""
    from quintnet_tpu.ops.paged_attention import (paged_walk_attention,
                                                  walk_lowers_for)

    _, hkv, P, dh = k.shape
    rows = q.shape[1] * q.shape[2]
    key_block, padded = _walk_tiles(rows, pools[0], block_tables, block_size)
    here = walk_lowers_for(jax.default_backend(), block_size)
    # what THIS process's program reads of a row
    _note_read(key_block if here else block_tables.shape[1] * block_size)
    pools = paged_write(*pools, layer, k, v, positions, lens,
                        block_tables=block_tables, block_size=block_size)

    def walk(q, k_pool, v_pool, layer, positions, block_tables):
        with jax.named_scope("sdpa"):
            qd, own = _diag_queries(q, k_pool, hkv, padded)
            qpos = jnp.pad(jnp.tile(positions, (1, rows // P)),
                           ((0, 0), (0, padded - rows)), constant_values=-1)
            o = paged_walk_attention(
                qd, qpos, k_pool, v_pool, layer, block_tables,
                block_size=block_size, key_block=key_block, head_dim=dh,
                scale=scale)
            return _off_diagonal(o, own, q.shape).astype(
                jnp.result_type(q, v_pool))

    def gathered(q, k_pool, v_pool, layer, positions, block_tables):
        k_rows, v_rows = _gather_kv((k_pool, v_pool), layer, None,
                                    block_tables, block_size=block_size,
                                    head_shape=None)
        return _lane_diag_sdpa(
            q, k_rows, v_rows, _seen(positions, q, block_tables, block_size),
            kv_heads=hkv, scale=scale)

    return kernel_where_it_lowers(
        here, walk_lowers_for("tpu", block_size), walk, gathered,
        q, *pools, layer, positions, block_tables), pools


def _paged_attend_key_blocked(q, k, v, pools, layer, positions, lens,
                              block_tables, *, block_size: int,
                              key_block: int, scale: Optional[float]):
    """:func:`paged_attend` for a run with MANY query rows against a
    LONG table (a prefill chunk: 48 heads x 1,024 tokens against 17,408
    positions): the run is written, then its keys are read ``key_block``
    positions at a time — that many table entries gathered, split into
    heads, scored, and folded into a running softmax
    (:func:`_online_merge`) — for only as many blocks as the run can
    SEE: ``ceil((start + len) / key_block)`` trips, counted on the
    device, whatever the table's width. One block's f32 scores are alive
    at a time (0.2 GB where the whole table's are 3.4), and a chunk at
    position 2,048 of a 17,408-wide table does a sixth of the work. Same
    mask, same rounding contract as :func:`_masked_sdpa`'s stored
    branch (q and the probabilities rounded to the pool's dtype, f32
    sums), the softmax's sums in block order. ``q`` [S, Hkv, G*P, Dh]
    (the query heads of a kv head as rows). A table whose width the
    block does not divide is read whole, in one trip."""
    S, hkv, P, dh = k.shape
    rows = q.shape[2]
    M = block_tables.shape[1]
    nb = max(key_block // block_size, 1)
    if M % nb:
        nb = M
    kb = nb * block_size
    pools = paged_write(*pools, layer, k, v, positions, lens,
                        block_tables=block_tables, block_size=block_size)
    qs = _as_stored(q, pools[0])
    pos_q = jnp.tile(positions, (1, rows // P))[:, None, :, None]
    trips = jnp.clip((jnp.max(positions[:, 0] + lens) + kb - 1) // kb,
                     1, M // nb)

    def block(c, carry):
        tables = lax.dynamic_slice_in_dim(block_tables, c * nb, nb, axis=1)
        k_c, v_c = _gather_kv(pools, layer, None, tables,
                              block_size=block_size, head_shape=(hkv, dh))
        with jax.named_scope("sdpa"):
            scores = jnp.einsum("shqd,shtd->shqt", qs, k_c,
                                preferred_element_type=jnp.float32)
            scores = (scores / math.sqrt(dh) if scale is None
                      else scores * scale)
            seen = (c * kb + jnp.arange(kb))[None, None, None, :] <= pos_q
            scores = jnp.where(seen, scores, -jnp.inf)
            m_new = jnp.max(scores, axis=-1)
            p = jnp.exp(scores - jnp.where(jnp.isfinite(m_new), m_new,
                                           0.0)[..., None])
            o_new = jnp.einsum("shqt,shtd->shqd",
                               _as_stored(p.astype(q.dtype), v_c), v_c,
                               preferred_element_type=jnp.float32)
            return _online_merge(*carry, m_new, jnp.sum(p, axis=-1), o_new)

    m0 = jnp.full((S, hkv, rows), -jnp.inf, jnp.float32)
    _, l, acc = lax.fori_loop(
        0, trips, block,
        (m0, jnp.zeros_like(m0), jnp.zeros((S, hkv, rows, dh), jnp.float32)))
    with jax.named_scope("sdpa"):
        # a pad row of the bucket sees at least its own (pad) key
        o = acc / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)[..., None]
    return o.astype(jnp.result_type(q, pools[1])), pools


# ---------------------------------------------------------------------
# The WINDOW store (sliding-window attention layers): a ring of ``R =
# window + block_size`` positions a SLOT beside the block pool,
# ``wk``/``wv`` [L_w, (slots + 1) * R, F] — pool rows like any other (a
# token's kv heads flattened, zero pad lanes), the last slot's ring the
# null one (warmup, dead rows and pad columns write there). Position
# ``p`` of the sequence in slot ``s`` lives at row ``s * R + p % R``, so
# a sequence holds ``R`` rows a layer whatever its length, a layer reads
# ``R`` rows of a sequence and never a table's width, and nothing is
# allocated or freed as the sequence grows. WHICH position a ring row
# holds is arithmetic on the positions the program is given: no length
# is stored, and a ring's rows from an earlier owner fall outside every
# mask of a sequence that starts at 0.
# ---------------------------------------------------------------------
def _ring_positions(last, ring: int):
    """The position each of a ring's ``ring`` rows holds once every
    position up to ``last`` [S] has been written: the largest ``p <=
    last`` with ``p % ring == r`` — NEGATIVE where the sequence has not
    reached row ``r`` yet (the row is an earlier owner's). [S, ring]."""
    r = jnp.arange(ring)[None, :]
    return last[:, None] - (last[:, None] - r) % ring


def window_write(wk, wv, layer, k, v, positions, lens, row0, *, ring: int):
    """Write every row's run of (k, v) [S, H, P, Dh] at ``positions``
    [S, P] into ``layer`` of the window store, rings ``row0 +
    arange(S)``: one scatter a buffer. Of a run longer than the ring
    only its LAST ``ring`` real columns are written (the earlier ones
    would be overwritten by them); those, columns at or beyond a row's
    ``lens`` and rows of ``lens`` 0 go to the null ring, the store's
    last."""
    S, _, P, _ = k.shape
    null = wk.shape[1] // ring - 1
    with jax.named_scope("kv_write"):
        col = jnp.arange(P)[None, :]
        keep = (col < lens[:, None]) & (col >= lens[:, None] - ring)
        slot = jnp.where(keep, (row0 + jnp.arange(S))[:, None], null)
        idx = (slot * ring + jnp.where(keep, positions, col) % ring
               ).reshape(S * P)
        return _put_rows(wk, layer, idx, k), _put_rows(wv, layer, idx, v)


def window_gather(buf, layer, row0, rows: int, *, ring: int):
    """The rings ``row0 + arange(rows)`` of ``layer``, as stored:
    [rows, ring, F]. A SLICE of the store (the rings of consecutive
    slots are adjacent), ``ring`` rows a sequence."""
    return lax.dynamic_slice(
        buf, (layer, row0 * ring, 0),
        (1, rows * ring, buf.shape[-1])).reshape(rows, ring, -1)


def window_attend(q, k, v, bufs, layer, positions, lens, row0, *,
                  window: int, ring: int,
                  max_diag_rows: Optional[int] = None):
    """:func:`paged_attend` for a SLIDING-WINDOW layer over the window
    store: query ``i`` of a row sees key ``j`` iff ``j <= i`` and ``j >
    i - window`` (``window`` keys, its own among them). ``q`` [S, Hq,
    G*P, Dh], ``k``/``v`` [S, Hkv, P, Dh] at absolute CONTIGUOUS
    ``positions`` [S, P] as there; ``bufs`` = (wk, wv) with rings of
    ``ring`` rows; row ``s`` of the run owns ring ``row0 + s``. Returns
    ``(o, bufs)``.

    Two orders, chosen from the run's width alone:

    - ``P <= ring - window + 1`` (a decode step, a verify run, a narrow
      prefill bucket): WRITE, THEN READ, as the block pool does — after
      the write the ring holds the ``ring`` positions up to the run's
      last, and the query at the run's first position still finds all
      ``window`` of its own: the ``ring - window`` spare rows are what
      the run overwrote. For a bf16/f16 store under ``max_diag_rows``
      each row walks its own ring's live rows in place, heads on the
      lane diagonal (:func:`_window_attend_walk`); otherwise the rings
      are sliced out whole and split into heads.
    - wider (a prefill chunk, up to twice the window and more): READ,
      THEN WRITE. The keys are the ring as the earlier chunks left it
      (up to ``ring`` positions before the run's first) beside the
      run's own, rounded to the store's dtype as a read-back would; the
      run's last ``ring`` columns are written afterwards.

    The mask is computed from positions in both (:func:`_ring_positions`):
    a ring row that holds nothing of this sequence yet reads a negative
    position and is masked."""
    wk, wv = bufs
    S, hkv, P, Dh = k.shape
    heads = (hkv, Dh)
    groups = q.shape[2] // P
    start = positions[:, 0]
    if ring < window:
        raise ValueError(f"a ring of {ring} rows cannot hold a window of "
                         f"{window}")
    pos_q = jnp.tile(positions, (1, groups))[:, None, :, None]  # [S,1,GP,1]

    def gathered(wk, wv):
        with jax.named_scope("window_gather"):
            return (window_gather(wk, layer, row0, S, ring=ring),
                    window_gather(wv, layer, row0, S, ring=ring))

    def split(rows):
        return _pool_heads(rows, *heads).transpose(0, 2, 1, 3)

    def seen():
        held = _ring_positions(start + lens - 1, ring)[:, None, None, :]
        return (held >= 0) & (held <= pos_q) & (held > pos_q - window)

    def diag(q, wk, wv):
        k_rows, v_rows = gathered(wk, wv)
        return _lane_diag_sdpa(q, k_rows[:, None], v_rows[:, None], seen(),
                               kv_heads=hkv)

    if P <= ring - window + 1:
        wk, wv = window_write(wk, wv, layer, k, v, positions, lens, row0,
                              ring=ring)
        if _as_gathered(q, wk, max_diag_rows):
            return _window_attend_walk(
                q, (wk, wv), layer, positions, lens, row0, window=window,
                ring=ring, kv_heads=hkv, gathered=diag), (wk, wv)
        _note_window_read(ring)
        k_rows, v_rows = gathered(wk, wv)
        o = _masked_sdpa(q, split(k_rows), split(v_rows), seen())
        return o, (wk, wv)
    _note_window_read(ring)
    k_rows, v_rows = gathered(wk, wv)
    held = _ring_positions(start - 1, ring)[:, None, None, :]
    col = jnp.arange(P)[None, None, None, :]
    row = jnp.tile(jnp.arange(P), groups)[None, None, :, None]
    valid = jnp.concatenate(
        [(held >= 0) & (held > pos_q - window),
         jnp.broadcast_to((col <= row) & (col > row - window),
                          (S, 1, groups * P, P))], axis=-1)
    k_all = jnp.concatenate([split(k_rows), k.astype(wk.dtype)], axis=2)
    v_all = jnp.concatenate([split(v_rows), v.astype(wv.dtype)], axis=2)
    o = _masked_sdpa(q, k_all, v_all, valid)
    return o, window_write(wk, wv, layer, k, v, positions, lens, row0,
                           ring=ring)


# Positions a trip of the walk over a sliding layer's RING aims at. A
# trip costs its DMA descriptors and the recurrence's rescale whatever
# it carries, and a ring is read whole once its sequence is older than
# it, so fewer, longer trips win there: on one v5e chip, the window
# cells' own sliding layers a decode step (`tools/attn_microbench.py
# --rings`), 528-row rings of 48 rows 0.230 ms a
# layer in three trips of 176, 0.213 in two of 272, 0.183 in one of
# 528 (0.225 sliced out whole); 4,112-row rings of 128 rows, 72 of them
# decoding, 1.32 ms in trips of 256, 1.22 of 464, 1.23 of 832 (5.01
# sliced out whole).
RING_KEY_BLOCK = 512


def _ring_key_block(pages: int, block_size: int,
                    fits=lambda key_block: True) -> int:
    """Positions a trip of the walk over a ring of ``pages`` pages of
    ``block_size`` reads: a whole ring takes as many trips as it holds
    :data:`RING_KEY_BLOCK`-s, to the nearest and at least one, with its
    pages spread evenly over them — 528 rows in one trip, 4,112 (257
    pages, a prime) in eight of 528 — or, where a key block of that
    many does not ``fit`` (a verify run's many query rows), the fewest
    trips whose block does (at least a page)."""
    trips = max((pages * block_size + RING_KEY_BLOCK // 2)
                // RING_KEY_BLOCK, 1)
    while True:
        key_block = -(-pages // trips) * block_size
        if trips >= pages or fits(key_block):
            return key_block
        trips += 1


def _window_attend_walk(q, bufs, layer, positions, lens, row0, *,
                        window: int, ring: int, kv_heads: int, gathered):
    """:func:`window_attend`'s write-then-read order on a bf16/f16
    store with few query rows (decode, verify): after the write each
    row walks its OWN ring of ``(wk / wv, layer)`` where it lies and
    folds it into a running softmax (ops/paged_attention.
    paged_walk_attention with a ``window``). Row ``s`` owns ring ``row0
    + s``, whose page ``j`` is store page ``(row0 + s) x ring_pages +
    j`` (a table made on the device from an iota); it reads ``ceil(min(
    last + 1, ring) / key_block)`` key blocks (:func:`_ring_key_block`)
    — what it wrote while younger than its ring, the ring once after —
    and a row of ``lens`` 0 one block, all of it masked (a finite output
    nothing reads). A query sees ring row ``r`` iff the position it
    holds (:func:`_ring_positions`) is this sequence's, at or before
    the query and inside its window. No slice of the store is taken.

    The arithmetic is :func:`_lane_diag_sdpa`'s — ring rows as stored,
    heads on the lane diagonal of the small operand, q and the
    probabilities rounded to the store's dtype, f32 sums, f32 softmax —
    with the softmax's sums in key-block order. Where the walk does not
    lower (ops/paged_attention.walk_lowers_for: another platform with no
    interpreter asked for, or pages under 8 rows) or its VMEM sum does
    not fit at a page a trip, ``gathered(q, wk, wv)`` runs instead — the
    caller's slice of every ring whole under :func:`_lane_diag_sdpa` —
    both traced under ``lax.platform_dependent`` where this process's
    backend and the chip would choose apart. Notes the granule a row's
    read is rounded up to (:func:`noted_window_reads`): the key block,
    or the ring."""
    from quintnet_tpu.ops.paged_attention import (VMEM_CAP_BYTES,
                                                  paged_walk_attention,
                                                  walk_lowers_for,
                                                  walk_vmem_bytes)

    wk, wv = bufs
    S, hq, gp, dh = q.shape
    P = positions.shape[1]
    bs = ring - window
    pages = ring // bs
    rows = hq * gp
    tile = 8 * 4 // wk.dtype.itemsize
    padded = -(-rows // tile) * tile

    def fits(key_block):
        return 2 * walk_vmem_bytes(
            rows=padded, lanes=wk.shape[-1], kept_lanes=wk.shape[-1],
            key_block=key_block, pools=2, pool_dtype=wk.dtype,
            q_dtype=wk.dtype) <= VMEM_CAP_BYTES

    key_block = _ring_key_block(pages, bs, fits)
    here = fits(key_block) and walk_lowers_for(jax.default_backend(), bs)
    # what THIS process's program reads of a row's ring
    _note_window_read(key_block if here else ring)

    def walk(q, wk, wv, layer, positions, lens):
        with jax.named_scope("sdpa"):
            qd, own = _diag_queries(q, wk, kv_heads, padded)
            # a row's columns at or beyond its lens wrote nothing: their
            # queries see nothing, and the row's last written position
            # is its largest
            real = jnp.arange(P)[None, :] < lens[:, None]
            qpos = jnp.pad(
                jnp.tile(jnp.where(real, positions, -1), (1, rows // P)),
                ((0, 0), (0, padded - rows)), constant_values=-1)
            tables = ((row0 + jnp.arange(S))[:, None] * pages
                      + jnp.arange(pages)[None, :])
            o = paged_walk_attention(
                qd, qpos, wk, wv, layer, tables, block_size=bs,
                key_block=key_block, head_dim=dh, window=window)
            return _off_diagonal(o, own, q.shape).astype(
                jnp.result_type(q, wv))

    return kernel_where_it_lowers(
        here, fits(key_block) and walk_lowers_for("tpu", bs), walk,
        lambda q, wk, wv, *_: gathered(q, wk, wv),
        q, wk, wv, layer, positions, lens)


# ---------------------------------------------------------------------
# The LATENT paged cache (multi-head latent attention): ONE row a token,
# ``[c | k_rope]`` — the normed compressed kv (``rank`` features) and
# the rotated shared rotary key (``rope`` features) — padded with zero
# lanes to the pool's width like every pool row; no V pool. All heads
# read the same row, so there is no head to split the rows by: the
# absorbed form contracts them where they lie in the pool, the
# materialized one as :func:`paged_gather` leaves them.
# ---------------------------------------------------------------------
def latent_write(pool, layer, rows, positions, lens, *, block_tables,
                 block_size: int):
    """:func:`paged_write` for the latent pool: ``rows`` [S, P, rank +
    rope] at ``positions`` [S, P] into ``layer`` of ``pool`` [L, slots,
    F], the pad lanes written as zeros; columns at or beyond a row's
    ``lens`` go to the null block. One scatter."""
    S, P, _ = rows.shape
    with jax.named_scope("kv_write"):
        idx = _write_index(positions, lens, block_tables, block_size)
        flat = _pool_rows(rows.reshape(S * P, 1, -1), pool.shape[-1])
        return pool.at[layer, idx].set(flat.astype(pool.dtype))


def latent_attend_absorbed(q_lat, q_rope, pool, layer, positions,
                           block_tables, *, block_size: int, scale: float):
    """The ABSORBED form (decode, verify): the queries were carried
    into the latent space (``q_lat`` [S, P, H, rank] = ``q_nope W_uk``
    per head, ``q_rope`` [S, P, H, rope] rotated), so all ``R = P * H``
    query rows of a sequence ``[q_lat | q_rope | zero pad]`` contract
    that sequence's rows of ``(pool, layer)`` AS STORED, the zero pad
    lanes of the query meeting the row's. Returns ``o_lat`` [S, P, H,
    rank] (``W_uv`` comes after).

    Each sequence walks its OWN live key blocks of the pool where they
    lie — ``max(positions[s]) // WALK_KEY_BLOCK + 1`` trips, one for a
    row at position 0 — and folds them into a running softmax
    (ops/paged_attention.paged_walk_attention with no v pool: the block
    the scores read is the block the values read, its first ``rank``
    lanes where ``rank`` is whole 128-lane tiles). No view of the
    table's width exists; a decoding row's 128 heads are 128 query rows
    on one latent row, a full matrix-unit tile with no diagonal. The
    arithmetic is :func:`_masked_sdpa`'s stored branch — the query and
    the probabilities rounded to the pool's dtype, f32 sums, f32
    softmax, the mask ``t <= positions[s, p]`` — with the softmax's
    sums in key-block order. Scope ``sdpa``.

    Chosen as :func:`_paged_attend_walk` chooses, from what the call
    can observe: the kernel takes a bf16/f16 pool, on a TPU with pages
    of whole HBM tiles or under the interpreter a caller turned on
    (ops/paged_attention.walk_lowers_for), for as many query rows as
    its own VMEM sum allows (``walk_vmem_bytes``: a verify bucket's
    ``R`` is 128 a drafted token). Everything else — an f32 pool,
    another platform, narrower pages — GATHERS the table's width
    (:func:`paged_gather`, scope ``kv_gather``) and contracts the view
    ``[S, T, F]`` in one matmul each for scores and values (scopes
    ``scores`` / ``values``), both traced under
    ``lax.platform_dependent`` where this process's backend and the
    chip would choose apart. That form lays its scores out ``[S, T,
    R]``, positions on the SUBLANES and the query rows on the lanes:
    the softmax then reduces over whole registers (``[S, R, T]`` took
    11.1 ms a layer at the published decode shapes where this takes
    2.4; my chip run, PR 31); its values' product also covers the
    rotary and pad lanes of the rows and drops them from the small
    result, where cutting them off the VIEW would copy it."""
    from quintnet_tpu.ops.paged_attention import (VMEM_CAP_BYTES,
                                                  paged_walk_attention,
                                                  walk_lowers_for,
                                                  walk_vmem_bytes)

    s, p, h, rank = q_lat.shape
    f, m = pool.shape[-1], block_tables.shape[1]
    t, r = m * block_size, p * h
    out_dtype = jnp.result_type(q_lat, pool)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).reshape(s, r, -1)
    key_block, padded = _walk_tiles(r, pool, block_tables, block_size)
    # the value lanes a free slice of a row gives
    kept = rank if rank % 128 == 0 else f
    fits = (pool.dtype in (jnp.bfloat16, jnp.float16)
            and 2 * walk_vmem_bytes(
                rows=padded, lanes=f, kept_lanes=kept, key_block=key_block,
                pools=1, pool_dtype=pool.dtype, q_dtype=pool.dtype)
            <= VMEM_CAP_BYTES)
    here = fits and walk_lowers_for(jax.default_backend(), block_size)
    # what THIS process's program reads of a row
    _note_read(key_block if here else t)

    def walk(q, pool, layer, positions, block_tables):
        with jax.named_scope("sdpa"):
            qd = jnp.pad(q.astype(pool.dtype),
                         ((0, 0), (0, padded - r), (0, f - q.shape[-1])))
            # query rows are laid out p * H + h
            qpos = jnp.pad(jnp.repeat(positions, h, axis=1),
                           ((0, 0), (0, padded - r)), constant_values=-1)
            o = paged_walk_attention(
                qd, qpos, pool, None, layer, block_tables,
                block_size=block_size, key_block=key_block,
                head_dim=q.shape[-1], scale=scale, kept_lanes=kept)
            return o[:, :r, :rank].reshape(s, p, h, rank).astype(out_dtype)

    def gathered(q, pool, layer, positions, block_tables):
        return _latent_absorbed_gathered(
            q, pool, layer, positions, block_tables, block_size=block_size,
            scale=scale, heads=h, rank=rank).astype(out_dtype)

    return kernel_where_it_lowers(
        here, fits and walk_lowers_for("tpu", block_size), walk, gathered,
        q, pool, layer, positions, block_tables)


def _latent_absorbed_gathered(q, pool, layer, positions, block_tables, *,
                              block_size: int, scale: float, heads: int,
                              rank: int):
    """:func:`latent_attend_absorbed` on a GATHERED view of the table's
    width (its docstring says when): ``q`` [S, R, rank + rope], query
    rows laid out ``p * heads + h``, against ``[S, T, F]`` rows of
    ``(pool, layer)`` in one matmul each for scores ``[S, T, R]`` and
    values. Returns ``o_lat`` [S, P, heads, rank] f32."""
    s, r, _ = q.shape
    p, f = r // heads, pool.shape[-1]
    t = block_tables.shape[1] * block_size
    with jax.named_scope("kv_gather"):
        rows = paged_gather(pool, layer, block_tables,
                            block_size=block_size).reshape(s, t, f)
    with jax.named_scope("scores"):
        qs = _as_stored(q, rows)
        pad = max(_MIN_DOT_ROWS - r, 0) if qs is not q else 0
        qs = jnp.pad(qs, ((0, 0), (0, pad), (0, f - qs.shape[-1])))
        # column c of a row's view is valid for the query at
        # positions[s, p] iff c <= positions[s, p] (paged_attend's mask)
        ok = jnp.arange(t)[None, :, None] <= positions[:, None, :]
        valid = jnp.pad(
            jnp.broadcast_to(ok[..., None],
                             (s, t, p, heads)).reshape(s, t, r),
            ((0, 0), (0, 0), (0, pad)))
        scores = jnp.einsum("stf,srf->str", rows, qs,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
        probs = _as_stored(
            jax.nn.softmax(scores, axis=1).astype(q.dtype), rows)
    with jax.named_scope("values"):
        o = jnp.einsum("str,stf->srf", probs, rows,
                       preferred_element_type=jnp.float32)
        return o[:, :r, :rank].reshape(s, p, heads, rank)


def latent_attend_materialized(q_nope, q_rope, view, kv_up, positions, *,
                               scale: float, v_dim: int,
                               head_group: int):
    """The MATERIALIZED form (prefill, chunked prefill): keys and
    values are rebuilt from the gathered latent rows — an earlier
    chunk's rows out of the pool among them — ``[k_nope | v] = c
    W_ukv``, and the scores are ``q_nope . k_nope + q_rope . k_rope``
    per head: ``rank + rope`` against ``nope + rope`` features a score,
    a quarter of the absorbed form's products once the run has more
    than a hundred-odd tokens to spread the rebuild over. ``q_nope``
    [S, P, H, nope], ``q_rope`` [S, P, H, rope], ``view`` [S, M, bs,
    F], ``kv_up`` [rank, H, nope + v_dim]. The heads go ``head_group``
    at a time (a loop): a bucket's f32 scores for all 128 heads of the
    published model at once would be gigabytes. Returns o [S, P, H,
    v_dim]. Same rounding contract as the absorbed form, plus the
    rebuilt keys and values held in the view's dtype. Scopes ``kv_up``
    / ``scores`` / ``values``."""
    s, p, h, nope = q_nope.shape
    _, m, bs, f = view.shape
    rank = kv_up.shape[0]
    rope = q_rope.shape[-1]
    t = m * bs
    rows = view.reshape(s, t, f)
    c, k_rope = rows[..., :rank], rows[..., rank:rank + rope]
    # scores [S, g, T, P]: positions on the sublanes, the run's tokens
    # on the lanes, so the softmax reduces over whole registers (one
    # head group of a 1,024-token bucket: 1.3 ms where [S, g, P, T]
    # took 10.1; my chip run, PR 31)
    valid = (jnp.arange(t)[None, :, None]
             <= positions[:, None, :])[:, None]          # [S, 1, T, P]
    g = min(head_group, h)
    if h % g:
        raise ValueError(f"head_group {g} must divide the {h} heads")
    out_dtype = jnp.result_type(q_nope, rows)

    def group(_, i):
        lo = i * g
        w = lax.dynamic_slice_in_dim(kv_up, lo, g, axis=1)
        qn = lax.dynamic_slice_in_dim(q_nope, lo, g, axis=2)
        qr = lax.dynamic_slice_in_dim(q_rope, lo, g, axis=2)
        with jax.named_scope("kv_up"):
            kv = jnp.einsum("stc,chn->sthn", c, _as_stored(w, c),
                            preferred_element_type=jnp.float32
                            ).astype(rows.dtype)
            k_nope, v = kv[..., :nope], kv[..., nope:]
        with jax.named_scope("scores"):
            scores = (jnp.einsum("sthn,sphn->shtp", k_nope,
                                 _as_stored(qn, rows),
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("str,sphr->shtp", k_rope,
                                   _as_stored(qr, rows),
                                   preferred_element_type=jnp.float32)
                      ) * scale
            scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
            probs = _as_stored(
                jax.nn.softmax(scores, axis=2).astype(q_nope.dtype), rows)
        with jax.named_scope("values"):
            o = jnp.einsum("shtp,sthv->sphv", probs, v,
                           preferred_element_type=jnp.float32)
        return None, o.astype(out_dtype)

    _, o = lax.scan(group, None, jnp.arange(h // g))   # [G, S, P, g, v]
    return o.transpose(1, 2, 0, 3, 4).reshape(s, p, h, v_dim)


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


def ring_paged_prefill(q, k, v, start, t0, pools, layer, *,
                       sp_axis: str, block_tables, block_size: int,
                       policy=None):
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
    block, exactly :func:`paged_write`'s convention. ``pools`` is the
    carried pool tuple — (k, v), or (k, v, k_scale, v_scale) under a
    scaled layout policy — addressed at ``layer``.

    Per call the sp wire carries ``2*sp`` ppermutes (the stacked K/V
    pair and its position vector rotate ``sp`` scan steps) plus one
    all_gather — the census analysis/specs.expected_serve_sp_prefill
    pins. Peak score memory is O(Pl * pool_row) per device instead of
    O(P * pool_row): the chunk's [P, P] score block never exists on any
    one rank, which is the RingAttention point — context length scales
    with device count, not one chip's memory.

    Returns (o [1, Hq, Pl, Dh] normalized local attention output,
    pools with the WHOLE chunk scattered)."""
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
    tables = block_tables[None]
    k_pool, v_pool = _gather_kv(pools, layer, policy, tables,
                                block_size=block_size,
                                head_shape=(k.shape[1], dh))
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
    pos2 = (start + jnp.arange(pl * sp, dtype=jnp.int32))[None, :]
    lens = jnp.reshape(t0 - start, (1,))
    if len(pools) == 2:
        return o, paged_write(*pools, layer, kv_full[0][None],
                              kv_full[1][None], pos2, lens,
                              block_tables=tables, block_size=block_size)
    # quantize-on-scatter (no extra collectives: the gathered prefix
    # views already hold the row, the chunk inserts into them and only
    # the touched private blocks requantize — every rank identically)
    k_cache, v_cache, ks, vs = pools
    span = _quant_span(pl * sp, block_size, block_tables.shape[0])
    k_cache, ks, _ = paged_quant_update(
        policy, k_cache, ks, layer, k_pool, kv_full[0][None], pos2, lens,
        block_tables=tables, block_size=block_size, max_blocks=span)
    v_cache, vs, _ = paged_quant_update(
        policy, v_cache, vs, layer, v_pool, kv_full[1][None], pos2, lens,
        block_tables=tables, block_size=block_size, max_blocks=span)
    return o, (k_cache, v_cache, ks, vs)


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


def _pool_tuple(k_cache, v_cache, kv_scales):
    """The carried pool tuple the paged helpers take, from the
    ``k_cache, v_cache, kv_scales=`` arguments of the entry points."""
    return (k_cache, v_cache, *(kv_scales if kv_scales is not None else ()))


def mha_prefill_paged_sp(p, x, k_cache, v_cache, start, t0, *,
                         num_heads: int, sp_axis: str, layer,
                         tp_axis: Optional[str] = None,
                         block_tables=None,
                         block_size: Optional[int] = None,
                         kv_scales=None, policy=None):
    """A one-row :func:`mha_verify_paged`'s sequence-parallel sibling
    (the chunked prefill of a long prompt): ``x``
    [1, Pl, D] is this sp rank's slice of the chunk's hidden states;
    the attention runs through :func:`ring_paged_prefill` (K/V sharded
    over ``sp_axis`` during the score pass, reassembled once for the
    pool write). The output projection is position-wise, so it stays
    local. LoRA is deliberately absent — the engine rejects the
    (adapters, sp) combination at construction."""
    q, k, v = _qkv_heads(p, x, num_heads)
    with jax.named_scope("sdpa"):
        o, pools = ring_paged_prefill(
            q, k, v, start, t0, _pool_tuple(k_cache, v_cache, kv_scales),
            layer, sp_axis=sp_axis, block_tables=block_tables,
            block_size=block_size, policy=policy)
    return (_proj_out(p, o, tp_axis), *pools)


def mha_verify_paged(p, x, k_cache, v_cache, positions, tail_lens, *,
                     num_heads: int, layer,
                     tp_axis: Optional[str] = None,
                     block_tables=None, block_size: Optional[int] = None,
                     lora=None, lora_scale=None,
                     kv_scales=None, policy=None,
                     attn_kernel: str = "xla"):
    """Paged attention of one layer, for every serving program: EVERY
    slot scores a run of tokens against its own cached row in ONE
    forward (:func:`paged_attend`) — speculative decoding's
    target-scoring step (serve/spec.py) when the run is the last
    sampled token + drafted continuations, a decode step at P == 1, a
    (chunked) prefill at S == 1.

    ``x``: [S, P, D] per-slot token runs at absolute ``positions``
    [S, P]; ``k_cache``/``v_cache``: the WHOLE pool
    [L, N_blocks*block_size, F] (serve/kv_pool.py), written and read at
    ``layer``; ``block_tables`` [S, M] maps each row's logical blocks
    to pool blocks. The runs' (k, v) scatter through each row's block
    table first (pad columns masked to the null block by
    ``tail_lens``), then each row's whole history — cached prefix +
    fresh run — is gathered back position-ordered and each token
    attends causally against it: column t is valid iff ``t <=
    positions[s, i]``.

    Returns (y [S, P, D], k_cache, v_cache). ``num_heads`` is LOCAL
    heads under ``tp_axis`` (head-sharded pool + RowParallel psum).
    ``lora``/``lora_scale``: per-slot packed adapters (serving
    multi-LoRA; nn/layers.lora_delta) — qkv's delta lands before the
    head split, proj's before the psum; zero-adapter rows are
    base-model rows exactly.

    ``kv_scales``/``policy`` (serve/kv_quant.py): under a scaled layout
    policy ``kv_scales`` is the (k_scale, v_scale) pair of whole
    [L, num_blocks, H] arrays, addressed at ``layer`` like the pools,
    and the return grows to (y, k_cache, v_cache, k_scale, v_scale).

    ``attn_kernel="pallas"``: the fused block-table-walking kernel
    instead of the gathered view (ops/paged_attention.py)."""
    q, k, v = _qkv_heads(p, x, num_heads, lora, lora_scale)
    o, pools = paged_attend(
        q, k, v, _pool_tuple(k_cache, v_cache, kv_scales), layer,
        positions, tail_lens, block_tables, block_size=block_size,
        policy=policy, attn_kernel=attn_kernel)
    return (_proj_out(p, o, tp_axis, lora, lora_scale), *pools)


def mha_decode(p, x, k_cache, v_cache, pos, *, num_heads: int,
               tp_axis: Optional[str] = None):
    """Single-token cached attention on the DENSE single-request cache
    (models/gpt2_generate.py): x [B, 1, D], caches [B, H, T, Dh],
    ``pos`` the (dynamic, scalar) write position shared by the whole
    batch. Returns (y, k_cache, v_cache). The continuous-batching
    decode step is :func:`mha_verify_paged` at one token a row — same
    math on the gathered view; tests/test_serve.py holds the two
    token-for-token equal.

    The reference's generation loop re-runs the full prefix every step
    (utils/metrics.py:74-149, O(T^2) per token); here one token attends
    against the cache — O(T) per token, fully jittable (static shapes,
    dynamic_update_slice for the cache write, masked softmax over the
    not-yet-written tail).

    ``tp_axis``: head-sharded decode — ``num_heads`` is LOCAL heads, the
    cache holds this rank's heads, and the output projection psums over
    the axis (RowParallel, same as mha_apply's training path). The
    reference skips generation entirely under any parallelism
    (GPT2_Trainer.py:509-555)."""
    q, k, v = _qkv_heads(p, x, num_heads)
    with jax.named_scope("kv_write"):
        k_cache = lax.dynamic_update_slice(k_cache, k, (0, 0, pos, 0))
        v_cache = lax.dynamic_update_slice(v_cache, v, (0, 0, pos, 0))
    valid = (jnp.arange(k_cache.shape[2]) <= pos)[None, :]      # [1, T]
    o = _masked_sdpa(q, k_cache, v_cache, valid[:, None, None, :])
    return _proj_out(p, o, tp_axis), k_cache, v_cache
