"""Pallas TPU flash-attention kernels: forward AND hand-tiled backward.

Tiles Q/K/V through VMEM with online-softmax accumulators in scratch so
the [S, S] score matrix never reaches HBM (the reference relies on
cuDNN's fused SDPA — gpt2_attention.py:156-161; this is the TPU-native
equivalent, written against jax.experimental.pallas).

Forward grid: (batch*heads, q_blocks, k_blocks), k innermost — scratch
accumulators persist across the k dimension; the output block and the
row logsumexp (saved for backward, FlashAttention-2 style) are finalised
at the last k step.

Backward: two kernels (TPU Pallas has no cross-grid-cell atomics, so
dK/dV and dQ accumulate over different grid orders):
- dK/dV: grid (bh, k_blocks, q_blocks), q innermost, dk/dv in scratch;
- dQ:    grid (bh, q_blocks, k_blocks), k innermost, dq in scratch;
with the standard recurrence p = exp(s - lse), dv += p^T dO,
ds = p * (dO v^T - delta), dk += ds^T q, dq += ds k, where
delta = rowsum(dO * O) is precomputed outside the kernel.

Causal grid pruning: fully-masked blocks (k block strictly above the
diagonal) skip ALL their matmuls via pl.when in forward and both
backward kernels — ~2x less MXU work at long S. (The block DMA still
runs — rectangular grids — but long-sequence attention is FLOPs-bound.)

Throughput notes (round-4): matmul inputs stay in their NATIVE dtype —
bf16 activations hit the MXU at full bf16 rate with f32 accumulation
(`preferred_element_type`); the previous unconditional f32 upcast halved
matmul throughput. The causal iota/mask is built only for tiles that
CROSS the diagonal (lax.cond); interior tiles run unmasked.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # avoid literal -inf inside the kernel (exp/max safety)


def _block_live(qi, ki, block_q: int, block_k: int):
    """True when the (qi, ki) tile intersects the causal lower triangle:
    its smallest column index <= its largest row index."""
    return ki * block_k <= qi * block_q + block_q - 1


def _block_needs_mask(qi, ki, block_q: int, block_k: int):
    """True when the tile CROSSES the diagonal (some but not all entries
    masked). Fully-below-diagonal tiles skip the iota/where entirely —
    at long S the vast majority of live tiles."""
    return ki * block_k + block_k - 1 > qi * block_q


def _causal_mask(s, qi, ki, block_q: int, block_k: int):
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(cols <= rows, s, NEG_INF)


def _segment_mask(s, sq_ref, sk_ref):
    """Mask score entries whose q and k positions belong to different
    packed segments (segment refs carried as [1, blk, 1] int32 — the
    same trailing-unit-dim trick the lse output uses)."""
    sq = sq_ref[0][:, 0]                           # [bq]
    sk = sk_ref[0][:, 0]                           # [bk]
    return jnp.where(sq[:, None] == sk[None, :], s, NEG_INF)


def _segment_overlap(sq_ref, sk_ref):
    """False when the q and k tiles cannot share any segment id (their
    id RANGES are disjoint — conservative for arbitrary ids; exact for
    the monotone packed-document layout, where it prunes every
    fully-cross-document tile. Non-monotone ids may keep a tile live
    whose entries are all masked, which costs work but never
    correctness — _segment_mask still zeroes the cross pairs).
    Combined into the pl.when liveness so pruned tiles skip all three
    MXU matmuls, the same treatment the causal grid pruning gets."""
    sq = sq_ref[0][:, 0]
    sk = sk_ref[0][:, 0]
    return ((jnp.max(sk) >= jnp.min(sq))
            & (jnp.min(sk) <= jnp.max(sq)))


def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, has_seg: bool):
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = _block_live(qi, ki, block_q, block_k) if causal else ki >= 0
    if has_seg:
        live = live & _segment_overlap(sq_ref, sk_ref)

    @pl.when(live)
    def _accumulate():
        # native-dtype MXU inputs (bf16 in -> bf16 matmul, f32
        # accumulate): upcasting to f32 first would HALVE matmul
        # throughput on v5e; softmax stats stay f32 regardless
        q = q_ref[0]                               # [bq, d]
        k = k_ref[0]                               # [bk, d]
        v = v_ref[0]                               # [bk, d]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk] f32

        if causal:
            # only diagonal-crossing tiles pay the iota/mask; fully
            # lower-triangle tiles are unmasked
            s = jax.lax.cond(
                _block_needs_mask(qi, ki, block_q, block_k),
                lambda t: _causal_mask(t, qi, ki, block_q, block_k),
                lambda t: t, s)
        if has_seg:
            s = _segment_mask(s, sq_ref, sk_ref)

        m_prev = m_scr[:, :1]                      # [bq, 1]
        l_prev = l_scr[:, :1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        if has_seg:
            # a live tile can be FULLY segment-masked (k tile from a
            # different packed document): m_new stays NEG_INF there and
            # exp(s - m_new) would be exp(0) = 1 for every masked entry.
            # Guard the exponent base; m_scr still records the true max
            # (the recurrence and the final lse are unchanged for rows
            # that ever see a valid entry — and every row sees at least
            # its own diagonal position).
            m_exp = jnp.where(m_new > 0.5 * NEG_INF, m_new, 0.0)
        else:
            m_exp = m_new
        p = jnp.exp(s - m_exp)                     # NEG_INF -> 0
        l_cur = jnp.sum(p, axis=1, keepdims=True)
        corr = jnp.exp(m_prev - m_exp)
        l_new = l_prev * corr + l_cur
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # lse carried as [bq, 1] (trailing unit dim keeps the block
        # legal for Mosaic: last dims must be (8k, 128k) or array-equal)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _seg3(segments, b, s):
    """[B, S] int32 segment ids -> [B, S, 1] (the block-legal layout)."""
    return segments.astype(jnp.int32).reshape(b, s, 1)


def _flash_fwd(q, k, v, segments, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    has_seg = segments is not None

    qr = q.reshape(b * h, s, d)
    kr = k.reshape(b * h, s, d)
    vr = v.reshape(b * h, s, d)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, has_seg=has_seg)
    grid = (b * h, s // bq, s // bk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
    ]
    inputs = [qr, kr, vr]
    if has_seg:
        # segments are per-BATCH (shared by heads): index_map divides
        # the flattened batch*head grid coordinate back down
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh // h, qi, 0)),
            pl.BlockSpec((1, bk, 1), lambda bh, qi, ki: (bh // h, ki, 0)),
        ]
        seg = _seg3(segments, b, s)
        inputs += [seg, seg]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(*inputs)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s, 1)


def _bwd_block(q, k, v, do, lse, delta, qi, ki, seg_refs, *, scale, causal,
               block_q, block_k):
    """Shared per-tile backward math -> (p, ds), both [bq, bk] f32.
    Matmul inputs stay in their native dtype (bf16 MXU when bf16 in).
    ``seg_refs``: (sq_ref, sk_ref) or None; masked entries have
    s = NEG_INF so p = exp(s - lse) = 0 and ds = 0 — no extra guard
    needed (lse is finite for every row: the diagonal is always
    same-segment)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        s = jax.lax.cond(
            _block_needs_mask(qi, ki, block_q, block_k),
            lambda t: _causal_mask(t, qi, ki, block_q, block_k),
            lambda t: t, s)
    if seg_refs is not None:
        s = _segment_mask(s, *seg_refs)
    p = jnp.exp(s - lse)                          # [bq, bk]; masked -> 0
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # [bq, bk]
    ds = p * (dp - delta) * scale
    return p, ds


def _bwd_dkv_kernel(*refs, scale: float, causal: bool, block_q: int,
                    block_k: int, has_seg: bool):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        seg_refs = (sq_ref, sk_ref)
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        seg_refs = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = _block_live(qi, ki, block_q, block_k) if causal else qi >= 0
    if has_seg:
        live = live & _segment_overlap(sq_ref, sk_ref)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p, ds = _bwd_block(q, k, v, do, lse_ref[0], delta_ref[0], qi, ki,
                           seg_refs, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # p^T dO  [bk, d]
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # ds^T q  [bk, d]

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale: float, causal: bool, block_q: int,
                   block_k: int, has_seg: bool):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dq_ref, dq_scr) = refs
        seg_refs = (sq_ref, sk_ref)
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        seg_refs = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = _block_live(qi, ki, block_q, block_k) if causal else ki >= 0
    if has_seg:
        live = live & _segment_overlap(sq_ref, sk_ref)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        _, ds = _bwd_block(q, k, v, do, lse_ref[0], delta_ref[0], qi, ki,
                           seg_refs, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # ds k  [bq, d]

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, segments, out, lse, do, causal: bool, block_q: int,
               block_k: int, interpret: bool):
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    bq = min(block_q, s)
    bk = min(block_k, s)
    has_seg = segments is not None

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)       # [b, h, s, 1]

    qr = q.reshape(b * h, s, d)
    kr = k.reshape(b * h, s, d)
    vr = v.reshape(b * h, s, d)
    dor = do.reshape(b * h, s, d)
    lser = lse.reshape(b * h, s, 1)
    dr = delta.reshape(b * h, s, 1)
    seg = _seg3(segments, b, s) if has_seg else None

    q_spec = pl.BlockSpec((1, bq, d), lambda bh, a, b_: (bh, a, 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda bh, a, b_: (bh, a, 0))

    # dK/dV: k blocks on grid dim 1, q innermost (dim 2)
    kv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq,
        block_k=bk, has_seg=has_seg)
    kv_in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),  # q
        pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),  # k
        pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),  # v
        pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),  # do
        pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),  # lse
        pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),  # delta
    ]
    kv_inputs = [qr, kr, vr, dor, lser, dr]
    if has_seg:
        kv_in_specs += [
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh // h, qi, 0)),
            pl.BlockSpec((1, bk, 1), lambda bh, ki, qi: (bh // h, ki, 0)),
        ]
        kv_inputs += [seg, seg]
    dk, dv = pl.pallas_call(
        kv_kernel,
        grid=(b * h, s // bk, s // bq),
        in_specs=kv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*kv_inputs)

    # dQ: q blocks on grid dim 1, k innermost (dim 2)
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=bq,
        block_k=bk, has_seg=has_seg)
    dq_in_specs = [
        q_spec,
        pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        q_spec,
        row_spec,
        row_spec,
    ]
    dq_inputs = [qr, kr, vr, dor, lser, dr]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh // h, qi, 0)),
            pl.BlockSpec((1, bk, 1), lambda bh, qi, ki: (bh // h, ki, 0)),
        ]
        dq_inputs += [seg, seg]
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, s // bq, s // bk),
        in_specs=dq_in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_inputs)

    rs = lambda x: x.reshape(b, h, s, d)
    return rs(dq), rs(dk), rs(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _pallas_flash(q, k, v, segments, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, segments, causal, block_q, block_k,
                        interpret)
    return out


def _fa_fwd(q, k, v, segments, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, segments, causal, block_q, block_k,
                          interpret)
    return out, (q, k, v, segments, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, segments, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, segments, out, lse, g, causal,
                            block_q, block_k, interpret)
    return dq, dk, dv, None  # segment ids: integer input, no cotangent


_pallas_flash.defvjp(_fa_fwd, _fa_bwd)


def pallas_flash_attention(q, k, v, causal: bool = False,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False, segment_ids=None):
    """[B, H, S, D] fused attention via the Pallas TPU kernels (fwd and
    hand-tiled bwd).

    ``segment_ids``: optional [B, S] int32 packed-document ids —
    positions in different segments never attend to each other (the
    masking runs INSIDE the kernel, so PackedLMDataset training keeps
    the fused path; reference analogue: none — its sdpa call has no
    packing support either, gpt2_attention.py:156-161).

    ``interpret=True`` runs the kernels in the Pallas interpreter (CPU
    testing). S must divide by the block sizes (the dispatcher in
    ops/flash_attention.py falls back to jnp otherwise).
    """
    return _pallas_flash(q, k, v, segment_ids, causal, block_q, block_k,
                         interpret)
