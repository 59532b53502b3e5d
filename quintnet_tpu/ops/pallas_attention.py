"""Pallas TPU flash-attention kernels: forward AND hand-tiled backward,
in two geometries. Which one a model runs is
``nn/attention.local_attention_path``'s choice, from the shape.

Both keep the [S, S] scores on the chip (the reference relies on cuDNN's
fused SDPA — gpt2_attention.py:156-161; this is the TPU-native
equivalent, written against jax.experimental.pallas) and do ``sdpa``'s
arithmetic: matmul inputs in their NATIVE dtype (bf16 in -> bf16 matrix
unit, f32 accumulation), f32 scores, f32 max and sum, probabilities cast
to the inputs' dtype for the value matmul; p = exp(s - lse) backward,
dv += p^T dO, ds = p * (dO v^T - delta), dk += ds^T q, dq += ds k with
delta = rowsum(dO * O). Fully masked tiles are skipped, nothing else.

**Resident** (:func:`resident_flash_attention`; the second half of this
file): a grid step is one head, whole in VMEM, the tile loop unrolled
inside; one backward kernel. The training cells' geometry (S 1,024,
Dh 64): 6.6 ms a layer forward + backward + recomputed forward at
[32, 12, 1024, 64] against 20.9 for XLA's ``sdpa`` and 10.1-50.1 for
the streamed geometry at 1,024- to 128-wide tiles (my chip runs, PR 32;
PERF.md section 6).

**Streamed** (:func:`pallas_flash_attention`): a grid step is one tile.
Forward grid (batch*heads, q_blocks, k_blocks), k innermost — scratch
accumulators persist across the k dimension; the output block and the
row logsumexp (saved for backward, FlashAttention-2 style) are finalised
at the last k step. Backward: two kernels (TPU Pallas has no
cross-grid-cell atomics, so dK/dV and dQ accumulate over different grid
orders): dK/dV on grid (bh, k_blocks, q_blocks), q innermost; dQ on
(bh, q_blocks, k_blocks), k innermost; delta precomputed outside. Tiles
above the diagonal skip their matmuls via ``pl.when`` (their DMA still
runs: the grid is rectangular); the causal iota/mask is built only for
tiles that CROSS the diagonal (``lax.cond``). For sequences past what
stays resident.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # avoid literal -inf inside the kernel (exp/max safety)

# The resident geometry's scoped-VMEM request (the default is 16 MiB of
# the chip's 128): backward holds q, k, v, o, dO in and dq, dk, dv out,
# double-buffered at 256 B a position (Dh <= 128 fills a lane row), and
# the f32 dq accumulator — 4.6 kB a position — plus the unrolled tiles'
# temporaries. S 4,096 compiles inside it; at 8,192 the compiler plans
# 146 MiB (my chip run, PR 32).
RESIDENT_VMEM_LIMIT = 64 * 1024 * 1024


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


# ---------------------------------------------------------------------
# The resident geometry: a whole head in VMEM, the tile loop inside.
#
# Up to a few thousand positions a head's q, k, v (and, backward, o, dO
# and the f32 dq accumulator) fit in VMEM many times over (1,024 x 64
# bf16 is 128 kB, padded to 256 kB of lanes), so a grid step takes one
# whole head and walks the (q tile, k tile) pairs of the causal lower
# triangle in a loop the compiler sees unrolled: no grid step per tile,
# no tile fetched to be skipped, m / l / acc in values not scratch, and
# ONE backward pass (five matmuls a tile pair: the scores and dO V^T
# are computed once, not once each for dK/dV and dQ; delta inside).
#
# The row statistics are lane-dense: ``lse`` and ``delta`` are
# [B*H, 1, S] rows. The forward finds them as [bq, 1] columns and turns
# them once a q tile (:func:`_col_to_row`); the backward works on the
# TRANSPOSED tile, s^T = k q^T [bk, bq], where a row statistic
# broadcasts along sublanes as it lies and dV = p^T dO, dK = ds^T q are
# plain matmuls (only dQ = (ds^T)^T k contracts dim 0).
# ---------------------------------------------------------------------
def _col_to_row(col):
    """[n, 1] f32 -> [1, n]: the column laid on the diagonal of an
    [n, n] tile and summed over sublanes — iota, compare, select and a
    sublane reduce, all of which Mosaic has for every n (a [n, 1] ->
    [1, n] reshape or transpose it has not). Exact: one non-zero a
    column."""
    n = col.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(rows == cols, col, 0.0), axis=0,
                   keepdims=True)


def _tile_pairs(s: int, block_q: int, block_k: int, causal: bool):
    """The live (q tile, k tile, crosses the diagonal) triples, static."""
    out = []
    for i in range(s // block_q):
        for j in range(s // block_k):
            if causal and j * block_k > i * block_q + block_q - 1:
                continue                      # wholly above the diagonal
            crosses = causal and j * block_k + block_k - 1 > i * block_q
            out.append((i, j, crosses))
    return out


def _resident_fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                         block_k: int, has_seg: bool):
    if has_seg:
        q_ref, k_ref, v_ref, sqc_ref, skr_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    _, s, d = q_ref.shape
    pairs = _tile_pairs(s, block_q, block_k, causal)
    for i in range(s // block_q):
        rq = pl.ds(i * block_q, block_q)
        q = q_ref[0, rq, :]                            # [bq, d]
        m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((block_q, 1), jnp.float32)
        acc = jnp.zeros((block_q, d), jnp.float32)
        for (_, j, crosses) in [t for t in pairs if t[0] == i]:
            rk = pl.ds(j * block_k, block_k)
            k = k_ref[0, rk, :]
            v = v_ref[0, rk, :]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if crosses:
                sc = _causal_mask(sc, i, j, block_q, block_k)
            if has_seg:
                sc = jnp.where(sqc_ref[0, rq, :] == skr_ref[0, :, rk],
                               sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            # a tile can be wholly another document's: see the
            # streamed kernel's m_exp
            m_exp = (jnp.where(m_new > 0.5 * NEG_INF, m_new, 0.0)
                     if has_seg else m_new)
            p = jnp.exp(sc - m_exp)
            corr = jnp.exp(m - m_exp)
            l = l * corr + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m = m_new
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rq, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, :, rq] = _col_to_row(m + jnp.log(l))


def _causal_mask_t(st, qi, ki, block_q: int, block_k: int):
    """:func:`_causal_mask` for a transposed tile [bk, bq]."""
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    return jnp.where(kpos <= qpos, st, NEG_INF)


def _resident_bwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                         block_k: int, has_seg: bool):
    if has_seg:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, skc_ref, sqr_ref,
         dq_ref, dk_ref, dv_ref, dq_scr, delta_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
         dq_ref, dk_ref, dv_ref, dq_scr, delta_scr) = refs
    _, s, d = q_ref.shape
    pairs = _tile_pairs(s, block_q, block_k, causal)
    dq_scr[:] = jnp.zeros_like(dq_scr)
    for i in range(s // block_q):
        rq = pl.ds(i * block_q, block_q)
        delta_scr[:, rq] = _col_to_row(jnp.sum(
            do_ref[0, rq, :].astype(jnp.float32)
            * o_ref[0, rq, :].astype(jnp.float32), axis=1, keepdims=True))
    for j in range(s // block_k):
        rk = pl.ds(j * block_k, block_k)
        k = k_ref[0, rk, :]                                # [bk, d]
        v = v_ref[0, rk, :]
        dk = jnp.zeros((block_k, d), jnp.float32)
        dv = jnp.zeros((block_k, d), jnp.float32)
        for (i, _, crosses) in [t for t in pairs if t[1] == j]:
            rq = pl.ds(i * block_q, block_q)
            q = q_ref[0, rq, :]                            # [bq, d]
            do = do_ref[0, rq, :]
            st = jax.lax.dot_general(                      # s^T [bk, bq]
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if crosses:
                st = _causal_mask_t(st, i, j, block_q, block_k)
            if has_seg:
                st = jnp.where(skc_ref[0, rk, :] == sqr_ref[0, :, rq],
                               st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, :, rq])           # masked -> 0
            dv = dv + jax.lax.dot_general(
                pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(                     # (dO v^T)^T
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta_scr[:, rq]) * scale).astype(q.dtype)
            dk = dk + jax.lax.dot_general(
                dst, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_scr[rq, :] = dq_scr[rq, :] + jax.lax.dot_general(
                dst, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_ref[0, rk, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rk, :] = dv.astype(dv_ref.dtype)
    dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _seg_specs(segments, b, h, s):
    """A batch row's segment ids as a [S, 1] column and a [1, S] row,
    shared by its ``h`` heads (grid steps)."""
    seg = segments.astype(jnp.int32)
    return ([pl.BlockSpec((1, s, 1), lambda n: (n // h, 0, 0)),
             pl.BlockSpec((1, 1, s), lambda n: (n // h, 0, 0))],
            [seg.reshape(b, s, 1), seg.reshape(b, 1, s)])


_RESIDENT_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel",), vmem_limit_bytes=RESIDENT_VMEM_LIMIT)


def _resident_fwd(q, k, v, segments, causal, block_q, block_k, interpret):
    b, h, s, d = q.shape
    bq, bk = min(block_q, s), min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    has_seg = segments is not None
    head_spec = pl.BlockSpec((1, s, d), lambda n: (n, 0, 0))
    row_spec = pl.BlockSpec((1, 1, s), lambda n: (n, 0, 0))
    in_specs = [head_spec] * 3
    inputs = [x.reshape(b * h, s, d) for x in (q, k, v)]
    if has_seg:
        specs, segs = _seg_specs(segments, b, h, s)
        in_specs, inputs = in_specs + specs, inputs + segs
    out, lse = pl.pallas_call(
        functools.partial(_resident_fwd_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, block_q=bq, block_k=bk,
                          has_seg=has_seg),
        grid=(b * h,),
        in_specs=in_specs,
        out_specs=[head_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32)],
        compiler_params=_RESIDENT_PARAMS,
        interpret=interpret,
        name="flash_attention_fwd",
    )(*inputs)
    return out.reshape(b, h, s, d), lse


def _resident_bwd(q, k, v, segments, out, lse, do, causal, block_q, block_k,
                  interpret):
    b, h, s, d = q.shape
    bq, bk = min(block_q, s), min(block_k, s)
    has_seg = segments is not None
    head_spec = pl.BlockSpec((1, s, d), lambda n: (n, 0, 0))
    in_specs = [head_spec] * 5 + [
        pl.BlockSpec((1, 1, s), lambda n: (n, 0, 0))]
    inputs = [x.reshape(b * h, s, d) for x in (q, k, v, out, do)] + [lse]
    if has_seg:
        specs, segs = _seg_specs(segments, b, h, s)
        in_specs, inputs = in_specs + specs, inputs + segs
    grads = pl.pallas_call(
        functools.partial(_resident_bwd_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, block_q=bq, block_k=bk,
                          has_seg=has_seg),
        grid=(b * h,),
        in_specs=in_specs,
        out_specs=[head_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), x.dtype)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32),     # dq
                        pltpu.VMEM((1, s), jnp.float32)],    # delta
        compiler_params=_RESIDENT_PARAMS,
        interpret=interpret,
        name="flash_attention_bwd",
    )(*inputs)
    return tuple(x.reshape(b, h, s, d) for x in grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _resident_flash(q, k, v, segments, causal, block_q, block_k, interpret):
    return _resident_fwd(q, k, v, segments, causal, block_q, block_k,
                         interpret)[0]


def _rf_fwd(q, k, v, segments, causal, block_q, block_k, interpret):
    out, lse = _resident_fwd(q, k, v, segments, causal, block_q, block_k,
                             interpret)
    return out, (q, k, v, segments, out, lse)


def _rf_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, segments, out, lse = res
    return (*_resident_bwd(q, k, v, segments, out, lse, g, causal, block_q,
                           block_k, interpret), None)


_resident_flash.defvjp(_rf_fwd, _rf_bwd)


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
    testing). S must divide by the block sizes
    (``nn/attention.local_attention_path`` sends other shapes elsewhere).
    The streamed geometry: a grid step a tile, k innermost, two backward
    kernels — for sequences past what stays resident in VMEM.
    """
    return _pallas_flash(q, k, v, segment_ids, causal, block_q, block_k,
                         interpret)


def resident_flash_attention(q, k, v, causal: bool = False,
                             block_q: int = 256, block_k: int = 256,
                             interpret: bool = False, segment_ids=None):
    """[B, H, S, D] fused attention in the resident geometry: one head a
    grid step, whole in VMEM, the tile loop unrolled inside; forward and
    ONE backward kernel (``flash_attention_fwd``, ``flash_attention_bwd``
    on a device trace). Same contract as :func:`pallas_flash_attention`
    (``segment_ids``, ``interpret``, S a multiple of both blocks). VMEM
    bounds S (``RESIDENT_VMEM_LIMIT``): the chooser calls this up to
    ``nn/attention.RESIDENT_MAX_SEQ``."""
    return _resident_flash(q, k, v, segment_ids, causal, block_q, block_k,
                           interpret)
