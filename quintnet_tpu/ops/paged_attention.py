"""Fused paged-attention Pallas kernels for the serving hot paths.

The serving attention entry point (nn/attention.py ``paged_attend``,
under ``mha_verify_paged`` and the llama and hybrid blocks) is
gathered-view math wherever a program has many query rows or the pool
is f32, scaled or float8: materialize every block of a row's
block table into a position-ordered ``[S, H, T, Dh]`` HBM view
(``paged_gather``), matmul against it, and — under a scaled KV layout
policy — run a separate dequantize pass before the matmul ever sees a
byte. Each step moves the whole gathered KV through HBM twice.

This module is the serving twin of ops/pallas_attention.py: ONE Pallas
kernel family that walks the block table INSIDE the kernel (vLLM's
PagedAttention insight, Kwon et al. — PAPERS.md — expressed in Pallas)
and covers all three serving shapes, which are the same computation at
different widths:

- decode:  S rows x 1 query          (P = 1),
- verify:  S rows x k+1 queries      (P = draft bucket + 1),
- prefill: 1 row  x P tail queries at a dynamic start offset
  (chunked prefill / prefix-cache tails).

Mechanics (``pltpu.PrefetchScalarGridSpec``): the block table and the
per-row start positions are scalar-prefetch arguments, so each grid
step's BlockSpec index map reads ``tables[s, j]`` and DMAs exactly ONE
live pool block into VMEM — the gathered ``[S, H, T, Dh]`` view never
exists in HBM. Blocks past a row's live length are clamped to the last
live block's index (consecutive equal index-map results skip the DMA),
so only live blocks ever move. Dequantization is fused into the load:
int8 block bytes multiply by their per-block-per-head scale
(serve/kv_quant.py) on the way into the score matmul.

Oracle contract (what the parity tests pin, tests/test_paged_attention
.py): the kernel mirrors the gathered-view math operation for
operation — dequantized blocks assemble into full-row K/V VMEM
scratch, then the IDENTICAL head-batched score dot / ``/ sqrt(dh)`` /
mask / ``jax.nn.softmax`` / ``probs @ V`` sequence the XLA path
runs — including its rounding of ``q`` and the probabilities to a bf16
pool's dtype before the f32-accumulating dots — so f32 and fake_quant
outputs are BIT-exact against the oracle and bf16/int8 hold to a
pinned tolerance. For scaled policies the
kernel reads the PRE-write pool and overrides the current run's
columns with the exact f32 fresh K/V (the oracle scores the
post-insert f32 view, not the quantized round-trip), and :func:`paged_quant_window_update` then requantizes
ONLY the touched blocks — byte-identical pool updates without ever
building the full row view.

TPU notes (first met the v5e compiler in PR 21): the layout favors
oracle exactness over Mosaic pipelining: blocks accumulate into
``[T, Hkv, Dh]`` K/V VMEM scratch during the walk (dynamic
sublane-offset stores at ``block_size`` granularity) and ALL the matmul
work runs at the last grid step as one whole-row head-batched dot —
bit-identical to the oracle's einsum, but serial after the DMA walk.
That whole-row scratch is a VMEM CAPACITY wall: Mosaic pads the two
minor dims of every buffer to its (sublane, 128-lane) tile, so GPT-2's
12 heads x Dh 64 occupy 16 x 128 and the two f32 scratch rows alone
are ``2 * T * 16 * 128 * 4`` bytes — 16 MiB at T = 1024, the whole of
the compiler's DEFAULT scoped-VMEM budget, which is why the kernel was
refused at GPT-2's own context length until it asked for more.
:func:`paged_attention_vmem_bytes` prices a call from its padded
scratch, block and finalize shapes; the call hands that number to the
compiler as ``vmem_limit_bytes`` and refuses, with the number, a shape
that cannot fit :data:`VMEM_CAP_BYTES` (:func:`require_vmem`;
``ServeEngine`` asks the same of every program at construction). Long rows need either a capped
``max_seq_len`` / chunked prefill through narrow buckets, or the
production evolution: the flash recurrence next door (per-block
online-softmax accumulation overlapping the walk, Flash-Decoding's
KV-split for long single-row contexts — PAPERS.md), which trades the
bit-parity pin for a bounded-ulp one and is ROADMAP S3's work, gated
behind the same parity suite.

**The per-row walk** (:func:`paged_walk_attention`, PR 34) is that
flash recurrence for the programs with FEW query rows a sequence
(decode, verify) on a bf16/f16 pool — what ``attn_kernel="xla"`` runs
there, chosen by nn/attention.paged_attend from dtypes and shapes. It
differs from the whole-row kernel above in every respect the walls
above name: the pool is passed WHOLE, in HBM, addressed ``(layer,
page)`` (no layer's view is cut out of the carried buffer); a row's
live pages are DMA'd ``key_block`` positions at a time into a double
buffer, the next block — or the next row's first — in flight while
this one is scored; ``m``, ``l`` and ``acc`` run in scratch, so VMEM
holds two key blocks and an ``[R, F]`` accumulator whatever the
table's width (17,408 positions in the window cell); and the heads
stay on the lane diagonal of the small operand, so a page is
contracted as stored. It is held to the gathered form by the rounding
bound, not to bits (tests/test_paged_attention.py ``TestRowWalk``). A
pool with NO v buffer (PR 36: a latent family's one row a token, every
head's key and value at once) takes the same kernel with one buffer and
one DMA a page, the values' product against the block the scores read
(``TestLatentRowWalk``; nn/attention.latent_attend_absorbed). A
``window`` (a sliding-window layer's ring of ``window + block_size``
rows a slot, nn/attention.window_attend) makes a row's table its ring's
pages in ring order: the row reads what it wrote while younger than its
ring and the ring once after, and the mask is the position each ring
row holds (``_ring_seen``), not the row's index
(tests/test_window_walk.py). Both selections are read when the call is
traced; the plain call's program is what it was.

Interpret mode is the CALLER's decision, never the kernel's: the
default is the compiled Mosaic kernel on whatever backend the process
has, so a serving process that is not on a TPU fails at lowering
instead of quietly emulating. The CPU test session (tests/conftest.py)
sets :data:`INTERPRET` once, in the open.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from quintnet_tpu.nn.attention import _as_stored

# Module-level interpret switch, read when ``paged_attention`` is
# called without ``interpret=``. False = lower the real Mosaic kernel.
# The kernel never looks at the backend to decide this for itself.
INTERPRET = False

# What one call may ask of the chip's VMEM: three quarters of the
# 128 MiB a v5e TensorCore has (Google Cloud documentation, "TPU v5e"),
# the rest left to the compiler's own stack and spills.
VMEM_CAP_BYTES = 96 * 2 ** 20


def _padded_bytes(shape, dtype) -> int:
    """Bytes a VMEM buffer of ``shape`` occupies once Mosaic pads its
    two minor dims to the native tile: 128 lanes, and 8 sublanes of 32
    bits (16 rows of bf16, 32 of int8)."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // item)
    *lead, r, c = (1, 1) + tuple(int(d) for d in shape)
    return (math.prod(lead) * (-(-r // sub) * sub)
            * (-(-c // 128) * 128) * item)


def paged_attention_vmem_bytes(*, n_q_heads: int, n_kv_heads: int,
                               n_queries: int, head_dim: int,
                               block_size: int, table_width: int,
                               q_dtype=jnp.float32,
                               pool_dtype=jnp.float32,
                               scaled: bool = False) -> int:
    """VMEM one grid cell of :func:`paged_attention` needs, from its
    own padded shapes — the number handed to the compiler as
    ``vmem_limit_bytes`` and the one the engine quotes when it refuses
    a shape. Four parts plus a quarter of headroom: the two whole-row
    f32 scratch buffers; every pipelined block twice (Pallas
    double-buffers inputs and outputs); one row again, head-major, for
    the batched dots; one ``[Hq, P, T]`` score array. Calibrated
    against the v5e compiler (PR 21, bisecting the smallest limit it
    accepts at T = 1024, 12 heads x Dh 64): decode 23 MiB where this
    prices 29, 128-query prefill 27 (bf16) / 29 (int8) against 37 /
    46, 512-query prefill 46 against 65; 25 heads at 128 queries 50
    against 75."""
    Hq, Hkv, P, D = n_q_heads, n_kv_heads, n_queries, head_dim
    T = table_width * block_size
    f32 = jnp.float32
    scratch = 2 * _padded_bytes((T + (P if scaled else 0), Hkv, D), f32)
    blocks = (2 * _padded_bytes((Hq, P, D), q_dtype)            # q, o
              + 2 * _padded_bytes((block_size, Hkv, D), pool_dtype))
    if scaled:
        blocks += (2 * _padded_bytes((1, Hkv), f32)
                   + 2 * _padded_bytes((P, Hkv, D), f32))       # fresh k, v
    finalize = (_padded_bytes((Hq, T, D), f32)
                + _padded_bytes((Hq, P, T), f32))
    return (scratch + 2 * blocks + finalize) * 5 // 4


def require_vmem(*, what: str = "paged_attention", **shape) -> int:
    """:func:`paged_attention_vmem_bytes` of ``shape``, or a ValueError
    naming ``what`` and the number where it exceeds
    :data:`VMEM_CAP_BYTES` — the one refusal the kernel call and
    ``ServeEngine`` construction share."""
    need = paged_attention_vmem_bytes(**shape)
    if need > VMEM_CAP_BYTES:
        positions = shape["table_width"] * shape["block_size"]
        raise ValueError(
            f"{what} at {shape['n_queries']} queries a row over "
            f"{positions} positions needs {need / 2 ** 20:.1f} MiB of "
            f"VMEM for its whole-row scratch and score arrays; the cap "
            f"is {VMEM_CAP_BYTES / 2 ** 20:.0f} MiB. Lower max_seq_len, "
            f"or serve long prompts through narrower prefill buckets "
            f"(chunked_prefill=True with a smaller prefill_len), or "
            f"use attn_kernel='xla'")
    return need


def _kernel(tbl_ref, st_ref, *refs, block_size: int, n_queries: int,
            n_rep: int, scaled: bool, override: bool, head_dim: int):
    """One (row, table-slot) grid step.

    Grid is ``(S, M)`` with the table walk innermost: step ``(s, j)``
    sees pool block ``tables[s, j]`` (the index maps in
    :func:`paged_attention` read the prefetched table), accumulates its
    dequantized K/V rows into the per-row VMEM scratch, and the last
    step runs the oracle's exact score/softmax/PV sequence on the
    assembled row. ``n_queries`` is P (the run width). All heads
    ride ONE grid cell: each block DMA carries every kv head's rows
    (one table walk per row, GQA repeat in-register) and the score/PV
    dots are HEAD-BATCHED dot_generals — the same batched-matmul
    lowering the oracle's einsum takes, which is what keeps even the
    P = 1 decode matvec BIT-exact on the XLA:CPU interpret path rather
    than merely close (a per-head 2D dot reduces in a different
    order)."""
    idx = 0
    q_ref = refs[idx]; idx += 1
    k_ref = refs[idx]; idx += 1
    v_ref = refs[idx]; idx += 1
    if scaled:
        ks_ref = refs[idx]; idx += 1
        vs_ref = refs[idx]; idx += 1
    if override:
        fk_ref = refs[idx]; idx += 1
        fv_ref = refs[idx]; idx += 1
    o_ref, k_scr, v_scr = refs[idx], refs[idx + 1], refs[idx + 2]

    bs, P, rep = block_size, n_queries, n_rep
    s_i = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    start = st_ref[s_i]

    @pl.when(j == 0)
    def _init():
        # dead rows must be FINITE zeros: their scores are masked to
        # finfo.min before the softmax (prob exactly 0), but 0 * NaN
        # from stale scratch would still poison the score/PV matmuls
        k_scr[...] = jnp.zeros_like(k_scr)
        v_scr[...] = jnp.zeros_like(v_scr)

    # blocks past the run's last position hold nothing any query may
    # see; their index map re-points at the last live block (no new
    # DMA) and their compute is skipped outright
    live = j * bs <= start + P - 1

    @pl.when(live)
    def _accumulate():
        kb = k_ref[0].astype(jnp.float32)           # [bs, Hkv, Dh]
        vb = v_ref[0].astype(jnp.float32)
        if scaled:
            # dequant-on-load: the block's per-head absmax scales ride
            # in on their own scalar-prefetched index map
            kb = kb * ks_ref[0, 0][None, :, None]
            vb = vb * vs_ref[0, 0][None, :, None]
        k_scr[pl.ds(j * bs, bs)] = kb
        v_scr[pl.ds(j * bs, bs)] = vb

    @pl.when(j == n_blocks - 1)
    def _finalize():
        T = n_blocks * bs
        if override:
            # scaled layouts: the oracle scores the post-insert f32
            # view, so the current run's columns carry the EXACT fresh
            # K/V, not the pool's quantize round-trip. The run is
            # contiguous at ``start``: one dynamic store along the
            # scratch's MAJOR dim (untiled, so any offset is legal)
            # lays it over the walked row. The scratch carries P spare
            # rows past T so a run whose pad columns cross the row end
            # stays in bounds — the oracle's padded insert, exactly.
            at = jnp.clip(start, 0, T)
            k_scr[pl.ds(at, P)] = fk_ref[0].astype(jnp.float32)
            v_scr[pl.ds(at, P)] = fv_ref[0].astype(jnp.float32)
        # the oracle sequence on the assembled row, op for op: ONE
        # head-batched whole-row score dot (a per-block [P, bs] tile
        # dot lowers differently for P = 1 on XLA:CPU — the tile
        # variant was measured 1-2 ulp off, this one is bit-exact),
        # then scores / sqrt(dh) -> positional mask to finfo.min ->
        # jax.nn.softmax -> probs @ V
        # the oracle rounds q and the probabilities to a bf16 pool's
        # dtype before its f32-accumulating dots (nn/attention
        # _as_stored); products of bf16 values are exact in f32, so
        # the same rounding followed by f32 dots is the same math. An
        # f32 or scaled (int8) pool rounds nothing
        qf = _as_stored(q_ref[0], k_ref).astype(jnp.float32)  # [Hq, P, Dh]
        kr = _rep_heads(k_scr[pl.ds(0, T)], rep)    # [Hq, T, Dh]
        sc = jax.lax.dot_general(
            qf, kr, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)     # [Hq, P, T]
        q_pos = start + lax.broadcasted_iota(jnp.int32, (P, T), 0)
        t_pos = lax.broadcasted_iota(jnp.int32, (P, T), 1)
        sc = sc / math.sqrt(head_dim)
        sc = jnp.where((t_pos <= q_pos)[None], sc,
                       jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(sc, axis=-1).astype(o_ref.dtype)
        vr = _rep_heads(v_scr[pl.ds(0, T)], rep)    # [Hq, T, Dh]
        o_ref[0] = jax.lax.dot_general(
            _as_stored(probs, v_ref).astype(jnp.float32), vr,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _rep_heads(x, rep: int):
    """[.., Hkv, Dh] block slots -> head-major [Hkv*rep, .., Dh]: move
    heads in front and repeat each kv head ``rep`` times, contiguous
    groups (exactly nn/attention.repeat_kv's layout on the gathered
    view)."""
    t = jnp.moveaxis(x, -2, 0)                      # [Hkv, .., Dh]
    if rep == 1:
        return t
    hkv = t.shape[0]
    return jnp.broadcast_to(t[:, None], (hkv, rep) + t.shape[1:]
                            ).reshape((hkv * rep,) + t.shape[1:])


def paged_attention(q, k_pool, v_pool, block_tables, starts, *,
                    block_size: int, kv_scales=None, policy=None,
                    fresh_kv=None, interpret=None):
    """Block-table-walking fused attention over the paged KV pool.

    ``q``: [S, Hq, P, D] query runs (decode P=1, verify P=k+1, prefill
    P=bucket with S=1); ``k_pool``/``v_pool``: [N_slots, Hkv, D] flat
    pool views in the policy's store dtype; ``block_tables``: [S, M];
    ``starts``: [S] — row s's queries sit at absolute positions
    ``starts[s] + arange(P)`` and attend causally to every pool
    position ``t <= starts[s] + i`` (exactly the gathered-view mask).
    GQA: ``Hq`` may be a multiple of ``Hkv``; each kv head's block walk
    serves its whole query group.

    ``kv_scales``: (k_scale [nb, Hkv], v_scale) per-block-per-head
    scales of a SCALED layout policy — dequantization then happens on
    block load, inside the kernel. Scaled callers must pass
    ``fresh_kv`` = (k, v) [S, Hkv, P, D], the run's exact f32
    projections: the kernel scores them directly (the oracle's
    post-insert view) while :func:`paged_quant_window_update` owns the
    pool write. Passthrough callers write the pool FIRST (the existing
    scatter) and the kernel reads the fresh run back like any other
    slot.

    Returns o [S, Hq, P, D] in q's dtype. ``policy`` is accepted for
    signature symmetry with the gathered-view path; only
    ``kv_scales``'s presence selects the scaled kernel (the ladder's
    scaled policies all dequantize as ``stored * scale``)."""
    del policy  # dequant is stored * scale for every scaled policy
    if interpret is None:
        interpret = INTERPRET
    S, Hq, P, D = q.shape
    Hkv = k_pool.shape[1]
    rep = Hq // Hkv
    if Hkv * rep != Hq:
        raise ValueError(
            f"query heads {Hq} not a multiple of kv heads {Hkv}")
    M = block_tables.shape[-1]
    tables = block_tables.reshape(S, M).astype(jnp.int32)
    starts = starts.reshape(S).astype(jnp.int32)
    bs = block_size
    nb = k_pool.shape[0] // bs
    T = M * bs
    scaled = kv_scales is not None
    override = fresh_kv is not None
    if scaled and not override:
        raise ValueError(
            "scaled kv_scales need fresh_kv: the kernel scores the "
            "run's exact f32 K/V (the oracle's post-insert view); the "
            "pool write is paged_quant_window_update's job")

    k4 = k_pool.reshape(nb, bs, Hkv, D)
    v4 = v_pool.reshape(nb, bs, Hkv, D)

    def blk_idx(s, j, tbl, st):
        # clamp dead steps to the last live block: equal consecutive
        # index-map results skip the DMA, so dead table slots move no
        # bytes (starts >= 0, so the floordiv is safe)
        last = jnp.minimum((st[s] + P - 1) // bs, M - 1)
        return tbl[s, jnp.minimum(j, last)]

    pool_spec = pl.BlockSpec(
        (1, bs, Hkv, D),
        lambda s, j, tbl, st: (blk_idx(s, j, tbl, st), 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, Hq, P, D), lambda s, j, tbl, st: (s, 0, 0, 0)),
        pool_spec,
        pool_spec,
    ]
    inputs = [q, k4, v4]
    if scaled:
        ks, vs = kv_scales
        # [nb, 1, Hkv] so the block's two minor dims EQUAL the array's
        # (Mosaic refuses a (1, Hkv) block of an [nb, Hkv] array: its
        # second-minor dim is neither 8-divisible nor the array's)
        scale_spec = pl.BlockSpec(
            (1, 1, Hkv),
            lambda s, j, tbl, st: (blk_idx(s, j, tbl, st), 0, 0))
        in_specs += [scale_spec, scale_spec]
        inputs += [ks.reshape(nb, 1, Hkv), vs.reshape(nb, 1, Hkv)]
    if override:
        # position-major [S, P, Hkv, D]: the layout of the scratch
        # rows the kernel stores the run over (the swap is XLA's, out
        # here, not a Mosaic relayout in there)
        run_spec = pl.BlockSpec((1, P, Hkv, D),
                                lambda s, j, tbl, st: (s, 0, 0, 0))
        in_specs += [run_spec, run_spec]
        inputs += [f.reshape(S, Hkv, P, D).swapaxes(1, 2)
                   for f in fresh_kv]

    vmem = require_vmem(
        n_q_heads=Hq, n_kv_heads=Hkv, n_queries=P, head_dim=D,
        block_size=bs, table_width=M, q_dtype=q.dtype,
        pool_dtype=k_pool.dtype, scaled=scaled)

    kernel = functools.partial(
        _kernel, block_size=bs, n_queries=P, n_rep=rep, scaled=scaled,
        override=override, head_dim=D)
    spare = P if override else 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, P, D),
                               lambda s, j, tbl, st: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T + spare, Hkv, D), jnp.float32),
            pltpu.VMEM((T + spare, Hkv, D), jnp.float32),
        ],
    )
    with jax.named_scope("paged_attn"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, Hq, P, D), q.dtype),
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
            interpret=interpret,
            name="paged_attention",
        )(tables, starts, *inputs)


def paged_quant_window_update(policy, cache, scales, vals, positions,
                              lens, *, block_tables, block_size: int,
                              max_blocks: int):
    """The scaled-policy pool write WITHOUT the row view: requantize
    exactly the run's touched blocks.

    Byte-identical to what nn/attention.paged_quant_update scatters
    (the parity tests compare pool bytes directly): per row, the
    ``max_blocks`` window of blocks the contiguous run
    ``positions[s, 0] .. positions[s, 0] + lens[s] - 1`` can touch is
    gathered (O(window), never O(row)), dequantized under its OLD
    scales, the exact f32 run inserted at its window offset, slots
    beyond the row's last written position zeroed (recycled-block
    stale bytes must not inflate the fresh absmax — the PR 10
    invariant), fresh per-block-per-head scales computed, and the
    requantized blocks + scales scattered back. Untouched window slots
    target the null block, the same convention as every paged update.

    ``vals``: [S, H, P, D]; ``positions``: [S, P] contiguous;
    ``lens``: [S]. Returns (cache, scales)."""
    S, H, P, D = vals.shape
    bs = block_size
    K = max_blocks
    M = block_tables.shape[1]
    nb = cache.shape[0] // bs
    first = positions[:, 0] // bs
    last_pos = positions[:, 0] + lens - 1          # < first*bs if len 0
    j = first[:, None] + jnp.arange(K)[None, :]                  # [S, K]
    touched = (j <= last_pos[:, None] // bs) & (j < M)
    j_c = jnp.clip(j, 0, M - 1)
    tgt = jnp.where(touched,
                    jnp.take_along_axis(block_tables, j_c, axis=1), 0)

    pool4 = cache.reshape(nb, bs, H, D)
    win = policy.dequant(pool4[tgt],
                         scales[tgt][:, :, None, :, None])
    # [S, K, bs, H, D] -> position-ordered window [S, H, K*bs, D]
    win = win.transpose(0, 3, 1, 2, 4).reshape(S, H, K * bs, D)
    # insert the run at its window offset; the P-slot pad keeps a run
    # whose tail crosses the window end from clamp-shifting onto valid
    # slots (mirrors paged_quant_update's padded insert)
    off = positions[:, 0] - first * bs
    padded = jnp.concatenate(
        [win, jnp.zeros((S, H, P, D), win.dtype)], axis=2)
    padded = jax.vmap(
        lambda row, val, st: lax.dynamic_update_slice_in_dim(
            row, val, st, axis=1)
    )(padded, vals.astype(jnp.float32), off)
    win = padded[:, :, :K * bs]

    winb = win.reshape(S, H, K, bs, D)
    live = (j_c[:, :, None] * bs + jnp.arange(bs)[None, None, :]
            <= last_pos[:, None, None])                   # [S, K, bs]
    winb = jnp.where(live[:, None, :, :, None], winb, 0.0)
    sc = policy.compute_scale(winb, axes=(3, 4))          # [S, H, K]
    qn = policy.quant(winb, sc[..., None, None])
    flat = tgt.reshape(-1)
    qn = qn.transpose(0, 2, 3, 1, 4).reshape(S * K, bs, H, D)
    cache = pool4.at[flat].set(qn).reshape(nb * bs, H, D)
    scales = scales.at[flat].set(sc.transpose(0, 2, 1).reshape(S * K, H))
    return cache, scales


# ---------------------------------------------------------------------
# The PER-ROW walk (decode, verify): each row reads its own live key
# blocks of the carried pool, in place, and no more.
# ---------------------------------------------------------------------
def walk_lowers_for(backend: str, block_size: int) -> bool:
    """Whether :func:`paged_walk_attention` can be lowered for
    ``backend``: under the interpreter the caller turned on
    (:data:`INTERPRET`) always; for a TPU when a page is whole tiles of
    the pool in HBM (8 rows: Mosaic slices a DMA's source no finer);
    for nothing else."""
    return INTERPRET or (backend == "tpu" and block_size % 8 == 0)


def _page_copy(pool, buf, sem, tbl_ref, layer, s, c, slot, i, *,
               block_size: int, pages: int):
    """The DMA descriptor of page ``i`` of key block ``c`` of row ``s``:
    table entry ``c * pages + i`` — ``block_size`` pool rows, contiguous
    in HBM — into its place in ``buf[slot]``. An entry past the table's
    end re-reads its last (its positions are masked)."""
    blk = tbl_ref[s, jnp.minimum(c * pages + i, tbl_ref.shape[1] - 1)]
    return pltpu.make_async_copy(
        pool.at[layer, pl.ds(pl.multiple_of(blk * block_size, block_size),
                             block_size)],
        buf.at[slot, pl.ds(pl.multiple_of(i * block_size, block_size),
                           block_size)],
        sem.at[slot])


def _ring_seen(r, qpos, last, *, ring: int, window: int):
    """Which of a ring's rows ``r`` (ring-row indices, any shape) the
    queries at ``qpos`` see once every position up to ``last`` (a
    scalar) has been written: row ``r`` holds ``last - (last - r) %
    ring`` (nn/attention._ring_positions) — ``base + r`` up to ``last``'s
    own row, ``ring`` less past it — and is seen iff that position is
    this sequence's (``>= 0``), at or before the query and inside its
    window. Rows past the ring (a trip's pages beyond the table re-read
    its last) are not."""
    at = lax.rem(last, ring)                       # last >= 0
    held = last - at + r - jnp.where(r > at, ring, 0)
    return ((r < ring) & (held >= 0) & (held <= qpos)
            & (held > qpos - window))


def _walk_kernel(layer_ref, tbl_ref, trips_ref, first_ref, *refs,
                 block_size: int, pages: int, head_dim: int, scale,
                 window=None):
    """One row of the grid: the flash recurrence over the row's
    ``trips_ref[s]`` live key blocks. The pool stays in HBM; block ``c
    + 1`` (or the next row's first) is in flight into the other half of
    ``k_buf`` / ``v_buf`` while block ``c`` is scored, so the walk never
    waits on a row's end. ``first_ref[s]`` is the number of blocks the
    rows before ``s`` walked: the parity of ``first + c`` is the buffer
    half, carried across rows without a counter.

    ``refs``: with a ``window``, the rows' last written positions (a
    fifth scalar prefetch; the table is then each row's RING in ring
    order, :func:`_ring_seen` its mask); the queries and their
    positions; the pools in HBM, the output, a double buffer a pool, a
    semaphore pair a pool, then ``m``, ``l`` and ``acc``. A pool with no
    v (a latent one: a position's key and value are the same row) has
    ONE of each: one DMA a page, and the values' product reads the block
    the scores read, its first ``acc``-many lanes."""
    if window is not None:
        last_ref, *refs = refs
    qd_ref, qpos_ref, *refs = refs
    n = (len(refs) - 4) // 3
    pools, o_ref = refs[:n], refs[n]
    bufs, sems = refs[n + 1:2 * n + 1], refs[2 * n + 1:3 * n + 1]
    m_scr, l_scr, acc_scr = refs[3 * n + 1:]
    k_buf, v_buf = bufs[0], bufs[-1]
    kept = acc_scr.shape[1]
    s = pl.program_id(0)
    rows = pl.num_programs(0)
    layer = layer_ref[0]
    trips, first = trips_ref[s], first_ref[s]
    kb = pages * block_size

    def fetch(s_, c_, slot, act):
        """Start or wait for (``act``) the pages of key block ``c_`` of
        row ``s_`` into half ``slot``, every pool's. One page's code, traced
        once and unrolled when the kernel is lowered: sixteen pages a
        trip written out in Python cost the decode program's first
        trace half a second."""
        def page(i, _):
            for pool, buf, sem in zip(pools, bufs, sems):
                act(_page_copy(pool, buf, sem, tbl_ref, layer, s_, c_,
                               slot, i, block_size=block_size,
                               pages=pages))
            return 0
        lax.fori_loop(0, pages, page, 0, unroll=True)

    start, wait = (lambda copy: copy.start()), (lambda copy: copy.wait())

    @pl.when(s == 0)
    def _first_block():
        fetch(0, 0, 0, start)

    neg = jnp.finfo(jnp.float32).min
    m_scr[...] = jnp.full_like(m_scr, neg)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    qd = qd_ref[0]                                   # [R, F], stored dtype
    qpos = qpos_ref[0]                               # [R, 1]

    def block(c, _):
        slot = (first + c) % 2
        more = c + 1 < trips

        @pl.when(more | (s + 1 < rows))
        def _next_block():
            fetch(jnp.where(more, s, s + 1), jnp.where(more, c + 1, 0),
                  1 - slot, start)

        fetch(s, c, slot, wait)
        scores = lax.dot_general(
            qd, k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [R, kb]
        scores = (scores / math.sqrt(head_dim) if scale is None
                  else scores * scale)
        row = c * kb + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        seen = (row <= qpos if window is None else _ring_seen(
            row, qpos, last_ref[s], ring=tbl_ref.shape[1] * block_size,
            window=window))
        scores = jnp.where(seen, scores, neg)
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, jnp.max(scores, axis=1, keepdims=True))
        grow = jnp.exp(m_old - m_new)
        p = jnp.exp(scores - m_new)
        l_scr[...] = l_scr[...] * grow + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * grow + lax.dot_general(
            p.astype(v_buf.dtype),
            v_buf[slot] if kept == v_buf.shape[2] else v_buf[slot, :, :kept],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [R, kept lanes]
        m_scr[...] = m_new
        return 0

    lax.fori_loop(0, trips, block, 0)
    o_ref[0] = acc_scr[...] / l_scr[...]


def walk_vmem_bytes(*, rows: int, lanes: int, kept_lanes: int,
                    key_block: int, pools: int, pool_dtype,
                    q_dtype) -> int:
    """VMEM one grid step of :func:`paged_walk_attention` holds, from
    its padded shapes: both halves of a key block a pool, ``rows`` query
    rows of ``lanes`` and their ``kept_lanes``-wide f32 output
    (pipelined: twice each), ``acc`` and its rescaled copies, the
    scores and the probabilities. The call asks the compiler for twice
    this; a caller whose rows a sequence grow with its program (a
    latent family's verify bucket: 128 heads a drafted token) asks
    whether twice this is inside :data:`VMEM_CAP_BYTES` before it takes
    the walk."""
    f32 = jnp.float32
    return (2 * pools * _padded_bytes((key_block, lanes), pool_dtype)
            + 2 * 2 * _padded_bytes((rows, lanes), q_dtype)
            + 2 * _padded_bytes((rows, kept_lanes), f32) * 2
            + 3 * _padded_bytes((rows, kept_lanes), f32)
            + 4 * _padded_bytes((rows, key_block), f32))


def paged_walk_attention(qd, qpos, k_pool, v_pool, layer, block_tables, *,
                         block_size: int, key_block: int, head_dim: int,
                         scale=None, kept_lanes=None, window=None,
                         interpret=None):
    """Few query rows a sequence against each sequence's LIVE blocks of
    the carried pool (nn/attention.paged_attend's decode and verify
    form). ``qd`` [S, R, F]: a sequence's query rows in the pool's
    dtype, each holding its ``head_dim`` values in the lanes of the kv
    head it reads and exact zeros elsewhere (heads on the lane
    diagonal: nn/attention._diag_queries), ``R`` a whole number of
    sublane tiles; ``qpos`` [S, R]: the position each row's query sits
    at, -1 for a pad row. ``k_pool`` / ``v_pool`` [L, slots, F] whole,
    read at ``layer`` (a traced scalar) through ``block_tables`` [S, M].

    ``v_pool`` None is a pool of ONE row kind (a latent family's: every
    head reads the same row, as key and as value;
    nn/attention.latent_attend_absorbed): one buffer and one DMA a page,
    and the values' product runs against the block the scores read.
    ``kept_lanes``: the leading lanes of a value row the output keeps
    (a latent row's rank where that is whole 128-lane tiles: the
    rotary and pad lanes' products are never made); None keeps all.

    Row ``s`` reads key blocks ``0 .. max(qpos[s]) // key_block`` and no
    more — ``key_block`` positions, a whole number of pages, a trip —
    straight out of the pool in HBM, double-buffered, and folds them
    into a running softmax: scores ``[R, F] x [key_block, F]`` in the
    stored dtype with f32 sums, the mask ``t <= qpos``, f32 ``exp``,
    the probabilities rounded to the stored dtype before ``[R,
    key_block] x [key_block, F]``. Returns ``o`` [S, R, kept lanes]
    f32, normalised: row ``r``'s output is in its own head's lanes.

    ``window`` (a sliding-window layer's ring, nn/attention.window_attend):
    row ``s``'s table is its RING's pages in ring order, ``R = M x
    block_size`` rows holding the last ``R`` positions up to ``max(qpos
    [s])`` (its last written; -1 everywhere for a row that wrote
    nothing). It reads ``ceil(min(max(qpos[s]) + 1, R) / key_block)``
    key blocks — what it wrote while younger than its ring, the ring
    once after, one block for a row that wrote nothing — and masks by
    the position each ring row holds (:func:`_ring_seen`): a query sees
    the ``window`` positions up to its own."""
    if interpret is None:
        interpret = INTERPRET
    S, R, F = qd.shape
    M = block_tables.shape[1]
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    kept = F if kept_lanes is None else kept_lanes
    pages = max(min(key_block // block_size, M), 1)
    kb = pages * block_size
    last = jnp.max(qpos, axis=1)
    ring = []
    if window is None:
        trips = jnp.clip(last // kb + 1, 1, -(-M // pages)).astype(jnp.int32)
    else:
        held = jnp.minimum(last + 1, M * block_size)
        trips = jnp.maximum(-(-held // kb), 1).astype(jnp.int32)
        ring = [jnp.maximum(last, 0).astype(jnp.int32)]
    first = (jnp.cumsum(trips) - trips).astype(jnp.int32)
    vmem = walk_vmem_bytes(rows=R, lanes=F, kept_lanes=kept, key_block=kb,
                           pools=len(pools), pool_dtype=k_pool.dtype,
                           q_dtype=qd.dtype)
    kernel = functools.partial(_walk_kernel, block_size=block_size,
                               pages=pages, head_dim=head_dim, scale=scale,
                               window=window)
    row = lambda s, *_: (s, 0, 0)                             # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + len(ring),
        grid=(S,),
        in_specs=[pl.BlockSpec((1, R, F), row),
                  pl.BlockSpec((1, R, 1), row),
                  # wherever the compiler keeps the carried pool: HBM
                  # at any deployment's size (a pool of a few tens of
                  # MB it moves into VMEM whole, scatter and all)
                  *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools]],
        out_specs=pl.BlockSpec((1, R, kept), row),
        scratch_shapes=[
            *[pltpu.VMEM((2, kb, F), p.dtype) for p in pools],
            *[pltpu.SemaphoreType.DMA((2,)) for _ in pools],
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, kept), jnp.float32),
        ])
    with jax.named_scope("paged_walk"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, R, kept), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=min(max(2 * vmem, 32 * 2 ** 20),
                                     VMEM_CAP_BYTES)),
            interpret=interpret,
            name="paged_walk_attention",
        )(jnp.reshape(layer, (1,)).astype(jnp.int32),
          block_tables.astype(jnp.int32), trips, first, *ring,
          qd, qpos[..., None].astype(jnp.int32), *pools)
