"""The per-row paged-attention walk: the Pallas kernel of the serving
programs with FEW query rows a sequence (decode, verify) on a bf16/f16
pool, chosen by nn/attention.paged_attend from dtypes and shapes
(vLLM's PagedAttention insight, Kwon et al., and Flash-Decoding's
running softmax — PAPERS.md — expressed in Pallas). Every other program
— a scaled, float8 or f32 pool, a prefill bucket, another platform —
reads the gathered view (nn/attention.paged_kv_step), which is also
the reference the walk is bounded against.

Why in place: the gathered view materializes every block of a row's
table, live or not, as a position-ordered copy in HBM before a score
is taken. The walk takes the pool WHOLE, in HBM, addressed ``(layer,
page)`` (no layer's view is cut out of the carried buffer); a row's
live pages are DMA'd ``key_block`` positions at a time into a double
buffer, the next block — or the next row's first — in flight while
this one is scored; ``m``, ``l`` and ``acc`` run in scratch, so VMEM
holds two key blocks and an ``[R, F]`` accumulator whatever the
table's width; and the heads stay on the lane diagonal of the small
operand, so a page is contracted as stored. It is held to the gathered
form by the rounding bound, not to bits (tests/test_paged_attention.py
``TestRowWalk``).

Three modes of one kernel, each read when the call is traced: the
plain walk over a K and a V pool; a pool with NO v buffer (a latent
family's one row a token: one buffer and one DMA a page, the values'
product against the block the scores read, ``TestLatentRowWalk``);
and a ``window`` (a sliding-window layer's ring, nn/attention
.window_attend), where a row's table is its ring's pages in ring order
and the mask is the position each ring row holds (``_ring_seen``,
tests/test_window_walk.py).

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

# Module-level interpret switch, read when ``paged_walk_attention`` is
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
