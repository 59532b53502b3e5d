"""The dropless mixture's grouped matmul as a TPU kernel that streams
each TOUCHED expert's weights once (nn/moe._moe_dropless, PR 38).

``x`` [M, K] holds the routings sorted by expert: group ``g`` is rows
``offsets[g] .. offsets[g + 1]`` and is multiplied by ``w[g]`` [K, N];
rows past the last group belong to nobody. At the serving cells' shapes
a group is 2-4 rows in a decode step and 32-512 in a 1,024-token prefill
chunk, under the chip's ridge of ~240 FLOPs a weight byte everywhere but
the largest: the least time of a call is the touched experts' weights
crossing HBM once. ``lax.ragged_dot`` took 2.6-2.9 times that (34-39% of
the bytes' roofline; PERF.md section 5, PRs 36-37).

The kernel is a grid over ``(column tile, visit)``. A VISIT is one (row
tile, group) pair that share a row: :func:`group_visits` lists them in
row order from the group sizes — an expert nobody was routed to is in no
visit, so its weights are never read — and the lists are scalar-
prefetched, as the walk's block tables are (ops/paged_attention.py). The
weight block's index map reads the visit's group (plus ``layer *
held`` groups into a stack ``[layers, held, K, N]``, which is indexed in
place: no slice of it exists), the row blocks' its row tile; consecutive
visits of one group (a group that spans row tiles) or of one row tile (a
tile that holds several groups) keep that block's index and the pipeline
does not fetch it again. A visit multiplies the whole row tile by the
group's weights — under 128 rows the matrix unit's time is loading the
weight tile, not streaming rows — and stores the rows that are the
group's. The grid's visit axis is the static bound ``row tiles + groups
- 1``; steps past the live count repeat the last visit's blocks (no
copy) and compute nothing.

``K`` is never tiled: a weight block is ``[K, tn]`` with ``tn`` the
widest multiple of 128 lanes that divides ``N`` and keeps the block
inside :data:`WEIGHT_TILE_BYTES`, so the f32 sums of a visit are one
``dot`` and need no accumulator across steps. Operands as given (bf16 /
f16 / f32, ``x`` in the weights' dtype), f32 sums: what
``lax.ragged_dot(..., preferred_element_type=float32)`` is asked for.

Rows of no group: zero where a visit shares their row tile, whatever
the output buffer held where none does. The caller selects them out
(nn/moe's ``combine``), as it had to with ``ragged_dot``.

Interpret mode is the CALLER's decision (:data:`INTERPRET`), as in
ops/paged_attention.py.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from quintnet_tpu.ops.paged_attention import VMEM_CAP_BYTES, _padded_bytes

# Read when ``grouped_matmul`` is called without ``interpret=``. False
# = lower the real Mosaic kernel; a test of the kernel on the CPU turns
# it on around itself.
INTERPRET = False

LANES = 128
# ---------------------------------------------------------------------
# The tiles. Chip numbers: a v5e ("TPU v5 lite"), bf16, one call of a
# sparse layer's three with the metadata computed beside it, from
# tools/gmm_microbench.py (my chip runs, PR 38; PERF.md section 6):
# "KDA decode" is 1,536 sorted routings of which 399 lie on 125 of a
# layer's 128 held experts [2560, 768] in a stack of four layers (492 MB
# touched: 0.600 ms at 819 GB/s), "window chunk" 8,192 routings on all
# 256 experts [2048, 512] (537 MB: 0.656 ms).
# ---------------------------------------------------------------------
# The widest weight block one visit holds (twice: the pipeline's double
# buffer). Ling's [2560, 768] and Laguna's [2048, 512] bf16 experts are
# 3.9 and 2.1 MB and go whole; openPangu's [7680, 2048] (31.5 MB) goes
# as two [7680, 1024] blocks of 15.7 MB, its down projection [2048,
# 7680] as two [2048, 3840]. Wider is faster wherever both ran: KDA
# decode's down projection whole (2,560 columns) 0.693 ms, in blocks of
# 512 0.774, of 256 0.993 (a narrow block is a strided copy and one
# more pass over the rows); openPangu's decode 1,024 columns 0.555, 512
# 0.559, 256 0.594.
WEIGHT_TILE_BYTES = 16 * 2 ** 20
# Rows a visit multiplies. The matrix unit loads a [128, 128] weight
# tile in about the time 128 rows take to stream past it, so up to 128
# rows a visit cost what 16 do, and a taller tile is met by fewer
# visits (visits = touched groups + the row-tile edges that fall inside
# a group). KDA decode: 16 rows 0.714 ms, 32 0.696, 64 0.691, 128
# 0.689, 256 0.738; window chunk: 16 1.148, 32 1.016, 64 0.940, 128
# 0.900, 256 0.963 (past 128 rows a visit pays for rows that are not
# its group's). ``lax.ragged_dot`` on the same operands: 2.306 and
# 1.951; the library's megablox kernel at (128, K, N): 0.727 and 1.003.
ROW_TILE = 128
KERNEL_DTYPES = tuple(jnp.dtype(d) for d in (jnp.bfloat16, jnp.float16,
                                             jnp.float32))


class Visits(NamedTuple):
    """What :func:`grouped_matmul` prefetches (all int32): ``offsets``
    [G + 1] the groups' first rows; ``group`` / ``tile`` [V] each
    visit's group and row tile, the last live visit's repeated past
    ``count`` [1], the number of live visits."""

    offsets: jax.Array
    group: jax.Array
    tile: jax.Array
    count: jax.Array


def row_tile_for(rows: int, dtype) -> int:
    """The row tile of a call over ``rows`` rows of ``dtype``:
    :data:`ROW_TILE`, or all the rows rounded up to the dtype's sublane
    packing (16 rows of bf16) where they are fewer."""
    sub = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return min(ROW_TILE, -(-rows // sub) * sub)


def column_tile_for(k: int, n: int, dtype) -> Optional[int]:
    """The widest multiple of 128 lanes that divides ``n`` with ``[k,
    tile]`` of ``dtype`` inside :data:`WEIGHT_TILE_BYTES`; None where
    not even 128 columns are."""
    item = jnp.dtype(dtype).itemsize
    fits = [t for t in range(LANES, n + 1, LANES)
            if n % t == 0 and k * t * item <= WEIGHT_TILE_BYTES]
    return max(fits, default=None)


def grouped_matmul_lowers_for(backend: str, *, k: int, n: int, x_dtype,
                              w_dtype) -> bool:
    """Whether :func:`grouped_matmul` is taken for ``backend``: under
    the interpreter the caller turned on always; for a TPU when ``k``
    and ``n`` are whole 128-lane tiles, the weights are a float the
    kernel takes with the rows in the same dtype, and a column tile
    fits; for nothing else (a tiny preset, a packed weight)."""
    shaped = (k % LANES == 0 and n % LANES == 0
              and jnp.dtype(w_dtype) in KERNEL_DTYPES
              and jnp.dtype(x_dtype) == jnp.dtype(w_dtype)
              and column_tile_for(k, n, w_dtype) is not None)
    return shaped and (INTERPRET or backend == "tpu")


def group_visits(sizes, *, rows: int, row_tile: int) -> Visits:
    """The (row tile, group) pairs that share a row, in row order, from
    the groups' ``sizes`` [G] (consecutive from row 0; rows past their
    sum are nobody's). A group of ``s > 0`` rows from row ``o`` is
    visited once a row tile it reaches into, ``(o + s - 1) // t - o //
    t + 1`` times; an empty group never. Visits number at most ``tiles +
    G - 1`` (every group but the first may begin inside a tile an
    earlier one began in), the static length of ``group`` / ``tile``."""
    sizes = sizes.astype(jnp.int32)
    G = sizes.shape[0]
    tiles = -(-rows // row_tile)
    V = tiles + G - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // row_tile
    per = jnp.where(sizes > 0, (ends - 1) // row_tile - first + 1, 0)
    upto = jnp.cumsum(per)                       # visits through group g
    count = upto[-1]
    # a dead step repeats the last live visit (no block moves); with no
    # live visit at all it names group 0's first tile
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(count - 1, 0))
    group = jnp.minimum(
        jnp.sum(upto[None, :] <= v[:, None], axis=1), G - 1).astype(jnp.int32)
    tile = first[group] + v - (upto - per)[group]
    return Visits(jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
                  group, jnp.clip(tile, 0, tiles - 1).astype(jnp.int32),
                  count.reshape(1))


def grouped_matmul_vmem_bytes(*, row_tile: int, k: int, column_tile: int,
                              x_dtype, w_dtype) -> int:
    """VMEM one grid step of :func:`grouped_matmul` holds, from its
    padded shapes: the row tile, the weight block and the f32 output
    block (pipelined: twice each), the visit's product and its masked
    copy. The call asks the compiler for this and a half."""
    f32 = jnp.float32
    return (2 * _padded_bytes((row_tile, k), x_dtype)
            + 2 * _padded_bytes((k, column_tile), w_dtype)
            + 4 * _padded_bytes((row_tile, column_tile), f32))


def _visit_kernel(layer_ref, offs_ref, group_ref, tile_ref, count_ref,
                  x_ref, w_ref, o_ref, *, row_tile: int):
    """One visit: the row tile times the visit's group's weight block,
    kept for the rows that are the group's. The first visit of a row
    tile writes zeros to the rest of it, a later one leaves what the
    earlier groups stored."""
    del layer_ref
    v = pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _visit():
        g, t = group_ref[v], tile_ref[v]
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        row = t * row_tile + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
        fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)

        @pl.when(fresh)
        def _first_of_its_tile():
            o_ref[...] = jnp.where(mine, acc, 0.0)

        @pl.when(jnp.logical_not(fresh))
        def _after_another_group():
            o_ref[...] = jnp.where(mine, acc, o_ref[...])


def grouped_matmul(x, w, visits: Visits, *, layer=None,
                   row_tile: Optional[int] = None,
                   column_tile: Optional[int] = None, interpret=None):
    """``out[r] = x[r] @ w[g]`` (f32) for every row ``r`` of group
    ``g``, the groups as ``visits`` lists them (:func:`group_visits`
    with this call's row tile). ``x`` [M, K]; ``w`` [G, K, N], or a
    stack [L, G, K, N] read at ``layer`` (a traced scalar) in place.
    Returns [M, N] f32; rows of no group: module docstring."""
    if interpret is None:
        interpret = INTERPRET
    if (layer is None) != (w.ndim == 3):
        raise ValueError("weights [G, K, N] take no layer; a stack's "
                         "[L, G, K, N] needs one")
    M, K = x.shape
    N = w.shape[-1]
    tm = row_tile_for(M, x.dtype) if row_tile is None else row_tile
    tn = column_tile_for(K, N, w.dtype) if column_tile is None \
        else column_tile
    V = visits.group.shape[0]
    if V != -(-M // tm) + w.shape[-3] - 1 or N % tn or tn % LANES:
        raise ValueError(
            f"{V} visits do not belong to {M} rows in tiles of {tm} and "
            f"{w.shape[-3]} groups, or {tn} columns are no whole lane "
            f"tiles that divide {N}")
    w4 = w if w.ndim == 4 else w[None]
    vmem = grouped_matmul_vmem_bytes(row_tile=tm, k=K, column_tile=tn,
                                     x_dtype=x.dtype, w_dtype=w.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // tn, V),
        in_specs=[
            pl.BlockSpec((tm, K), lambda j, v, la, of, gr, ti, co:
                         (ti[v], 0)),
            pl.BlockSpec((None, None, K, tn),
                         lambda j, v, la, of, gr, ti, co:
                         (la[0], gr[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, la, of, gr, ti, co:
                               (ti[v], j)))
    with jax.named_scope("grouped_matmul"):
        return pl.pallas_call(
            functools.partial(_visit_kernel, row_tile=tm),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=min(max(vmem * 3 // 2, 32 * 2 ** 20),
                                     VMEM_CAP_BYTES)),
            interpret=interpret,
            name="grouped_matmul",
        )(jnp.reshape(0 if layer is None else layer, (1,)).astype(jnp.int32),
          *visits, x, w4)
