"""Jaxpr-level auditor: collective census, dtype promotion, donation.

QuintNet-TPU's contract is that each parallel strategy compiles to a
*predictable* communication pattern on the mesh (parallel/dp.py shards
the batch and pmeans grads; parallel/tp.py psums row-parallel partials;
parallel/zero.py reduce-scatters into chunks). Nothing used to check
that: a stray resharding or an accidental extra all-gather lands in the
jitted step and only ever shows up — if it shows up at all — as a perf
regression in a BENCH_*.json weeks later. This module turns the
expected pattern into data that tests can pin exactly:

- :func:`collective_census` lowers any traceable function against its
  (abstract or concrete) inputs and walks the ClosedJaxpr — including
  every sub-jaxpr under ``scan``/``while``/``cond``/``pjit``/
  ``shard_map``/``custom_*`` — counting collective primitives per mesh
  axis. ``psum``/``pmin``/``pmax`` count as ``all_reduce`` (``pmean``
  lowers to psum + divide-by-constant, so it is an all_reduce here
  too). Collectives inside a ``lax.scan`` body are multiplied by the
  static trip count: a RowParallel psum inside a depth-L block scan is
  L psums on the wire, and the census says so.
- :func:`dtype_report` walks the same jaxprs for silent precision
  changes: f32->f64 upcasts (an accidental Python float or x64 flag
  widening a hot buffer 2x) and reductions/contractions carried out
  entirely in 16-bit dtypes (bf16/f16 accumulation — fine for storage,
  usually wrong for sums).
- :func:`donation_report` inspects a jitted function's lowering
  (``Lowered.args_info``) and reports per-argument donation: which
  buffers are donated, which undonated buffers could alias an output
  of identical shape/dtype (params/opt-state in a train step — the
  classic missed ``donate_argnums`` that doubles peak memory), and how
  many bytes each decision covers.

The census's shape is plain nested dicts (axis -> op -> count) so
expected values can be written declaratively — see analysis/specs.py
for the shipped specs of the dp/tp/zero/3D train steps and the serve
prefill/decode programs, and tests/test_qtcheck.py for the pinned
golden counts.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax

# jaxpr primitive name -> census op name. pmean does not appear: it
# lowers to psum + div by the (static) axis size.
COLLECTIVE_OPS = {
    "psum": "all_reduce",
    "pmin": "all_reduce",
    "pmax": "all_reduce",
    "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "ppermute": "ppermute",
    "all_to_all": "all_to_all",
}

_16BIT = ("bfloat16", "float16")


def _eqn_axis_names(eqn) -> Tuple[str, ...]:
    """Named mesh axes a collective eqn reduces/gathers over. psum's
    ``axes`` may mix named axes with positional ints — ints are local
    reductions, not communication, and are dropped."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


@dataclass
class Census:
    """Per-axis collective counts of one lowered program.

    ``counts[axis][op]`` is the number of times ``op`` executes over
    mesh axis ``axis`` in one call of the program (scan bodies
    multiplied by trip count). ``dynamic`` counts collectives under a
    ``while_loop`` whose trip count is unknowable statically — they are
    counted ONCE in ``counts`` and tallied here so a spec can assert
    there are none (every QuintNet train/serve program is while-free).
    """

    counts: Dict[str, Dict[str, int]] = field(default_factory=dict)
    dynamic: int = 0

    def add(self, axis: str, op: str, n: int = 1) -> None:
        per_axis = self.counts.setdefault(axis, {})
        per_axis[op] = per_axis.get(op, 0) + n

    def total(self) -> int:
        return sum(n for per in self.counts.values() for n in per.values())

    def as_dict(self) -> Dict[str, Dict[str, int]]:
        return {a: dict(sorted(ops.items()))
                for a, ops in sorted(self.counts.items())}

    def diff(self, expected: Dict[str, Dict[str, int]]) -> List[str]:
        """Human-readable mismatches vs a declarative expected census
        (empty list == exact match). Zero-count entries on either side
        are ignored so specs can write explicit zeros."""
        lines = []
        keys = set()
        for side in (self.counts, expected):
            for a, ops in side.items():
                keys.update((a, op) for op, n in ops.items() if n)
        for a, op in sorted(keys):
            got = self.counts.get(a, {}).get(op, 0)
            want = expected.get(a, {}).get(op, 0)
            if got != want:
                lines.append(f"{a}.{op}: expected {want}, got {got}")
        return lines


def _subjaxprs(params) -> List[Any]:
    """Every jaxpr-valued entry of an eqn's params (ClosedJaxpr or raw
    Jaxpr, single or sequence) — covers pjit/scan/while/custom_* and
    whatever primitive grows one next."""
    found = []
    for v in params.values():
        if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
            found.append(v)
        elif isinstance(v, (tuple, list)):
            found.extend(vv for vv in v
                         if hasattr(vv, "eqns") or hasattr(vv, "jaxpr"))
    return found


def _as_open(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _walk(jaxpr, census: Census, mult: int, dyn: bool,
          visit: Optional[Callable] = None) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if visit is not None:
            visit(eqn, mult, dyn)
        if name in COLLECTIVE_OPS:
            for axis in _eqn_axis_names(eqn):
                census.add(axis, COLLECTIVE_OPS[name], mult)
                if dyn:
                    census.dynamic += mult
            continue
        if name == "scan":
            body = _as_open(eqn.params["jaxpr"])
            _walk(body, census, mult * int(eqn.params["length"]), dyn,
                  visit)
        elif name == "while":
            for key in ("cond_jaxpr", "body_jaxpr"):
                _walk(_as_open(eqn.params[key]), census, mult, True, visit)
        elif name == "cond":
            # mutually exclusive branches: a collective runs on at most
            # one path — take the elementwise max over branches so the
            # census reports the worst case, not the sum
            branches = [Census() for _ in eqn.params["branches"]]
            for b, bj in zip(branches, eqn.params["branches"]):
                _walk(_as_open(bj), b, 1, dyn, visit)
            merged: Dict[str, Dict[str, int]] = {}
            for b in branches:
                for a, ops in b.counts.items():
                    for op, n in ops.items():
                        cur = merged.setdefault(a, {})
                        cur[op] = max(cur.get(op, 0), n)
            for a, ops in merged.items():
                for op, n in ops.items():
                    census.add(a, op, n * mult)
            census.dynamic += mult * max((b.dynamic for b in branches),
                                         default=0)
        else:
            for sub in _subjaxprs(eqn.params):
                _walk(_as_open(sub), census, mult, dyn, visit)


def collective_census(fn: Callable, *args, **kwargs) -> Census:
    """Trace ``fn`` against ``args``/``kwargs`` (concrete arrays or
    ShapeDtypeStructs — nothing executes) and count its collectives.

    ``fn`` may be a plain function, a ``jax.jit``-wrapped one, or a
    shard_map'd program; jit boundaries show up as ``pjit`` eqns and
    are walked through."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    census = Census()
    _walk(closed.jaxpr, census, 1, False)
    return census


# ---------------------------------------------------------------------------
# dtype promotion report


@dataclass(frozen=True)
class DtypeIssue:
    kind: str        # "f64-upcast" | "half-accum"
    primitive: str
    detail: str
    count: int       # occurrences on the wire (scan-multiplied)


def dtype_report(fn: Callable, *args,
                 allow_half_accum_primitives: Tuple[str, ...] = (),
                 **kwargs) -> List[DtypeIssue]:
    """Silent-precision audit of one traced program.

    Flags (a) any eqn producing float64 from narrower float inputs
    (or an explicit convert to f64) — the classic accidental-x64 2x
    memory/bandwidth tax, and (b) ``reduce_sum``/``dot_general``/
    ``cumsum`` eqns whose output stays 16-bit — accumulation carried
    out in bf16/f16 truncates every partial sum, which is exactly the
    failure mode mixed-precision recipes exist to avoid (accumulate in
    f32, store in bf16)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    found: Dict[Tuple[str, str, str], int] = {}

    def visit(eqn, mult, _dyn):
        name = eqn.primitive.name
        out_dtypes = [v.aval.dtype for v in eqn.outvars
                      if hasattr(v.aval, "dtype")]
        in_dtypes = [v.aval.dtype for v in eqn.invars
                     if hasattr(v, "aval") and hasattr(v.aval, "dtype")]
        for od in out_dtypes:
            if od == np.float64 and any(
                    np.issubdtype(d, np.floating) and d != np.float64
                    for d in in_dtypes):
                key = ("f64-upcast", name,
                       f"{[str(d) for d in in_dtypes]} -> float64")
                found[key] = found.get(key, 0) + mult
        if (name in ("reduce_sum", "dot_general", "cumsum")
                and name not in allow_half_accum_primitives):
            for od in out_dtypes:
                if str(od) in _16BIT:
                    key = ("half-accum", name, f"accumulates in {od}")
                    found[key] = found.get(key, 0) + mult

    census = Census()
    _walk(closed.jaxpr, census, 1, False, visit)
    return [DtypeIssue(kind=k, primitive=p, detail=d, count=n)
            for (k, p, d), n in sorted(found.items())]


# ---------------------------------------------------------------------------
# donation report


@dataclass(frozen=True)
class ArgDonation:
    path: str
    shape: Tuple[int, ...]
    dtype: str
    bytes: int
    donated: bool
    aliasable: bool   # an output leaf of identical shape+dtype exists


@dataclass
class DonationReport:
    args: List[ArgDonation]

    @property
    def donated_bytes(self) -> int:
        return sum(a.bytes for a in self.args if a.donated)

    @property
    def undonated_aliasable(self) -> List[ArgDonation]:
        """The headline finding: buffers a caller is almost certainly
        discarding (an identically-shaped output replaces them — the
        params/opt-state pattern) that the program does not donate.
        Each one is peak-memory paid twice."""
        return [a for a in self.args if a.aliasable and not a.donated]

    def summary(self) -> str:
        flagged = self.undonated_aliasable
        lines = [f"{len(self.args)} array args, "
                 f"{self.donated_bytes} bytes donated, "
                 f"{len(flagged)} undonated-but-aliasable"]
        lines += [f"  MISSED {a.path}: {a.shape} {a.dtype} ({a.bytes} B)"
                  for a in flagged]
        return "\n".join(lines)


def _path_str(path) -> str:
    return jax.tree_util.keystr(path) or "<arg>"


def donation_report(jitted: Callable, *args, **kwargs) -> DonationReport:
    """Lower a jitted function and report per-argument donation.

    ``aliasable`` marks undonated inputs for which an output leaf of
    the same shape+dtype is still UNCLAIMED — each output slot can
    alias at most one donated input, so donated args consume matching
    slots first (a decode step with two int32[S] inputs and one
    int32[S] output flags nothing once one of them is donated). The
    flagged set is the train-step params/opt-state shape of missed
    donation: peak memory paid twice. Buffers that cannot alias any
    output (an eval batch feeding scalar metrics) still benefit from
    donation (freed during the computation instead of after), but only
    aliasable ones are definite misses."""
    from collections import Counter

    lowered = jitted.lower(*args, **kwargs)
    info_flat = jax.tree_util.tree_flatten_with_path(lowered.args_info)[0]
    out_shape = jax.eval_shape(jitted, *args, **kwargs)
    slots = Counter((tuple(l.shape), str(l.dtype))
                    for l in jax.tree_util.tree_leaves(out_shape))
    entries = []
    for path, info in info_flat:
        # public .aval on newer jax; _aval on 0.4.x ArgInfo
        aval = getattr(info, "aval", None) or info._aval
        if not hasattr(aval, "shape"):
            continue
        sig = (tuple(aval.shape), str(aval.dtype))
        entries.append((path, aval, sig, bool(info.donated)))
    aliasable = [False] * len(entries)
    for i, (_, _, sig, donated) in enumerate(entries):
        if donated and slots[sig] > 0:   # donated args claim slots first
            slots[sig] -= 1
            aliasable[i] = True
    for i, (_, _, sig, donated) in enumerate(entries):
        if not donated and slots[sig] > 0:
            slots[sig] -= 1
            aliasable[i] = True
    rows = []
    for i, (path, aval, sig, donated) in enumerate(entries):
        nbytes = int(np.prod(aval.shape, dtype=np.int64)
                     * np.dtype(aval.dtype).itemsize)
        rows.append(ArgDonation(
            path=_path_str(path), shape=tuple(aval.shape),
            dtype=str(aval.dtype), bytes=nbytes,
            donated=donated, aliasable=aliasable[i]))
    return DonationReport(args=rows)


# ---------------------------------------------------------------------------
# gathered-view audit (paged attention, nn/attention.paged_attend)


def _walk_skip_kernels(jaxpr, visit) -> None:
    """Walk every eqn (scan/cond/pjit bodies included, a
    ``pallas_call`` itself too) EXCEPT inside
    ``pallas_call`` kernels: kernel-internal memory ops act on VMEM
    blocks by construction, which is exactly the property the
    gathered-view audit exists to distinguish from HBM traffic."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in _subjaxprs(eqn.params):
            _walk_skip_kernels(_as_open(sub), visit)


def row_walk_calls(fn: Callable, *args, pool_shape, pools: int = 2,
                   **kwargs) -> int:
    """Count the calls of the per-row walk
    (ops/paged_attention.paged_walk_attention) that read the pool IN
    PLACE: ``pallas_call`` eqns of that name with ``pools`` operands of
    ``pool_shape`` — the whole carried k and v (1: a latent family's
    one pool), no layer's slice and no gathered view. Decode and verify
    on a bf16/f16 pool show one a layer scan's body (a scan body counts
    once) where :func:`gathered_view_gathers` shows none; every other
    program none."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    found = 0

    def visit(eqn):
        nonlocal found
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == "paged_walk_attention"
                and sum(tuple(v.aval.shape) == tuple(pool_shape)
                        for v in eqn.invars) == pools):
            found += 1

    _walk_skip_kernels(closed.jaxpr, visit)
    return found


def gathered_view_gathers(fn: Callable, *args, num_blocks: int,
                          table_width: int, **kwargs) -> int:
    """Count the XLA ``gather`` eqns that materialize a FULL
    block-table row view: operand 0 is a pool-shaped array — the whole
    pool by block, ``[L, num_blocks, ...]``, or one layer's slice of
    it, ``[num_blocks, ...]`` — and the output carries a
    ``table_width`` dim — the `paged_gather`/`paged_gather_scales`
    signature, the HBM round-trip the per-row walk
    (ops/paged_attention.py) exists to delete.

    The count is structural (one per eqn occurrence; a scan body
    counts once, not per trip), and the table dim is positional: a
    pool gather indexed by an [.., W]-wide table slice lands W at
    OUTPUT DIM 1 ([rows, W, slots-or-heads, ...]), so only dim 1 is
    compared — a head/feature dim that happens to equal
    ``table_width`` cannot alias. A serving program that gathers its
    view shows >= 2 per layer (k + v, plus both scale gathers under a
    scaled KV policy); one that walks each row's live blocks in place
    shows ZERO. CALLER CONTRACT: audit a program whose requant span is
    strictly below ``table_width`` (decode's span is 1; for prefill
    pick a bucket well under the row length) — a run covering the
    whole row must legitimately touch every block it wrote.
    ``pallas_call`` interiors are skipped — VMEM block moves are the
    kernel doing its job."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    found = 0

    def visit(eqn):
        nonlocal found
        if eqn.primitive.name != "gather":
            return
        op = eqn.invars[0]
        if not (hasattr(op, "aval") and hasattr(op.aval, "shape")):
            return
        if num_blocks not in tuple(op.aval.shape)[:2]:
            return
        out = tuple(eqn.outvars[0].aval.shape)
        if len(out) >= 2 and out[1] == table_width:
            found += 1

    _walk_skip_kernels(closed.jaxpr, visit)
    return found


def widened_view_dots(fn: Callable, *args, table_width: int,
                      block_size: int, **kwargs) -> int:
    """Count the ``dot_general`` eqns that contract a gathered row view
    in a dtype WIDER than the one it is stored in: one operand carries
    a whole row's positions — a ``table_width * block_size`` dim (the
    head-major ``[B, H, T, Dh]`` view of `paged_gather`, or its rows
    as gathered, ``[B, T, F]``) or that dim still split by block,
    ``table_width, block_size`` side by side — and its float dtype is
    narrower than the other operand's. A TPU has no mixed
    dot, so the compiler widens the BIG operand — a second, f32 copy
    of every row written to HBM and read back each layer (at GPT-2
    XL's serving shapes 75 of a 128-ms decode step).
    nn/attention._masked_sdpa casts the small operand down instead, so
    every paged program reads ZERO here, whatever the pool's dtype;
    two a layer (scores and weighted values) is the mixed form.

    Structural like :func:`gathered_view_gathers` (a scan body counts
    once; ``pallas_call`` interiors are skipped). CALLER CONTRACT: pick
    a geometry whose row length collides with no other dim of a dot
    operand of rank 3 or more."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    row = table_width * block_size
    found = 0

    def is_view(aval) -> bool:
        shape = tuple(aval.shape)
        return len(shape) >= 3 and (
            row in shape
            or (table_width, block_size) in zip(shape, shape[1:]))

    def visit(eqn):
        nonlocal found
        if eqn.primitive.name != "dot_general":
            return
        a, b = (v.aval for v in eqn.invars[:2])
        for view, other in ((a, b), (b, a)):
            if (is_view(view)
                    and jax.dtypes.issubdtype(view.dtype, np.floating)
                    and jax.dtypes.issubdtype(other.dtype, np.floating)
                    and view.dtype.itemsize < other.dtype.itemsize):
                found += 1
                return

    _walk_skip_kernels(closed.jaxpr, visit)
    return found


def view_head_splits(fn: Callable, *args, table_width: int,
                     block_size: int, **kwargs) -> int:
    """Count the ``reshape`` eqns that split a gathered row view's LANE
    dim into heads: the operand ends ``[..., table_width, block_size,
    F]`` (`paged_gather`'s rows, pad lanes cut or not) and the result
    ``[..., table_width, block_size, H, Dh]`` with ``H * Dh == F``. On
    the chip a ``Dh``-wide minor dim is half a lane row, so the split
    view is a COPY of the view, written padded to twice its bytes and
    read back (at GPT-2 XL's decode shapes most of the step: PERF.md,
    PR 28 and 30). A program with few query rows contracts the view
    as gathered instead (nn/attention._lane_diag_sdpa): decode and
    verify programs on a bf16/f16 pool read ZERO; a prefill bucket, an
    f32 pool and a scaled policy's dequantized view keep the split, 2
    a layer body (k and v).

    Structural like :func:`gathered_view_gathers` (a scan body counts
    once; ``pallas_call`` interiors are skipped)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    found = 0

    def visit(eqn):
        nonlocal found
        if eqn.primitive.name != "reshape":
            return
        src = tuple(eqn.invars[0].aval.shape)
        out = tuple(eqn.outvars[0].aval.shape)
        if (len(src) >= 3 and len(out) == len(src) + 1
                and src[-3:-1] == (table_width, block_size)
                and out[:-2] == src[:-1]
                and out[-2] * out[-1] == src[-1]):
            found += 1

    _walk_skip_kernels(closed.jaxpr, visit)
    return found


def pool_scan_operands(fn: Callable, *args, pool_shape: Tuple[int, ...],
                       **kwargs) -> int:
    """Count the ``scan`` eqns of a serving program that take a
    pool-shaped array as xs or give one back as ys: a leaf of
    ``pool_shape`` sliced a layer at a time into the body and stacked
    back out of it. On the chip that form re-lays every layer's slice
    (four layer-sized copies a layer at GPT-2 XL, and two of the whole
    pool at the loop's edge — PERF.md, PR 28). Every paged program
    carries its pools whole in the scan's CARRY and addresses them by
    ``(layer, slot)``, so this reads ZERO for every family, policy and
    program; ``pool_shape`` is any buffer the program keeps per
    sequence — ``k``'s (``v``'s is the same), a scale array's, a
    recurrent state buffer's.

    Structural (a scan counts once, nested scans each on their own;
    ``pallas_call`` interiors are skipped). A carried operand is not
    counted: in ``scan``'s operands the xs come after ``num_consts +
    num_carry``, in its results the ys after ``num_carry``."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    pool_shape = tuple(pool_shape)
    found = 0

    def visit(eqn):
        nonlocal found
        if eqn.primitive.name != "scan":
            return
        skip = eqn.params["num_consts"] + eqn.params["num_carry"]
        streamed = (list(eqn.invars[skip:])
                    + list(eqn.outvars[eqn.params["num_carry"]:]))
        if any(tuple(getattr(v.aval, "shape", ())) == pool_shape
               for v in streamed):
            found += 1

    _walk_skip_kernels(closed.jaxpr, visit)
    return found


def store_reads(fn: Callable, *args, store_shape: Tuple[int, ...],
                **kwargs) -> List[Tuple[int, ...]]:
    """The shapes a program READS out of a per-sequence buffer of
    ``store_shape`` (a window store's ``wk``: ``[L_w, (slots + 1) *
    ring, F]``): the result shape of every ``dynamic_slice``, ``slice``
    or ``gather`` eqn whose operand 0 is store-shaped, one entry an
    eqn. A sliding-window layer must read ``rows * ring`` rows of ONE
    layer — ``(1, rows * ring, F)`` — and never the table's width or
    the whole store (nn/attention.window_gather).

    Structural like :func:`gathered_view_gathers` (a scan body counts
    once; ``pallas_call`` interiors are skipped)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    store_shape = tuple(store_shape)
    found: List[Tuple[int, ...]] = []

    def visit(eqn):
        if eqn.primitive.name not in ("dynamic_slice", "slice", "gather"):
            return
        op = eqn.invars[0]
        if tuple(getattr(getattr(op, "aval", None), "shape", ())) \
                == store_shape:
            found.append(tuple(eqn.outvars[0].aval.shape))

    _walk_skip_kernels(closed.jaxpr, visit)
    return found
