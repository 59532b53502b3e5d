"""Declarative expected-census specs for QuintNet's compiled programs.

Each function returns the exact per-axis collective counts
(``{axis: {op: count}}``) that one call of the corresponding program
puts on the wire, derived from program structure — parameter-tree
leaf counts, block depth, microbatch count — rather than measured and
pasted. tests/test_qtcheck.py checks them against
:func:`~quintnet_tpu.analysis.jaxpr_audit.collective_census` of the
real lowered programs, so ANY change to the communication pattern of
``parallel/`` or ``serve/`` (an extra all-gather in a tp layer, a
second grad reduction, a resharding XLA was forced to insert) fails
tier-1 with a named diff instead of landing as a silent perf
regression.

Census terms, for reading the formulas below:

- **leaf pmean** — ``reduce_grads`` pmeans every gradient leaf over the
  data axes: one all_reduce per parameter leaf, plus one for the loss.
- **row-parallel psum** — each transformer block holds two RowParallel
  projections (attention out-proj, MLP down-proj): 2 psums per block
  per forward; autodiff's transpose doubles it (the backward re-psums
  the replicated cotangents), so a depth-L scan contributes ``4 L``.
- **replicated-grad psum** — leaves replicated over tp (LayerNorms,
  embeddings) receive rank-partial gradients and are psummed over tp:
  one all_reduce per tp-replicated leaf (the sync the reference torch
  implementation omits — parallel/tp.py docstring).
- **clip-norm psum** — ``clip_sharded_grads`` psums the local
  sum-of-squares of every SHARDED leaf over its sharding axes: one
  all_reduce per tp-sharded leaf when ``grad_clip_norm`` is set.
- **ZeRO terms** — ZeRO-1 re-assembles updated params with ONE
  all_gather (the chunks ravel into a single flat vector); ZeRO-2
  replaces the per-leaf dp pmean with ONE reduce_scatter into the
  rank's chunk plus one psum for the chunk-space clip norm.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec

CensusDict = Dict[str, Dict[str, int]]


def _merge(*censuses: CensusDict) -> CensusDict:
    out: CensusDict = {}
    for c in censuses:
        for axis, ops in c.items():
            cur = out.setdefault(axis, {})
            for op, n in ops.items():
                cur[op] = cur.get(op, 0) + n
    return out


def spec_leaf_counts(param_specs, axis: str) -> Tuple[int, int, int]:
    """(total, replicated-over-axis, sharded-over-axis) leaf counts of a
    PartitionSpec tree — the structural inputs to the formulas below."""
    from quintnet_tpu.parallel.train_step import _spec_axes

    leaves = jax.tree.leaves(
        param_specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
    sharded = sum(1 for s in leaves if axis in _spec_axes(s))
    return len(leaves), len(leaves) - sharded, sharded


def expected_dp_train_step(n_param_leaves: int, *,
                           dp_axis: str = "dp") -> CensusDict:
    """make_parallel_train_step on a dp-only mesh: one leaf pmean per
    gradient leaf + the loss pmean. Nothing else — XLA does the
    bucketing/overlap the reference hand-built (parallel/dp.py)."""
    return {dp_axis: {"all_reduce": n_param_leaves + 1}}


def expected_tp_train_step(depth: int, n_tp_replicated: int,
                           n_tp_sharded: int, *, tp_axis: str = "tp",
                           row_collectives_per_block: int = 2,
                           grad_clip: bool = True) -> CensusDict:
    """tp-only train step of a stacked-block model (ViT/GPT-2 layout:
    QKV column-sharded, projections row-sharded):

      2 row-parallel psums/block x depth x (forward + backward)
      + one psum per tp-replicated gradient leaf
      + one psum per tp-sharded leaf for the global clip norm.

    No data axis -> no loss pmean (the loss is already replicated
    across tp by the final psum's semantics)."""
    fwd_bwd = 2 * row_collectives_per_block * depth
    n = fwd_bwd + n_tp_replicated + (n_tp_sharded if grad_clip else 0)
    return {tp_axis: {"all_reduce": n}}


def expected_dp_tp_train_step(n_param_leaves: int, depth: int,
                              n_tp_replicated: int, n_tp_sharded: int,
                              *, dp_axis: str = "dp",
                              tp_axis: str = "tp",
                              grad_clip: bool = True) -> CensusDict:
    """2-axis dp x tp mesh: the dp and tp patterns compose without
    cross terms — dp sees exactly its dp-only census, tp exactly its
    tp-only one. (That THIS holds is the point of auditing: a stray
    resharding would show up as a new op on one of the axes.)"""
    return _merge(
        expected_dp_train_step(n_param_leaves, dp_axis=dp_axis),
        expected_tp_train_step(depth, n_tp_replicated, n_tp_sharded,
                               tp_axis=tp_axis, grad_clip=grad_clip))


def expected_zero1_train_step(n_param_leaves: int, *,
                              dp_axis: str = "dp") -> CensusDict:
    """ZeRO-1: the dp-only census plus ONE all_gather re-assembling the
    updated flat parameter vector from per-rank chunks
    (parallel/zero.py _chunk_apply). Gradient traffic is unchanged —
    that is ZeRO-1's contract (state sharded, grads still allreduced)."""
    return {dp_axis: {"all_reduce": n_param_leaves + 1, "all_gather": 1}}


def expected_zero2_train_step(*, dp_axis: str = "dp",
                              grad_clip: bool = True) -> CensusDict:
    """ZeRO-2: per-leaf dp pmeans collapse into ONE reduce_scatter of
    the flat grad vector straight into the rank's chunk (half the
    allreduce traffic — parallel/zero.py scatter_grad_chunk); the loss
    pmean stays; clipping psums one chunk-space sum-of-squares; one
    all_gather re-assembles params."""
    return {dp_axis: {
        "all_reduce": 1 + (1 if grad_clip else 0),
        "reduce_scatter": 1,
        "all_gather": 1,
    }}


def expected_3d_train_step(n_param_leaves: int, depth: int,
                           n_tp_replicated: int, n_tp_sharded: int,
                           n_pp_replicated: int, n_pp_sharded: int,
                           n_micro: int, pp_size: int, *,
                           dp_axis: str = "dp", tp_axis: str = "tp",
                           pp_axis: str = "pp",
                           grad_clip: bool = True,
                           store_activations: bool = False) -> CensusDict:
    """3D (dp x tp x pp) 1F1B train step.

    - dp: unchanged leaf pmeans + loss pmean;
    - tp: the fwd+bwd row-parallel psums now run once per MICROBATCH,
      and the memory-lean 1F1B variant (``store_activations=False``)
      recomputes each forward inside the backward — one extra forward's
      worth of psums per microbatch;
    - pp: one psum per pp-REPLICATED gradient leaf (stage-partial
      grads: embedding on stage 0, head on the last stage), one per
      pp-SHARDED leaf for the clip norm, one for the loss (masked to
      the last stage, then shared via broadcast_from), plus the 1F1B
      schedule's boundary ppermutes: two per microbatch (its forward
      and backward each cross one boundary per shift of the ladder)
      plus four per stage boundary for the warmup/cooldown sweeps —
      ``2 * n_micro + 4 * (pp_size - 1)`` (pinned empirically over
      pp in {2, 4} x n_micro in {2, 4, 8}; parallel/pp.py).
    """
    per_block = 2
    fwd = per_block * depth
    tp_count = (n_micro * (2 + (0 if store_activations else 1)) * fwd
                + n_tp_replicated + (n_tp_sharded if grad_clip else 0))
    ppermutes = 2 * n_micro + 4 * (pp_size - 1)
    pp_count = (n_pp_replicated + (n_pp_sharded if grad_clip else 0) + 1)
    return {
        dp_axis: {"all_reduce": n_param_leaves + 1},
        tp_axis: {"all_reduce": tp_count},
        pp_axis: {"all_reduce": pp_count, "ppermute": ppermutes},
    }


# ---------------------------------------------------------------------------
# serving programs (quintnet_tpu/serve/engine.py)


def prefill_buckets(prefill_len: int, *, floor: int = 16) -> Tuple[int, ...]:
    """THE canonical padded-length ladder for the bucketed prefill
    programs: powers of two from ``floor`` up to (and capped at)
    ``prefill_len``. A prompt tail of length t runs in the smallest
    bucket >= t, so short prompts stop paying max-length compute while
    the compile count stays bounded: the engine compiles AT MOST
    ``len(prefill_buckets(prefill_len))`` prefill programs (one
    RecompileSentinel per bucket, ``max_compiles=1`` each — the
    no-recompile invariant, now per bucket). Pinned here — engine and
    census tests derive the same ladder from the same place."""
    if prefill_len < 1:
        raise ValueError(f"prefill_len must be >= 1; got {prefill_len}")
    out = []
    b = floor
    while b < prefill_len:
        out.append(b)
        b *= 2
    out.append(prefill_len)
    return tuple(out)


def expected_serve_prefill(n_layers: int, *,
                           tp_axis: Optional[str] = None,
                           vocab_parallel: bool = False) -> CensusDict:
    """One compiled prefill bucket: 2 row-parallel psums per block
    under tp (attention out-proj + MLP down-proj — forward only, no
    autodiff), plus the vocab-parallel embedding psum and logits
    all_gather when the vocabulary is sharded. Single-device: ZERO
    collectives. The census is independent of the bucket width AND of
    the prefix-cache split (paged scatter/gather add no collectives),
    so every bucket program must match this same spec."""
    if tp_axis is None:
        return {}
    c: CensusDict = {tp_axis: {"all_reduce": 2 * n_layers}}
    if vocab_parallel:
        c[tp_axis]["all_reduce"] += 1   # vocab_parallel_embedding psum
        c[tp_axis]["all_gather"] = 1    # vocab_parallel_logits gather
    return c


def expected_serve_decode(n_layers: int, *,
                          tp_axis: Optional[str] = None,
                          vocab_parallel: bool = False) -> CensusDict:
    """One compiled decode step for ALL slots: identical communication
    shape to prefill — the continuous-batching engine adds batching,
    paging and sampling but NO collectives of its own."""
    return expected_serve_prefill(n_layers, tp_axis=tp_axis,
                                  vocab_parallel=vocab_parallel)


def verify_buckets(max_draft: int, *, floor: int = 2) -> Tuple[int, ...]:
    """THE canonical draft-length ladder for the speculative VERIFY
    programs (serve/spec.py): powers of two from ``floor`` up to (and
    capped at) ``max_draft`` — the default ``max_draft=8`` gives
    ``(2, 4, 8)``. A step whose longest draft is k runs in the
    smallest bucket >= k (program width = bucket + 1 tokens per row:
    the slot's last sampled token rides in front of the draft), so the
    engine compiles AT MOST ``len(verify_buckets(max_draft))`` verify
    programs — one RecompileSentinel per bucket, ``max_compiles=1``
    each, extending the bounded-compile invariant to
    ``<= len(prefill_buckets) + len(verify_buckets) + 1 decode``.
    Pinned here so engine, census and compile-count tests derive the
    same ladder from the same place."""
    if max_draft < 1:
        raise ValueError(f"max_draft must be >= 1; got {max_draft}")
    out = []
    b = floor
    while b < max_draft:
        out.append(b)
        b *= 2
    out.append(max_draft)
    return tuple(out)


def expected_serve_verify(n_layers: int, *,
                          tp_axis: Optional[str] = None,
                          vocab_parallel: bool = False) -> CensusDict:
    """One compiled verify bucket: the decode census exactly — verify
    is the decode step widened from 1 to bucket+1 tokens per row, and
    the batched draft scatter/gather (nn/attention.paged_write)
    adds no collectives. Independent of the bucket width, so every
    bucket program must match this same spec."""
    return expected_serve_decode(n_layers, tp_axis=tp_axis,
                                 vocab_parallel=vocab_parallel)


def expected_serve_moe(n_layers: int, *,
                       ep_axis: Optional[str] = None,
                       tp_axis: Optional[str] = None,
                       vocab_parallel: bool = False) -> CensusDict:
    """One compiled serving program (any of prefill/decode/verify) of
    an MoE family whose experts are sharded over ``ep_axis``: the
    dense tp census unchanged (the router, attention and lm_head are
    ep-replicated; expert FFN tp psums fold into the same 2-per-layer
    count) PLUS exactly **2 all_to_alls per MoE layer** — dispatch
    (tokens to their experts' owner ranks) and combine (expert
    outputs back, nn/moe.py) — and nothing else: the capacity-bounded
    scatter/gather is local, the router replicated. ``ep_axis=None``
    (ep=1 or no mesh) is the dense-replicated program: the MoE math
    runs everywhere identically, ZERO ep collectives — the census
    face of the ep=1 == dense-replication bit-identity contract.
    Independent of bucket width, top_k and capacity, so every bucket
    of every program kind must match this same spec."""
    c = expected_serve_prefill(n_layers, tp_axis=tp_axis,
                               vocab_parallel=vocab_parallel)
    if ep_axis is not None:
        c = dict(c)
        c[ep_axis] = dict(c.get(ep_axis, {}))
        c[ep_axis]["all_to_all"] = (
            c[ep_axis].get("all_to_all", 0) + 2 * n_layers)
    return c


def expected_serve_latent_moe(*, chunk: bool) -> Dict[str, object]:
    """What one compiled serving program (all named ``jit_serve_*``
    like the others') of a LATENT family with the dropless router
    (serve/families.pangu_moe_family) reads under the structural audits
    of analysis/jaxpr_audit.py, on one device — the only place it runs:
    the engine refuses it a mesh. ``chunk``: a prefill bucket (True) or
    the decode / a verify program, on a bf16/f16 pool where the
    per-row walk lowers (a TPU, or the interpreter the tests turn on).

    - ``census``: no collective at all. The expert layer is told which
      experts it holds and computes their part; nothing stands in for
      the exchange with the chips that hold the others.
    - ``pool_scan_operands`` 0: the one latent pool rides both layer
      scans' carry (the dense stack's, then the MoE stack's).
    - ``view_head_splits`` 0 in EVERY program, not in decode alone:
      all heads read the one row, so neither form of the attention
      cuts the rows into heads (nn/attention.py).
    - ``widened_view_dots`` 0: both forms round the small operand to
      a bf16 pool's dtype.
    - decode and verify (the ABSORBED form) gather NOTHING: each row
      walks its live blocks of the one pool in place —
      ``gathered_view_gathers`` 0, ``row_walk_calls`` 2 (``pools=1``),
      one in the body of each of the two layer scans
      (nn/attention.latent_attend_absorbed); a prefill bucket (the
      MATERIALIZED form) gathers the one row kind once a scan body, 2,
      and walks nothing."""
    return {"census": {}, "pool_scan_operands": 0, "view_head_splits": 0,
            "widened_view_dots": 0,
            "gathered_view_gathers": 2 if chunk else 0,
            "row_walk_calls": 0 if chunk else 2}


def expected_serve_kda_moe(*, periods: int, chunk: bool) -> Dict[str, object]:
    """What one compiled serving program of a LATENT AND RECURRENT
    family with the group-limited dropless router (serve/families.
    ling_hybrid_family) reads under the structural audits of
    analysis/jaxpr_audit.py, on one device — the only place it runs:
    the engine refuses it a mesh. ``periods``: the model's groups of
    layers (the first is written out, the rest are one scan over
    groups); ``chunk``: a prefill bucket (True) or the decode / verify
    program, on a bf16/f16 pool where the per-row walk lowers.

    - ``census``: no collective at all. The expert layer is told which
      experts it holds and computes their part; nothing stands in for
      the exchange with the chips that hold the others.
    - ``pool_scan_operands`` 0, asked of the latent pool's shape AND of
      both state buffers': all three ride every loop's carry, whole; a
      KDA layer reads and writes its rows of its own slice in place.
    - ``view_head_splits`` 0: all heads read the one latent row.
    - the latent layer is traced once for the written-out group and
      once more for the scan over the others: decode (the ABSORBED
      form) walks each row's live blocks in place — ``row_walk_calls``
      ``min(periods, 2)`` with ``pools=1``, ``gathered_view_gathers`` 0
      — and a prefill bucket (the MATERIALIZED form) gathers the one
      row kind as often and walks nothing."""
    traced = min(periods, 2)
    return {"census": {}, "pool_scan_operands": 0, "view_head_splits": 0,
            "gathered_view_gathers": traced if chunk else 0,
            "row_walk_calls": 0 if chunk else traced}


def expected_serve_window_moe(*, full_runs: int, sliding_runs: int,
                              rows: int, ring: int, width: int,
                              chunk: bool) -> Dict[str, object]:
    """What one compiled serving program of a WINDOW family with the
    dropless router (serve/families.laguna_family) reads under the
    structural audits of analysis/jaxpr_audit.py, on one device — the
    only place it runs: the engine refuses it a mesh. ``full_runs`` /
    ``sliding_runs``: the model's runs of consecutive full / sliding
    layers (a layer scan each: models/laguna.LagunaConfig.runs);
    ``rows``: the program's sequence rows (``max_slots``, 1 for a
    prefill bucket); ``width``: the store's lane width; ``chunk``: a
    prefill bucket (True) or the decode / a verify program.

    - ``census``: no collective at all (every expert is held here).
    - ``pool_scan_operands`` 0, for the block pool AND the window
      store: all four buffers ride every run's scan carry.
    - ``gathered_view_gathers`` 0 in EVERY program: decode and verify
      walk each row's live key blocks of a full layer's pool in place
      (``row_walk_calls`` one a full run, in its scan's body:
      nn/attention._paged_attend_walk), a prefill bucket reads a global
      layer's cache a block of keys at a time
      (nn/attention._paged_attend_key_blocked: no walk), and a sliding
      layer never reads at the table's width.
    - ``store_reads``: a sliding layer reads its rows' rings of ONE
      layer, k and v, ``(1, rows * ring, width)`` each — ``ring`` =
      ``sliding_window + block_size`` positions a row, whatever the
      sequences' lengths.
    - ``view_head_splits`` 0 in decode and verify, for 48 and 64 query
      heads alike (both contract the cached rows as stored, heads on
      the lane diagonal: the walk's kernel over the pool, and
      nn/attention._lane_diag_sdpa over the rings) on a bf16 pool;
      a prefill bucket splits each key block it gathers, never a view
      of the table's width: 0 there too.
    - ``widened_view_dots`` 0."""
    return {"census": {}, "pool_scan_operands": 0,
            "gathered_view_gathers": 0,
            "row_walk_calls": 0 if chunk else full_runs,
            "store_reads": [(1, rows * ring, width)] * (2 * sliding_runs),
            "view_head_splits": 0,
            "widened_view_dots": 0}


def expected_serve_sp_prefill(n_layers: int, sp: int, *,
                              sp_axis: str = "sp") -> CensusDict:
    """One compiled SEQUENCE-PARALLEL prefill bucket (long-context
    serving, serve/longctx.py + nn/attention.ring_paged_prefill), per
    layer:

    - ``2 * sp`` **ppermutes** — the ring: the stacked chunk K/V pair
      and its position vector each rotate once per scan step, ``sp``
      steps (scan body x trip count, exactly how the 1F1B ppermutes
      are counted);
    - one **all_gather** — the chunk's K/V reassembled in rank order
      for the (sp-replicated) pool scatter;

    plus ONE program-wide **all_reduce**: the masked psum that
    replicates position ``t0 - 1``'s hidden row for the logits read.
    Independent of the bucket width (sp shards it, never changes the
    collective count), so every bucket program must match this same
    spec — and the count is a pure function of (n_layers, sp): any
    extra collective XLA or a refactor sneaks in fails the census test
    with a named diff."""
    return {sp_axis: {"ppermute": 2 * sp * n_layers,
                      "all_gather": n_layers,
                      "all_reduce": 1}}


def kv_layout_policies() -> Tuple[str, ...]:
    """THE canonical KV-pool layout-policy ladder (serve/kv_quant.py):
    ``f32``/``bf16`` passthrough, ``int8`` with per-block-per-head
    absmax scales, ``fp8`` unscaled float8_e4m3fn passthrough (scales
    are OPTIONAL in the shared LayoutPolicy protocol — the read path
    is one upcast in the gathered view), and the ``fake_quant``
    identity-scale proof policy. Pinned here for the same reason the
    bucket ladders are: the policy must NOT change the
    compiled-program census. Per policy the engine compiles exactly
    the same sentinel set — ``len(prefill_buckets)`` prefill programs,
    1 decode (or one per LoRA rank bucket), and ``len(verify_buckets)``
    verify programs — because a scaled policy only widens the pool
    operand list (k, v -> k, v, k_scale, v_scale) inside the SAME
    programs; it never adds a program, a collective, or a recompile
    (tests/test_kv_quant.py pins the compile counts,
    tests/test_qtcheck.py the collective + dtype censuses)."""
    return ("f32", "bf16", "int8", "fp8", "fake_quant")


def weight_layout_policies() -> Tuple[str, ...]:
    """THE canonical weight layout-policy ladder
    (serve/weight_quant.py): ``f32`` identity (the param tree passes
    through untouched), ``bf16`` passthrough narrowing, ``int8``/
    ``fp8`` with per-output-channel absmax scales, and the
    ``fake_quant`` identity-scale proof policy (bit-identical to f32).
    Pinned for the zero-new-programs promise: the policy is baked into
    the param tree at engine BUILD (packed ``w`` + ``w_scale`` leaves,
    nn/layers.quantized_matmul dequants inside the existing dots), so
    per policy the engine compiles exactly the same sentinel set, with
    the same collective census — the per-channel scale multiply is
    rank-local elementwise math (tests/test_weight_quant.py pins the
    zero-backend-compile trace, tests/test_qtcheck.py the censuses)."""
    return ("f32", "bf16", "int8", "fp8", "fake_quant")


def attn_kernels() -> Tuple[str, ...]:
    """THE canonical serving attention-backend ladder
    (ops/paged_attention.py): ``xla`` is the gathered-view reference
    oracle, ``pallas`` the fused block-table-walking kernel. Pinned
    here for the same reason the policy ladder is: the backend must
    NOT change any census or bound — per backend the engine compiles
    exactly the same sentinel set, and every ``expected_serve_*``
    census above holds verbatim (the kernel lives strictly inside the
    per-layer attention; the RowParallel psums, the vocab-parallel
    collectives, and the sp ring all sit outside it, and a
    ``pallas_call`` carries no collectives at all). What DOES differ
    is structural and audited separately:
    ``jaxpr_audit.gathered_view_gathers`` must be exactly 0 for pallas
    programs, and for xla ones > 0 wherever they still gather a view:
    every program of an f32 or scaled pool and a many-row prefill
    bucket of a bf16 one. An xla decode or verify program of a bf16/f16
    pool walks each row's live blocks in place instead (one
    ``row_walk_calls`` a layer scan, no gather;
    tests/test_paged_attention.py)."""
    return ("xla", "pallas")


def lora_rank_buckets(max_rank: int, *, floor: int = 4) -> Tuple[int, ...]:
    """THE canonical adapter-rank ladder for multi-tenant LoRA serving
    (serve/adapters.py): powers of two from ``floor`` up to (and capped
    at) ``max_rank``. The packed per-slot adapter tensors a decode step
    ships ride a rank dimension padded to the smallest bucket covering
    the batch's largest bound adapter, so adapters of ANY rank <=
    ``max_rank`` join and leave with zero recompiles: the engine
    compiles AT MOST one decode program per bucket (RecompileSentinel,
    ``max_compiles=1`` each), and the bounded-compile invariant becomes
    ``<= len(prefill_buckets) + len(verify_buckets) + 1 decode per rank
    bucket``. Prefill and verify always run at the TOP bucket (one
    request / already the widest program — re-bucketing them would
    multiply their program count for no win), so their ladders are
    unchanged. The per-slot low-rank deltas add NO collectives under tp
    (column-target deltas are rank-local; row-target deltas ride the
    existing RowParallel psum), so the expected_serve_* censuses above
    hold for LoRA-enabled programs unchanged. Pinned here so engine,
    census and compile-count tests derive the same ladder from the same
    place."""
    if max_rank < 1:
        raise ValueError(f"max_rank must be >= 1; got {max_rank}")
    out = []
    b = floor
    while b < max_rank:
        out.append(b)
        b *= 2
    out.append(max_rank)
    return tuple(out)


# ---------------------------------------------------------------------------
# thread-spawn census (quintnet_tpu/analysis/threads.py, rule QT203)

# THE canonical expected-spawn spec for the fleet/serve/obs tree — the
# concurrency mirror of the collective censuses above. Every
# ``threading.Thread``/``Timer`` construction site in the audited tree
# must appear here, keyed (module, spawning symbol, target), with its
# shutdown story: ``daemon`` (does process exit reap it) and ``joined``
# (does some code path wait for it). qtcheck-threads fails BOTH
# directions — a spawn the spec lacks (new thread landed without a
# shutdown story) and a spec entry the tree lacks (thread removed,
# spec stale) — so the fleet's thread population changes only with a
# named diff here, never silently.
#
# MUST stay a pure literal: the zero-jax qtcheck CLI reads it with
# ``ast.literal_eval`` (threads.load_thread_specs) because this module
# imports jax at the top.
THREAD_SPAWN_SPECS = {
    "quintnet_tpu/fleet/fleet.py": [
        # in-process fleet dispatcher; close() joins it.
        {"symbol": "ServeFleet.__init__", "target": "self._dispatch_loop",
         "daemon": True, "joined": True},
    ],
    "quintnet_tpu/fleet/frontdoor.py": [
        # asyncio event-loop carrier thread; stop() joins it.
        {"symbol": "FrontDoor.start", "target": "run",
         "daemon": True, "joined": True},
        # per-stream disconnect watcher; self-terminates with the
        # stream (bounded by the request), daemon as backstop.
        {"symbol": "FrontDoor._generate_stream", "target": "watch",
         "daemon": True, "joined": False},
    ],
    "quintnet_tpu/fleet/proc.py": [
        # child-side stdin reader + heartbeat: live for the worker
        # process's lifetime, reaped by process exit.
        {"symbol": "replica_main", "target": "reader",
         "daemon": True, "joined": False},
        {"symbol": "replica_main", "target": "heartbeat",
         "daemon": True, "joined": False},
        # parent-side per-replica socket reader; exits on EOF when the
        # child dies or close() shuts the socket.
        {"symbol": "ProcReplica.attach", "target": "self._read_loop",
         "daemon": True, "joined": False},
        # fleet accept + dispatch loops; close() joins both.
        {"symbol": "ProcessFleet.__init__", "target": "self._accept_loop",
         "daemon": True, "joined": True},
        {"symbol": "ProcessFleet.__init__", "target": "self._dispatch_loop",
         "daemon": True, "joined": True},
        # async prefix-handoff push (PR 12); bounded by the RPC
        # timeout, daemon so a hung peer can't block close().
        {"symbol": "ProcessFleet._finish", "target": "self._run_handoff",
         "daemon": True, "joined": False},
        # tiered-KV peer-fetch daemon (PR 15); same bounded-RPC story.
        {"symbol": "ProcessFleet._dispatch_loop",
         "target": "self._run_peer_fetch",
         "daemon": True, "joined": False},
        # warmup fan-out: non-daemon worker threads joined in-call.
        {"symbol": "ProcessFleet.warmup", "target": "one",
         "daemon": False, "joined": True},
    ],
    "quintnet_tpu/fleet/replica.py": [
        # per-replica worker; stop() joins it.
        {"symbol": "Replica.__init__", "target": "self._worker",
         "daemon": True, "joined": True},
    ],
}
