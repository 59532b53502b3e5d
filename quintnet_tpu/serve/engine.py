"""Continuous-batching step loop over the paged KV pool.

The engine multiplexes many requests onto a SMALL FIXED SET of
compiled programs:

- ``prefill`` (one program per padded-length BUCKET — powers of two up
  to ``prefill_len``, analysis/specs.prefill_buckets): one request at
  a time, the UNCACHED TAIL of its prompt right-padded to the smallest
  bucket that holds it (causality makes pad columns inert; logits are
  read at the dynamic true length). Positions covered by a prefix-cache
  hit are not recomputed at all — the request's block table references
  the cached blocks and the tail program starts at a dynamic offset
  (``prefill_from``, serve/families.py). Short prompts stop paying
  max-length compute, shared prompts stop paying for their prefix;
- ``decode``: ONE step for ALL ``max_slots`` rows at once — static
  shapes, inactive slots masked (they point at the pool's null block
  and their outputs are dropped), per-row positions/block tables/PRNG
  keys. Requests come and go across steps without any retracing;
- ``verify`` (speculative decoding, ``spec=SpecConfig(...)``): the
  decode step widened to k+1 tokens per row, one program per
  draft-length bucket (analysis/specs.verify_buckets). A host-side
  n-gram drafter (serve/spec.py) proposes each request's continuation
  from its own prompt + generated history; one verify forward scores
  every slot's draft and the engine commits the longest matching
  prefix plus a bonus token — several tokens per request per step when
  the text is predictable, never fewer than one. Draft KV lands in
  TENTATIVE pool blocks rolled back on rejection; committed output is
  bit-identical to plain decoding (greedy and sampled — see
  serve/spec.py for the key-chain argument).

Multi-tenant LoRA (``adapters=AdapterRegistry(...)``,
serve/adapters.py): each engine slot binds one adapter id; the
registry's weights are packed per admission into stacked per-slot
``[L, S, in, r]``/``[L, S, r, out]`` factors (zero rows for base-model
slots — the null-object trick again) and EVERY program above adds each
row's low-rank delta ``scale * (x @ A_slot) @ B_slot`` on the targeted
matmuls (nn/layers.lora_delta). Heterogeneous tenants share one decode
step at base-model batching; the prefix cache namespaces its index by
adapter so cross-tenant token coincidences can never alias KV. Golden
contract: every request's output is token-identical to a dedicated
engine serving that adapter's ``lora_merge_tree`` merged weights
(tests/test_adapters.py).

The no-recompile invariant is now per program: ONE decode program
(adapter-blind engines; one per ``analysis/specs.lora_rank_buckets``
rank bucket with adapters armed) and AT MOST ``len(prefill_buckets)``
prefill programs per (model, mesh) config, each behind its own
RecompileSentinel with ``max_compiles=1`` (tests/test_serve.py
additionally observes zero backend compiles over a mixed trace via a
jax.monitoring hook).

Long context (``chunked_prefill=True``, serve/longctx.py): a prompt
longer than the largest prefill bucket — inadmissible above — is
admitted WHOLE (block table allocated up front; the ceiling becomes
pool capacity) and streamed through the SAME bucket programs across
engine steps at dynamic offsets, at most ``prefill_chunk_budget``
prompt tokens per step (Sarathi-Serve), so generating slots keep
emitting one token every step instead of stalling behind a monolithic
prefill. Chunked output is bit-identical to a single-shot prefill
(each chunk's attention gathers the pool row the previous chunks
wrote — the prefix-cache math), and mid-prefill slots compose with
preemption/deadlines/migration through ``_pos`` (valid-KV count) and
the untouched submit key. With a mesh carrying an ``sp`` axis
(``sp_axis=``), each chunk's attention additionally runs
ring-sharded across the ranks (nn/attention.ring_paged_prefill;
census in analysis/specs.expected_serve_sp_prefill) — ``sp`` absent
or 1 builds exactly the plain programs.

Prefix caching (``prefix_cache=True``, the default): on admission the
engine looks up the longest cached block-chain for ``prompt +
generated`` (serve/kv_pool.py), pins and clones those table entries,
copies-on-write when the chain ends inside a partially-filled block,
and prefills only the uncached tail. On retire AND preempt the
request's blocks are PUBLISHED into the index instead of freed — so a
preemption-resume (and a fleet migration onto an engine that has seen
the prefix) re-prefills almost nothing. The golden contract is
unchanged and non-negotiable: cache-on output is token-identical to
cache-off, including sampling, preemption and cross-replica migration
(tests/test_prefix_cache.py).

Sampling reproduces models/gpt2_generate.autoregress EXACTLY per
request (split-per-step key discipline, same sample_logits call
shapes), so continuous batching is token-for-token identical to N
independent ``gpt2_generate``/``llama_generate`` calls — the golden
contract. Preemption checkpoints a request's generated tokens + evolved
key host-side and resumes by prefilling ``prompt + generated`` (minus
whatever the prefix cache still holds); the continuation samples from
the checkpointed key state, so even sampled runs survive eviction
bit-identically.

Recurrent families (``family.state`` set — state-space layers among
attention ones, serve/families.granite_hybrid_family): the pool keeps a
fixed-size state per SLOT beside the blocks (serve/kv_pool.py), every
program carries the two state buffers beside the pools (donated,
updated in place), and prefill takes the slot's index. What assumes a
sequence is its KV alone — the prefix cache, the host tier, chain
export/import, speculation, adapters, a mesh — is refused at
construction with the missing piece named (:func:`_refuse_for_state`).
Preemption and migration re-prefill ``prompt + generated`` from
position 0 through the chunk program: correct to the arithmetic, not
bit-equal to the run that was interrupted (docs/serving.md, "Recurrent
families").

Latent families (``family.latent`` set — multi-head latent attention,
serve/families.pangu_moe_family): the pool holds ONE row a token and
has no ``v`` buffer, so every program takes and returns one pool
buffer. Blocks are blocks: the prefix cache, chunked prefill,
preemption, speculation and chain export/import work as for any other
family. A mesh, adapters, scaled or float8 KV and scaled weights are
refused at construction (:func:`_refuse_for_latent`).

Window families (``family.window`` set — sliding-window attention
layers among global ones, serve/families.laguna_family and
smallthinker_family): the global
layers page into the pool like any family's; each sliding layer keeps a
ring a SLOT beside it (serve/kv_pool.py, "Window families"). Every
program carries the two ring buffers beside the pools (donated, updated
in place) and prefill takes the slot's index, as for a recurrent
family; admission takes a ring with the blocks and a cleared slot gives
it back. What shares, moves or rolls back blocks alone — the prefix
cache, the host tier, chain export/import, the disaggregated prefill
phase, speculation — and a mesh, adapters, scaled or float8 KV and
scaled weights are refused at construction (:func:`_refuse_for_window`).
Preemption re-prefills ``prompt + generated`` from position 0 through
the chunk program.

All host<->device traffic per step is O(max_slots) scalars plus the
sampled tokens — the pool and parameters never leave the device. A step
reads back ONCE, after its last dispatch (:meth:`ServeEngine._fetch`):
the decode's tokens and keys, the first token and key of every row whose
prefill ended in the step, and every program's routing stats, in one
``jax.device_get``. Such a row rides the step's decode all the same: its
first token and key are written into the decode's rows ON the device
(``serve_join_row``), and the host commits the first token before the
decode's. An engine that speculates reads a first token at once — its
drafter needs it before the verify — and so does one under a mesh,
whose prefill outputs and uploaded rows are placed apart. Under
a TP mesh the whole step runs in one shard_map (head-sharded pool,
RowParallel psum per layer, replicated tokens), exactly the
``gpt2_generate_tp`` arrangement.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from quintnet_tpu.analysis.recompile import (RecompileError,
                                             RecompileSentinel)
from quintnet_tpu.analysis.specs import lora_rank_buckets as _rank_buckets
from quintnet_tpu.analysis.specs import prefill_buckets as _spec_buckets
from quintnet_tpu.models.gpt2_generate import sample_logits
from quintnet_tpu.nn.attention import noted_reads, noted_window_reads
from quintnet_tpu.obs.recorder import StepRecord, StepRecorder
from quintnet_tpu.obs.recorder import register as register_recorder
from quintnet_tpu.obs.spans import (SERVE_STEP, StepPhases, setup_span,
                                    warmup_program)
from quintnet_tpu.serve.adapters import (AdapterRegistry, adapter_paths,
                                         nest, tree_at)
from quintnet_tpu.serve.families import Family
from quintnet_tpu.serve.kv_pool import KVPool
from quintnet_tpu.serve.kv_quant import make_policy
from quintnet_tpu.serve.kv_tier import HostTier, PromotionState
from quintnet_tpu.serve.weight_quant import (augment_weight_specs,
                                             make_weight_policy,
                                             present_targets,
                                             quantize_params,
                                             weight_bytes)
from quintnet_tpu.serve.metrics import ServeMetrics
from quintnet_tpu.serve.scheduler import (FINISHED, PROMOTING, WAITING,
                                          DeadlineExceeded, Request,
                                          RequestProgress, Scheduler)
from quintnet_tpu.serve.spec import NgramDrafter, SpecConfig


def check_admissible(prompt_len: int, max_new_tokens: int, *,
                     max_seq_len: int, prefill_len: int,
                     usable_blocks: int, block_size: int,
                     max_slots: int = 0,
                     chunked_prefill: bool = False,
                     prefix_cache: bool = True,
                     kv_tier: bool = False) -> None:
    """Submit-time rejection of requests an engine with these limits
    can NEVER run. Standalone (no engine instance) so a remote
    dispatcher — the process fleet's parent, which has only the
    engine's ``limits()`` dict from the hello handshake — fails fast at
    ITS front door instead of round-tripping a doomed request to a
    replica process. ``max_slots`` (dispatch-window sizing) and
    ``prefix_cache`` (the disaggregated fleet's handoff precondition,
    validated at fleet startup) and ``kv_tier`` (whether a host-RAM
    second tier is attached — the fleet's tier-peer-lookup trigger)
    ride along in ``limits()`` and are accepted (unused) here so the
    dict splats straight in — none is an admissibility bound. ``chunked_prefill`` (serve/longctx.py) lifts
    the prefill-window bound: a chunked engine streams any prompt
    through bucket-sized chunks, so only ``max_seq_len`` and pool
    capacity remain."""
    if prompt_len < 1:
        raise ValueError("empty prompt")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    total = prompt_len + int(max_new_tokens)
    if total > max_seq_len:
        raise ValueError(
            f"prompt {prompt_len} + max_new {max_new_tokens} "
            f"exceeds max_seq_len={max_seq_len}")
    # a preemption-resume prefills prompt + generated (up to
    # total - 1 tokens), so prefill_len must cover that, not just
    # the prompt — cache hits can shrink the tail but are never
    # guaranteed (the chain may have been evicted). Chunked engines
    # have no such window: any prefill streams through the buckets.
    if total - 1 > prefill_len and not chunked_prefill:
        raise ValueError(
            f"prompt {prompt_len} + max_new {max_new_tokens} - 1 "
            f"exceeds prefill_len={prefill_len} (resume after "
            f"preemption prefills prompt + generated tokens). Long "
            f"prompts are served by the chunked-prefill mode: "
            f"ServeEngine(chunked_prefill=True) admits any prompt the "
            f"pool can hold and streams it through bucket-sized "
            f"chunks without starving decode (docs/serving.md, "
            f"'Long context')")
    # fail fast on requests the pool can NEVER admit: admission
    # needs blocks_for(total_len + 1) in the worst (cache-cold)
    # case — otherwise the scheduler would return None forever and
    # run() would spin
    worst = -(-total // block_size)
    if worst > usable_blocks:
        raise ValueError(
            f"KV pool too small for this request: needs up to "
            f"{worst} blocks, pool has {usable_blocks} "
            f"usable (block_size={block_size})")


def _refuse_for_state(family: Family, **asked) -> None:
    """A recurrent family's sequence is its KV AND its per-slot state.
    Every feature below moves, shares or rolls back KV alone; each is
    refused with the piece it lacks, none falls back quietly."""
    missing = {
        "prefix_cache": (
            "prefix_cache=True",
            "a cached block chain is reusable only with the recurrent "
            "state AT ITS LAST BLOCK'S BOUNDARY, and no state snapshots "
            "are kept there; pass prefix_cache=False"),
        "kv_tier": (
            "kv_tier_bytes > 0",
            "the host tier spills the prefix cache, which needs state "
            "snapshots at block boundaries"),
        "spec": (
            "spec (speculative decoding)",
            "a rejected draft rolls its KV blocks back, and nothing "
            "rolls the recurrent state back"),
        "adapters": (
            "adapters",
            "LoRA deltas are not plumbed through the recurrent "
            "layers' projections"),
        "mesh": (
            "a tp or sp mesh",
            "the recurrent state and the recurrent layers' projections "
            "are not head-sharded, and the chunked scan has no ring "
            "form"),
        "kv_chain": (
            "export_kv_chain / import_kv_chain",
            "the handoff payload carries KV blocks and no recurrent "
            "state"),
        "prefill_only": (
            "prefill_only (the disaggregated prefill phase)",
            "a prefill-phase retirement hands off its KV chain, and "
            "the chain carries no recurrent state"),
    }
    for key, on in asked.items():
        if on:
            what, why = missing[key]
            raise NotImplementedError(
                f"family {family.name!r} keeps recurrent state beside "
                f"its KV; {what} is refused: {why} (ROADMAP M4)")


def _refuse_for_latent(family: Family, **asked) -> None:
    """A latent family caches ONE row a token that all heads read, and
    routes without drops over the experts it holds. What that does not
    compose with yet is refused here, each with the piece it lacks.
    Any other family passes."""
    if family.latent is None:
        return
    missing = {
        "mesh": (
            "a mesh (tp, sp or ep axes)",
            "the latent row is not head-sharded, its prefill has no "
            "ring form, and the dropless router has no exchange over "
            "an ep axis"),
        "adapters": (
            "adapters",
            "LoRA deltas are not plumbed through the low-rank "
            "projections"),
        "kv_policy": (
            "a scaled or float8 KV layout (int8, fp8, fake_quant)",
            "the latent row is one group with no per-head scale, and "
            "the absorbed form contracts the rows as stored: pass "
            "kv_dtype 'f32' or 'bf16'"),
        "weights": (
            "a scaled weight layout (int8, fp8, fake_quant)",
            "the grouped expert matmul applies no per-channel scale: "
            "pass weights_dtype 'f32' or 'bf16'"),
    }
    for key, on in asked.items():
        if on:
            what, why = missing[key]
            raise NotImplementedError(
                f"family {family.name!r} serves from a latent pool; "
                f"{what} is refused: {why} (ROADMAP M1, M3)")


def _refuse_for_window(family: Family, **asked) -> None:
    """A window family's sequence is its blocks (global layers) AND its
    rings (sliding layers), which hold the last positions only and are
    overwritten in place. What the rings cannot do yet is refused here,
    each with the piece it lacks. Any other family passes."""
    if family.window is None:
        return
    missing = {
        "prefix_cache": (
            "prefix_cache=True",
            "a cached block chain is reusable only with the sliding "
            "layers' rings AS THEY STOOD at its last block's boundary, "
            "and no ring snapshots are kept there; pass "
            "prefix_cache=False"),
        "kv_tier": (
            "kv_tier_bytes > 0",
            "the host tier spills the prefix cache, which needs ring "
            "snapshots at block boundaries"),
        "spec": (
            "spec (speculative decoding)",
            "a rejected draft's rows have overwritten the ring's oldest "
            "positions, and nothing rolls a ring back"),
        "adapters": (
            "adapters",
            "LoRA deltas are not plumbed through the two attention "
            "shapes' projections"),
        "mesh": (
            "a mesh (tp, sp or ep axes)",
            "the layer kinds' query heads are not head-sharded, "
            "the rings are not sharded, the windowed prefill has no "
            "ring-attention form, and the dropless router has no "
            "exchange over an ep axis"),
        "kv_policy": (
            "a scaled or float8 KV layout (int8, fp8, fake_quant)",
            "the rings carry no per-block scales and are contracted as "
            "stored: pass kv_dtype 'f32' or 'bf16'"),
        "weights": (
            "a scaled weight layout (int8, fp8, fake_quant)",
            "the grouped expert matmul applies no per-channel scale: "
            "pass weights_dtype 'f32' or 'bf16'"),
        "kv_chain": (
            "export_kv_chain / import_kv_chain",
            "the handoff payload carries KV blocks and no rings"),
        "prefill_only": (
            "prefill_only (the disaggregated prefill phase)",
            "a prefill-phase retirement hands off its KV chain, and "
            "the chain carries no rings"),
        "block_size": (
            "a block_size other than the family's",
            "the rings are window + block_size rows: build the family "
            "(laguna_family, smallthinker_family) with the engine's "
            "block_size"),
    }
    for key, on in asked.items():
        if on:
            what, why = missing[key]
            raise NotImplementedError(
                f"family {family.name!r} keeps a window store beside "
                f"its blocks; {what} is refused: {why} (ROADMAP M2, M5)")


@dataclass
class _FirstToken:
    """A row whose prefill ended in this step, its first token and key
    still on the device until the step's read (``ServeEngine._fetch``).
    ``keeps_slot``: the row holds its slot and rides the step's decode;
    False for a row known to end on its first token, whose slot was
    given back at the prefill."""
    slot: int
    req: Request
    tok0: jax.Array
    key2: jax.Array
    keeps_slot: bool


class ServeEngine:
    @setup_span("build")
    def __init__(self, family: Family, params, *, max_slots: int = 8,
                 block_size: int = 16, num_blocks: int = 64,
                 max_seq_len: Optional[int] = None,
                 prefill_len: Optional[int] = None,
                 prefill_bucket_sizes: Optional[Sequence[int]] = None,
                 prefix_cache: bool = True,
                 spec: "SpecConfig | bool | None" = None,
                 adapters: Optional[AdapterRegistry] = None,
                 lora_targets: Optional[Sequence[str]] = None,
                 lora_max_rank: int = 8,
                 lora_rank_bucket_sizes: Optional[Sequence[int]] = None,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, policy: str = "fcfs",
                 mesh=None, tp_axis: str = "tp",
                 sp_axis: Optional[str] = None,
                 ep_axis: Optional[str] = None,
                 chunked_prefill: bool = False,
                 prefill_chunk_budget: Optional[int] = None,
                 kv_dtype=None,
                 weights_dtype=None,
                 kv_tier_bytes: int = 0,
                 kv_tier_promote_budget_bytes: Optional[int] = None,
                 attn_kernel: str = "xla",
                 logger=None, log_every: int = 0,
                 clock=time.monotonic,
                 tracer=None, recorder=None):
        self.family = family
        self.params = params
        self.max_slots = int(max_slots)
        self._recurrent = family.state is not None
        if self._recurrent:
            _refuse_for_state(
                family, prefix_cache=prefix_cache,
                kv_tier=int(kv_tier_bytes) > 0,
                spec=spec not in (None, False),
                adapters=adapters not in (None, False),
                mesh=mesh is not None)
        self._latent = family.latent is not None
        _refuse_for_latent(
            family, mesh=mesh is not None,
            adapters=adapters not in (None, False))
        self._window = family.window is not None
        _refuse_for_window(
            family, prefix_cache=prefix_cache,
            kv_tier=int(kv_tier_bytes) > 0,
            spec=spec not in (None, False),
            adapters=adapters not in (None, False),
            mesh=mesh is not None,
            block_size=self._window and family.window.ring
            != family.window.window + int(block_size))
        # per-slot buffers beside the pools (a recurrent family's state,
        # a window family's rings): two more arguments of every program,
        # under this name, and the slot's index to prefill
        self._slot_buffers = ("state" if self._recurrent
                              else "window" if self._window else None)
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.mesh = mesh
        self.tp_axis = tp_axis if mesh is not None else None
        # sequence-parallel prefill (serve/longctx.py): an ``sp`` mesh
        # axis of size > 1 swaps the prefill programs for ring-attention
        # ones (chunk K/V sharded across the ranks while scoring, one
        # all_gather for the replica-local pool write). sp absent or of
        # size 1 builds EXACTLY today's programs — the byte-identity
        # contract engine(sp=1) promises.
        self.sp_axis: Optional[str] = None
        if sp_axis is not None and (mesh is None
                                    or sp_axis not in mesh.shape):
            # an explicitly-requested sp axis the mesh does not carry
            # is a misconfiguration, not a degenerate case — silently
            # running replicated would burn N devices for nothing
            raise ValueError(
                f"sp_axis={sp_axis!r} is not an axis of the mesh "
                f"({None if mesh is None else tuple(mesh.shape)}); "
                f"pass a mesh with that axis (size 1 falls back to "
                f"the plain programs) or drop sp_axis")
        if (mesh is not None and sp_axis is not None
                and mesh.shape[sp_axis] > 1):
            if family.prefill_from_sp is None:
                raise ValueError(
                    f"family {family.name!r} has no sequence-parallel "
                    f"prefill path (Family.prefill_from_sp is None)")
            if tp_axis in mesh.shape and mesh.shape[tp_axis] > 1:
                raise NotImplementedError(
                    "sequence-parallel prefill does not yet compose "
                    "with tensor parallelism — use an sp-only mesh "
                    "(tp x sp is a future extension)")
            if adapters:
                raise NotImplementedError(
                    "sequence-parallel prefill does not yet compose "
                    "with multi-tenant adapters")
            self.sp_axis = sp_axis
        if self.sp_axis is not None or (
                mesh is not None and tp_axis not in mesh.shape):
            # sp-only mesh: params/pool replicated, no tp collectives
            self.tp_axis = None
        # one paged attention (nn/attention.paged_attend), its form the
        # code's own choice from dtypes and shapes; "xla" names it
        if attn_kernel != "xla":
            raise ValueError(
                f"attn_kernel={attn_kernel!r} is refused: the only paged "
                f"attention is the code's own choice, 'xla'")
        self.attn_kernel = attn_kernel
        # MoE serving (nn/moe.py through the family moe_args seam): an
        # ``ep`` mesh axis of size > 1 shards the experts — one
        # all_to_all each way per MoE layer inside every program
        # (census pinned in analysis/specs.expected_serve_moe). ep
        # absent or of size 1 builds the dense-replicated MoE programs
        # — the bit-identity contract engine(ep=1) promises. ep x tp
        # composes (moe_specs column/row-shards the expert FFN inside
        # each expert); ep x sp and ep x adapters are rejected here,
        # PR-9 style. MoEArgs misconfigurations fail HERE with
        # actionable errors, never deep inside the first serving
        # step's trace.
        moe = getattr(family.cfg, "moe_args", None)
        self.moe_args = moe
        self._moe_on = moe is not None
        # routing stats of the step's programs: on the device until the
        # step's read (_moe_dev), then on the host until folded (_moe_acc)
        self._moe_dev: List[Dict] = []
        self._moe_acc: List[Dict] = []
        self.ep_axis: Optional[str] = None
        if moe is not None:
            if not 1 <= moe.top_k <= moe.n_experts:
                raise ValueError(
                    f"MoEArgs.top_k={moe.top_k} must be in "
                    f"[1, n_experts={moe.n_experts}]")
            if moe.capacity is not None and int(moe.capacity) < 1:
                raise ValueError(
                    f"MoEArgs.capacity={moe.capacity} gives every "
                    f"expert a non-positive token buffer (every "
                    f"routed token would be dropped) — pass a "
                    f"positive capacity, or None to derive it from "
                    f"capacity_factor")
            if moe.capacity is None and moe.capacity_factor <= 0:
                raise ValueError(
                    f"MoEArgs.capacity_factor={moe.capacity_factor} "
                    f"must be > 0 — it sizes the per-expert token "
                    f"buffer C = ceil(S*top_k/E * capacity_factor)")
            if self.sp_axis is not None:
                raise NotImplementedError(
                    "sequence-parallel prefill does not yet compose "
                    "with MoE families — drop sp_axis")
        if ep_axis is not None:
            if moe is None:
                raise ValueError(
                    f"ep_axis={ep_axis!r} requires an MoE family "
                    f"(cfg.n_experts > 0); this {family.name!r} config "
                    f"is dense")
            if mesh is None or ep_axis not in mesh.shape:
                # like sp: an explicitly-requested axis the mesh does
                # not carry is a misconfiguration, not a degenerate
                # case — silently running replicated would burn N
                # devices for nothing
                raise ValueError(
                    f"ep_axis={ep_axis!r} is not an axis of the mesh "
                    f"({None if mesh is None else tuple(mesh.shape)}); "
                    f"pass a mesh with that axis (size 1 falls back to "
                    f"the dense-replicated MoE programs) or drop "
                    f"ep_axis")
            if adapters:
                raise NotImplementedError(
                    "expert-parallel serving does not yet compose "
                    "with multi-tenant adapters — drop ep_axis or "
                    "serve adapters on a replicated MoE engine")
            ep = int(mesh.shape[ep_axis])
            if moe.n_experts % ep != 0:
                raise ValueError(
                    f"n_experts={moe.n_experts} must be divisible by "
                    f"the ep axis size {ep} — each rank owns "
                    f"n_experts/ep experts (nn/moe.py moe_specs)")
            if ep > 1:
                self.ep_axis = ep_axis
        self.logger = logger
        self.log_every = int(log_every)
        self.clock = clock
        # observability (quintnet_tpu/obs/): an obs.StepRecorder keeps
        # the per-step flight-recorder ring — ON by default, host-only
        # and bounded: the engine owns one unless handed one — and an
        # obs.Tracer the per-request spans (opt-in). Both are INERT:
        # every hook reads host-side state the step already computed —
        # no device traffic, no host syncs, no key/sampling influence —
        # so tracing on is token-BIT-identical to tracing off and the
        # compiled-program census is unchanged (tests/test_obs.py).
        # Plain assignable attributes, not construction-only config:
        # the fleets attach their own AFTER the builder spec ran
        # (fleet/proc.py replica_main); ``recorder`` is attached at the
        # end of construction, once the facts it carries exist.
        self.tracer = tracer
        self._phases = StepPhases(clock)
        self._recorder: Optional[StepRecorder] = None
        self.prefix_cache = bool(prefix_cache)
        # speculative decoding (serve/spec.py): None/False -> off,
        # True -> defaults, or a SpecConfig. Drafting is host-side;
        # the verify programs are built below beside prefill/decode.
        if spec is True:
            spec = SpecConfig()
        elif spec is False:
            spec = None
        self.spec: Optional[SpecConfig] = spec
        self.drafter = NgramDrafter(spec) if spec is not None else None

        # multi-tenant LoRA (serve/adapters.py): None -> adapter-blind
        # engine whose compiled programs are byte-identical to the
        # pre-adapter surface; an AdapterRegistry (or True for a fresh
        # default one) arms per-slot adapter deltas in every program.
        if adapters is True:
            adapters = AdapterRegistry()
        elif adapters is False:
            adapters = None
        self.adapters: Optional[AdapterRegistry] = adapters
        if self.adapters is not None:
            targets = tuple(lora_targets or family.lora_targets)
            if not targets:
                raise ValueError(
                    f"family {family.name!r} declares no default LoRA "
                    f"targets; pass lora_targets=")
            self.lora_targets = targets
            self._lora_paths = adapter_paths(params["blocks"], targets)
            if not self._lora_paths:
                raise ValueError(
                    f"no LoRA targets {targets} found in the model's "
                    f"block tree")
            rb = tuple(sorted(set(
                int(b) for b in (lora_rank_bucket_sizes
                                 or _rank_buckets(lora_max_rank)))))
            if not rb or rb[0] < 1:
                raise ValueError(f"invalid LoRA rank buckets {rb}")
            # the canonical ladder (analysis/specs.lora_rank_buckets):
            # one decode program per bucket; prefill/verify run at the
            # top bucket (see _lora_args)
            self.lora_rank_buckets = rb
            self.lora_max_rank = rb[-1]
            S, R = self.max_slots, self.lora_max_rank
            # packed per-slot factors, one (a, b) pair per targeted
            # matmul: [L, S, in, R] / [L, S, R, out], zero rows for
            # base-model slots (the KV pool's null-object trick applied
            # to weights). DEVICE-resident masters updated one slot at
            # a time on (un)binding — a binding change ships only that
            # slot's [L, in, R] rows, never the whole pack; the sliced
            # per-bucket views in _lora_args_cache are device-side
            # copies rebuilt lazily after a change.
            self._lora_specs = None
            flat_specs = None
            if mesh is not None:
                from quintnet_tpu.serve.adapters import \
                    packed_lora_spec_flat

                flat_specs = packed_lora_spec_flat(
                    family.partition_specs(tp_axis)["blocks"],
                    self._lora_paths)
                self._lora_specs = nest(flat_specs)
            self._lora_shapes: Dict = {}
            self._lora_dev: Dict = {}
            for path in self._lora_paths:
                w = tree_at(params["blocks"], path)["w"]
                L, fin, fout = w.shape
                self._lora_shapes[path] = (L, fin, fout)
                a = jnp.zeros((L, S, fin, R), w.dtype)
                b = jnp.zeros((L, S, R, fout), w.dtype)
                if mesh is not None:
                    from jax.sharding import NamedSharding

                    a = jax.device_put(
                        a, NamedSharding(mesh, flat_specs[path]["a"]))
                    b = jax.device_put(
                        b, NamedSharding(mesh, flat_specs[path]["b"]))
                self._lora_dev[path] = {"a": a, "b": b}
            self._lora_scale = np.zeros((S,), np.float32)
            self._slot_rank = np.zeros((S,), np.int32)
            self._slot_adapter: List[Optional[str]] = [None] * S
            self._lora_args_cache: Dict = {}

            # ONE jitted pack-maintenance program for (un)binding: it
            # writes a single slot's rows into every target's packed
            # tensors in one dispatch, donating the old pack so the
            # update is in-place — host->device traffic per binding
            # change is O(one slot's factors), never the whole pack.
            # One static signature (slot is a traced scalar); warmup()
            # compiles it beside the serving programs so binds inside
            # a zero-recompile trace stay compile-free.
            def serve_pack_update(dev, slot, new):
                return jax.tree.map(
                    lambda d, n: jax.lax.dynamic_update_slice_in_dim(
                        d, n[:, None].astype(d.dtype), slot, axis=1),
                    dev, new)

            self._pack_update = jax.jit(serve_pack_update,
                                        donate_argnums=(0,))

        self.max_seq_len = int(max_seq_len or family.max_positions)
        if self.max_seq_len > family.max_positions:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"n_positions {family.max_positions}")
        self.prefill_len = int(prefill_len or self.max_seq_len)

        # padded-length buckets for the prefill programs: the canonical
        # ladder lives in analysis/specs.py so census/compile-count
        # tests derive the same set the engine compiles
        buckets = tuple(sorted(set(
            int(b) for b in (prefill_bucket_sizes
                             or _spec_buckets(self.prefill_len)))))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"invalid prefill buckets {buckets}")
        if buckets[-1] < self.prefill_len:
            raise ValueError(
                f"largest prefill bucket {buckets[-1]} does not cover "
                f"prefill_len={self.prefill_len} (a preemption-resume "
                f"prefill can need the full length)")
        self.prefill_buckets = buckets
        if self.sp_axis is not None:
            from quintnet_tpu.serve.longctx import validate_sp_buckets

            validate_sp_buckets(buckets, mesh.shape[self.sp_axis])

        # chunked prefill (serve/longctx.py): prompts longer than the
        # top bucket are admitted whole and streamed through the
        # EXISTING bucket programs across steps, at most
        # ``prefill_chunk_budget`` prefill tokens per engine step
        # (Sarathi-style) so decoding slots keep emitting every step
        self.chunked_prefill = bool(chunked_prefill)
        self.prefill_chunk_budget = (buckets[-1]
                                     if prefill_chunk_budget is None
                                     else int(prefill_chunk_budget))
        if self.prefill_chunk_budget < 1:
            raise ValueError(
                f"prefill_chunk_budget must be >= 1; got "
                f"{self.prefill_chunk_budget}")

        # Weight layout policy (serve/weight_quant.py): the targeted
        # block matmuls' weights are packed ONCE here, host-side —
        # deliberately AFTER adapter setup (the LoRA pack dtypes above
        # read the full-precision tree; the delta path stays
        # full-precision ON TOP of the packed base) and before any
        # program is built, so the policy is baked into the param tree
        # ahead of the first trace: same program ladder, same compile
        # counts per policy (analysis/specs.weight_layout_policies).
        self.weight_policy = make_weight_policy(weights_dtype)
        self.weights_dtype = self.weight_policy.name
        _refuse_for_latent(family, weights=self.weight_policy.scaled)
        _refuse_for_window(family, weights=self.weight_policy.scaled)
        self._weight_targets = present_targets(params,
                                               family.weight_targets)
        if self.weight_policy.name != "f32" and not self._weight_targets:
            raise ValueError(
                f"family {family.name!r} has no weight targets in this "
                f"param tree; weights_dtype={self.weights_dtype!r} "
                f"would be a silent no-op")
        self.params = quantize_params(params, self._weight_targets,
                                      self.weight_policy)
        self.weight_bytes = weight_bytes(self.params,
                                         self._weight_targets)

        # KV layout policy (serve/kv_quant.py): kv_dtype is "f32" /
        # "bf16" / "int8" / "fp8" / "fake_quant", a raw dtype (the
        # pre-policy surface), or a KVLayoutPolicy. Scaled policies add
        # the per-block-per-head scale arrays to the pool state — the
        # SAME program ladder compiles either way (compile counts per
        # policy are pinned unchanged, analysis/specs.py).
        self.kv_policy = make_policy(
            kv_dtype if kv_dtype is not None else family.kv_dtype)
        for refuse in (_refuse_for_latent, _refuse_for_window):
            refuse(family, kv_policy=self.kv_policy.scaled
                   or self.kv_policy.name == "fp8")
        sharding = scale_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(mesh, P(None, None, self.tp_axis))
            scale_sharding = NamedSharding(mesh,
                                           P(None, None, self.tp_axis))
        # host-RAM second tier under the prefix cache (serve/
        # kv_tier.py): kv_tier_bytes > 0 attaches a bounded HostTier —
        # eviction demotes published chains there instead of
        # destroying them, and a host-hit at admission re-promotes
        # asynchronously (PROMOTING state) under a per-step block
        # budget so demotion/promotion cost never lands on a decode
        # dispatch.
        self.kv_tier: Optional[HostTier] = None
        if int(kv_tier_bytes) > 0:
            if not self.prefix_cache:
                raise ValueError(
                    "kv_tier_bytes requires prefix_cache=True — the "
                    "host tier spills the prefix cache; with the "
                    "cache off there is nothing to demote")
            self.kv_tier = HostTier(byte_budget=int(kv_tier_bytes))
        elif int(kv_tier_bytes) < 0:
            raise ValueError(
                f"kv_tier_bytes must be >= 0; got {kv_tier_bytes}")
        self.pool = KVPool(
            n_layers=family.n_layers, n_kv_heads=family.n_kv_heads,
            head_dim=family.head_dim, block_size=block_size,
            num_blocks=num_blocks, policy=self.kv_policy,
            sharding=sharding, scale_sharding=scale_sharding,
            prefix_cache=self.prefix_cache, host_tier=self.kv_tier,
            state=family.state, max_slots=self.max_slots,
            latent=family.latent, window=family.window)
        # per-step promotion budget in BLOCKS (Sarathi's budget
        # discipline applied to host->device memcpy): default 4 blocks
        # a step — enough to drain typical chains in a few steps
        # without turning any single step into a bulk transfer
        bpb = self.pool.bytes_per_block
        budget_bytes = (4 * bpb if kv_tier_promote_budget_bytes is None
                        else int(kv_tier_promote_budget_bytes))
        if budget_bytes < 1:
            raise ValueError(
                f"kv_tier_promote_budget_bytes must be >= 1; got "
                f"{budget_bytes}")
        self._promote_budget_blocks = max(1, budget_bytes // bpb)
        # in-flight promotions by rid + rids whose promotion round
        # already ran (one promotion attempt per admission try — stops
        # a promote/evict livelock under extreme pool pressure)
        self._promoting: Dict[int, PromotionState] = {}
        self._promotion_done: set = set()
        # demotions observed DURING a plain decode dispatch — the
        # structural "decode never blocks on a demotion copy" counter
        # (always 0 by step phasing; surfaced so the bench can gate it)
        self._decode_blocked_demotions = 0
        self.table_width = self.pool.blocks_for(self.max_seq_len)
        self.scheduler = Scheduler(self.pool, policy=policy)
        self.metrics = ServeMetrics(clock=clock)

        S, M = self.max_slots, self.table_width
        # host-side slot state (tiny; shipped to device each step)
        self._tok = np.zeros((S,), np.int32)
        self._pos = np.zeros((S,), np.int32)
        self._tables = np.zeros((S, M), np.int32)
        self._key_data = np.array(
            jax.random.key_data(jax.random.split(jax.random.key(0), S)))
        self._slot_req: List[Optional[Request]] = [None] * S
        self._slot_blocks: List[List[int]] = [[] for _ in range(S)]
        # chunked-prefill progress per slot (serve/longctx.ChunkState);
        # a non-None entry means the slot is mid-prefill: it owns its
        # table but does not ride decode/verify steps yet
        self._slot_chunk: List[Optional[object]] = [None] * S

        self._results: Dict[int, Request] = {}
        self._rid_counter = 0
        self._arrival_counter = 0
        self._admissions_paused = False
        # rows whose prefill ended in this step, their first tokens still
        # on the device; and the program that writes one such row's
        # token and key into the decode's uploaded rows — the slot a
        # traced scalar (one program for every slot), the rows donated
        self._firsts: List[_FirstToken] = []

        def serve_join_row(tok, key_data, slot, tok0, key2):
            return tok.at[slot].set(tok0), key_data.at[slot].set(key2)

        self._join_row = jax.jit(serve_join_row, donate_argnums=(0, 1))
        # where a row's first token is read as its prefill ends instead:
        # a drafter reads every row's tokens before the verify, and
        # under a mesh the prefill's outputs live on the mesh while the
        # decode's rows are uploaded to one device — joined, the decode
        # would meet inputs placed as it was never compiled for
        self._join_on_device = self.drafter is None and mesh is None

        # the bounded-compile promise, enforced at call time: every
        # bucket (and the decode step) carries its own sentinel with
        # max_compiles=1, so a drifting abstract signature raises
        # RecompileError naming the leaf instead of silently
        # recompiling (analysis/recompile.py). Every program is its own
        # jitted callable under its own STABLE name (PROGRAM_NAME):
        # ``jit_serve_prefill_b128`` on a device trace, where one shared
        # callable read ``jit_body`` for all of them. A name carries no
        # id or counter, so the persistent compile cache keeps hitting.
        # donation sets = the aliasable args (jaxpr_audit.donation_report):
        # pools update in place; prefill's t0 aliases the sampled token,
        # key_data its evolved key; decode's tok row aliases the next-
        # token row. (ids/tables/pos/start/cow scalars cannot alias an
        # output slot that is not already covered — donating them would
        # only earn XLA's "not usable" warning.) Indices shift with the
        # pool-arg count: scaled KV policies carry 4 pool buffers
        # (k, v, k_scale, v_scale), a recurrent family 4 (k, v, ssm,
        # conv), a window family 4 (k, v, wk, wv), passthrough KV-only
        # ones 2, a latent family 1 (the bodies then take no v_pool).
        # (A recurrent or window prefill's slot index follows key_data:
        # no index moves.)
        n_pool = len(self.pool.caches())
        pool_idx = tuple(range(1, n_pool + 1))
        self._prefills: Dict[int, RecompileSentinel] = {
            b: RecompileSentinel(
                f"serve.prefill[{b}]",
                self._build_prefill(
                    f"serve_prefill_b{b}",
                    donate=pool_idx + (n_pool + 3, n_pool + 7)),
                max_compiles=1)
            for b in self.prefill_buckets}
        # decode: ONE program for adapter-blind engines; with adapters,
        # one program per LoRA rank bucket (the packed factors' rank
        # dim is the only signature difference), chosen per step by the
        # largest bound adapter. Keyed by bucket; None = the
        # adapter-blind program.
        decode_donate = pool_idx + (n_pool + 1, n_pool + 4)
        # program name -> the positions its paged layers round a row's
        # read up to (nn/attention.noted_reads), known once the program
        # is traced: the walk's key block, None for the table's width
        self._read_granule: Dict[str, Optional[int]] = {}
        # the granule the decode program's sliding layers round a row's
        # ring read up to: the walk's key block, the ring where every
        # ring is sliced out whole (nn/attention.noted_window_reads),
        # known once it is traced
        self._ring_read: Optional[int] = None
        if self.adapters is None:
            self._decode = RecompileSentinel(
                "serve.decode",
                self._build_decode("serve_decode", donate=decode_donate),
                max_compiles=1)
            self._decodes: Dict[Optional[int], RecompileSentinel] = {
                None: self._decode}
        else:
            self._decodes = {
                r: RecompileSentinel(
                    f"serve.decode[r{r}]",
                    self._build_decode(f"serve_decode_r{r}",
                                       donate=decode_donate),
                    max_compiles=1)
                for r in self.lora_rank_buckets}
        # verify programs (speculative decoding): one per draft-length
        # bucket — the bucket only changes the run width P = k + 1. ids
        # donates into the
        # candidate-token output (same [S, P] int32 row); key_data does
        # NOT alias anything (the chain output is [S, P, keysize]).
        self._verifies: Dict[int, RecompileSentinel] = {}
        if self.spec is not None:
            self._verifies = {
                k: RecompileSentinel(
                    f"serve.verify[{k}]",
                    self._build_verify(f"serve_verify_b{k}",
                                       donate=pool_idx + (n_pool + 1,)),
                    max_compiles=1)
                for k in self.spec.buckets}

        # the flight-recorder ring, last: its ``static`` facts exist now
        self.recorder = (recorder if recorder is not None
                         else StepRecorder(capacity=4096, clock=clock))

    @property
    def recorder(self) -> Optional[StepRecorder]:
        return self._recorder

    @recorder.setter
    def recorder(self, rec: Optional[StepRecorder]) -> None:
        """Attach a ring: fill its ``static`` facts and make it
        findable (obs.recorder.live) — the only handle a reader with
        no engine in hand has. The fleets attach their own rings after
        construction, so this is a setter, not an argument alone."""
        self._recorder = rec
        if rec is None:
            return
        rec.static.update(
            # every byte of the parameter tree: what a decode step
            # reads (``weight_bytes`` counts the packed targets alone)
            param_bytes=sum(int(x.nbytes)
                            for x in jax.tree.leaves(self.params)),
            kv_bytes_per_token=self.pool.bytes_per_token,
            # the layers that page every position into the pool: a
            # step's ``attended_rows`` over ``context_tokens`` x this
            # is how many times what its rows hold the programs read
            paged_layers=self.pool.n_layers,
            # the part of ``param_bytes`` that is routed experts' (nodes
            # under an ``experts`` key: the dropless router's): a step
            # reads only those of them that received a row
            expert_param_bytes=sum(
                int(x.nbytes) for path, x
                in jax.tree_util.tree_leaves_with_path(self.params)
                if any(getattr(k, "key", None) == "experts"
                       for k in path)),
            # a recurrent family's fixed cost per slot, and the kinds
            # of its layers in model order (0 and None for the rest)
            state_bytes_per_slot=self.pool.state_bytes_per_slot,
            # a window family's fixed cost per slot: its rings over the
            # sliding layers (``kv_bytes_per_token`` is then the global
            # layers' alone)
            window_bytes_per_slot=self.pool.window_bytes_per_slot,
            layer_pattern=(None if self.family.layer_pattern is None
                           else list(self.family.layer_pattern)),
            max_slots=self.max_slots,
            programs=sorted(s.fn.__name__ for s in (
                *self._prefills.values(), *self._decodes.values(),
                *self._verifies.values())))
        register_recorder(rec, self)

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _sample_rows(self, logits, subkeys):
        """Per-row sampling, bit-identical to what autoregress does for
        a [1, V] batch with each row's own key (vmap of the same
        sample_logits call — models/gpt2_generate.py)."""
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.vmap(
            lambda lg, kk: sample_logits(
                lg[None], kk, temperature=self.temperature,
                top_k=self.top_k, top_p=self.top_p)[0]
        )(logits, subkeys).astype(jnp.int32)

    def _build_prefill(self, name: str, *, donate):
        family, bs = self.family, self.pool.block_size
        tp_axis = self.tp_axis
        sp_axis = self.sp_axis
        ep_axis = self.ep_axis
        use_lora = self.adapters is not None
        policy = self.kv_policy
        scaled = policy.scaled

        slot_buffers = self._slot_buffers
        latent = self._latent

        def body(params, k_pool, *rest):
            v_pool = None
            if not latent:
                v_pool, *rest = rest
            if scaled:
                k_scale, v_scale, *rest = rest
            else:
                k_scale = v_scale = None
            extra = {}
            if slot_buffers:
                buf_a, buf_b, *rest = rest
            ids, start, t0, table_row, cow_src, cow_len, key_data, \
                *rest = rest
            if slot_buffers:
                slot, *rest = rest
                extra = {slot_buffers: (buf_a, buf_b), "slot": slot}
            lora, lora_scale = rest if use_lora else (None, None)
            # copy-on-write: when the reusable prefix chain ends inside
            # a partially-filled cached block, its first cow_len slots
            # are copied from cow_src into this request's first private
            # block BEFORE the tail lands — the cached copy stays
            # immutable while the index references it. cow_len == 0
            # degenerates to masked writes into the null block. (Under
            # sp the pool is replicated — every rank does the identical
            # copy.) Scaled policies copy the source block's per-head
            # scales too: the copied slots are raw stored bytes, so
            # they dequantize correctly only under their own scale
            # (cow_len == 0 rewrites dst's scale with itself — inert).
            sl = jnp.arange(bs)
            M = table_row.shape[0]
            dst = table_row[jnp.clip(start // bs, 0, M - 1)]
            dst_idx = jnp.where(sl < cow_len, dst * bs + sl, 0)
            src_idx = cow_src * bs + sl
            # addressed (layer, slot) like every program's writes: a
            # slice of all layers at once (``[:, idx]``) makes the
            # chip's compiler re-lay the whole pool layer-minor for the
            # copy and back after it (four pool-sized copies a prefill)
            every = jnp.arange(k_pool.shape[0])[:, None]
            k_pool = k_pool.at[every, dst_idx].set(k_pool[every, src_idx])
            if v_pool is not None:
                v_pool = v_pool.at[every, dst_idx].set(
                    v_pool[every, src_idx])
            if scaled:
                ksd = jnp.where(cow_len > 0, k_scale[:, cow_src],
                                k_scale[:, dst])
                vsd = jnp.where(cow_len > 0, v_scale[:, cow_src],
                                v_scale[:, dst])
                k_scale = k_scale.at[:, dst].set(ksd)
                v_scale = v_scale.at[:, dst].set(vsd)

            kv_scales = (k_scale, v_scale) if scaled else None
            if sp_axis is None:
                out = family.prefill_from(
                    params, k_pool, v_pool, ids, start, t0, table_row,
                    bs, tp_axis=tp_axis, ep_axis=ep_axis, lora=lora,
                    lora_scale=lora_scale, kv_scales=kv_scales,
                    policy=policy, **extra)
            else:
                # sequence-parallel chunk: ids arrives as this rank's
                # [1, P/sp] slice (the shard_map below splits dim 1);
                # ring attention inside (nn/attention.ring_paged_prefill)
                out = family.prefill_from_sp(
                    params, k_pool, v_pool, ids, start, t0, table_row,
                    bs, sp_axis=sp_axis, tp_axis=tp_axis,
                    kv_scales=kv_scales, policy=policy)
            logits, pools = out[0], out[1:]

            with jax.named_scope("sample"):
                key = jax.random.wrap_key_data(key_data)
                key2, sub = jax.random.split(key)
                tok = sample_logits(logits, sub,
                                    temperature=self.temperature,
                                    top_k=self.top_k, top_p=self.top_p)[0]
                return (*pools, tok.astype(jnp.int32),
                        jax.random.key_data(key2))

        return self._wrap(body, name, n_rest=7 + bool(slot_buffers),
                          donate=donate, ids_sharded=True)

    def _build_decode(self, name: str, *, donate):
        family, bs = self.family, self.pool.block_size
        tp_axis = self.tp_axis
        ep_axis = self.ep_axis
        use_lora = self.adapters is not None
        policy = self.kv_policy
        scaled = policy.scaled

        slot_buffers = self._slot_buffers
        latent = self._latent

        def body(params, k_pool, *rest):
            v_pool = None
            if not latent:
                v_pool, *rest = rest
            extra = {}
            if scaled:
                k_scale, v_scale, *rest = rest
            if slot_buffers:
                buf_a, buf_b, *rest = rest
                extra = {slot_buffers: (buf_a, buf_b)}
            tok, pos, tables, key_data, *rest = rest
            lora, lora_scale = rest if use_lora else (None, None)
            with noted_reads() as reads, noted_window_reads() as rings:
                out = family.decode(
                    params, k_pool, v_pool, tok, pos, tables, bs,
                    tp_axis=tp_axis, ep_axis=ep_axis,
                    lora=lora, lora_scale=lora_scale,
                    kv_scales=(k_scale, v_scale) if scaled else None,
                    policy=policy, **extra)
            self._read_granule[name] = max(reads, default=None)
            self._ring_read = max(rings, default=None)
            logits, pools = out[0], out[1:]
            with jax.named_scope("sample"):
                keys = jax.random.wrap_key_data(key_data)
                pairs = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                nxt = self._sample_rows(logits, pairs[:, 1])
                return (*pools, nxt, jax.random.key_data(pairs[:, 0]))

        return self._wrap(body, name, n_rest=4, donate=donate)

    def _build_verify(self, name: str, *, donate):
        """The speculative verify step (serve/spec.py): ONE forward
        scores every slot's short token run — its last sampled token +
        up to k drafted continuations — through the paged decode math
        (families.verify), then samples a candidate next token at EVERY
        run position with the keys plain decode would have used there.

        Key discipline is the heart of the golden contract: each row's
        split chain ``key -> (key', sub)`` advances once per POSITION
        on device, and the program returns the whole chain — the host
        commits c tokens and adopts the key after exactly c splits, so
        rejected drafts consume no randomness and the committed stream
        is bit-identical to plain decoding (greedy AND sampled)."""
        family, bs = self.family, self.pool.block_size
        tp_axis = self.tp_axis
        ep_axis = self.ep_axis
        use_lora = self.adapters is not None
        policy = self.kv_policy
        scaled = policy.scaled
        latent = self._latent

        def body(params, k_pool, *rest):
            v_pool = None
            if not latent:
                v_pool, *rest = rest
            if scaled:
                k_scale, v_scale, *rest = rest
            ids, starts, tail_lens, tables, key_data, *rest = rest
            lora, lora_scale = rest if use_lora else (None, None)
            with noted_reads() as reads:
                out = family.verify(
                    params, k_pool, v_pool, ids, starts, tail_lens,
                    tables, bs, tp_axis=tp_axis, ep_axis=ep_axis,
                    lora=lora, lora_scale=lora_scale,
                    kv_scales=(k_scale, v_scale) if scaled else None,
                    policy=policy)
            self._read_granule[name] = max(reads, default=None)
            logits, pools = out[0], out[1:]               # [S, P, V]
            P = ids.shape[1]

            def chain_step(kd, _):
                keys = jax.random.wrap_key_data(kd)
                pairs = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                pd = jax.random.key_data(pairs)            # [S, 2, ks]
                return pd[:, 0], (pd[:, 1], pd[:, 0])

            _, (sub_data, chain_data) = jax.lax.scan(
                chain_step, key_data, None, length=P)
            subs = jnp.swapaxes(sub_data, 0, 1)            # [S, P, ks]
            chain = jnp.swapaxes(chain_data, 0, 1)
            if self.temperature <= 0.0:
                toks = jnp.argmax(logits, axis=-1)
            else:
                toks = jax.vmap(jax.vmap(
                    lambda lg, kd1: sample_logits(
                        lg[None], jax.random.wrap_key_data(kd1),
                        temperature=self.temperature, top_k=self.top_k,
                        top_p=self.top_p)[0]))(logits, subs)
            return (*pools, toks.astype(jnp.int32), chain)

        return self._wrap(body, name, n_rest=5, donate=donate)

    def _wrap(self, body, name: str, *, n_rest: int, donate,
              ids_sharded: bool = False):
        """jit under ``name`` (the callable's ``__name__`` is what XLA
        names the module after: ``jit_<name>`` on a device trace, and
        shard_map keeps it), donating the aliasable arguments: the pool buffers
        (decode-state updates are in-place on device) plus the per-step
        host-shipped rows that alias an output (tok/t0/key_data are
        rebuilt from host state each call, so their device buffers are
        dead after the step). Under a mesh, shard_map first: params in
        their training layout, pool head-sharded, everything else
        replicated — and with adapters armed, the packed LoRA factors
        sharded per-target like their weights (adapters.py
        packed_lora_specs: a in-sharded, b out-sharded; never
        donated — they persist across steps).

        Under an ``sp`` mesh (sequence-parallel prefill) everything is
        REPLICATED — params, pool, per-step rows — except the prefill's
        ids, sharded over sp on the token dim (``ids_sharded``): the
        collectives live inside the body (ring ppermutes + the chunk
        K/V all_gather), not in the data layout. Decode/verify run
        fully replicated: every rank computes the identical step, so
        engine semantics (and outputs) match the single-device program
        exactly.

        Scaled KV layout policies (serve/kv_quant.py) carry 4 pool
        buffers — the k/v int8 (or fake-f32) pools plus their
        [L, nb, H] scale arrays, head-sharded over tp exactly like the
        pools — so the pool-spec prefix widens from 2 to 4; everything
        downstream of it is unchanged."""
        body.__name__ = body.__qualname__ = name
        if self.mesh is None:
            return jax.jit(body, donate_argnums=donate)
        from jax.sharding import PartitionSpec as P

        from quintnet_tpu.core import collectives as cc

        n_pool = len(self.pool.caches())
        if self.sp_axis is not None:
            rest = [P()] * n_rest
            if ids_sharded:
                rest[0] = P(None, self.sp_axis)
            smapped = cc.shard_map_fn(
                body, self.mesh,
                in_specs=(P(),) * (1 + n_pool) + tuple(rest),
                out_specs=(P(),) * n_pool + (P(), P()))
            return jax.jit(smapped, donate_argnums=donate)

        # k, v [L, slots, F] and, under a scaled policy, their scales
        # [L, blocks, H]: all head-sharded on dim 2
        pool_specs = (P(None, None, self.tp_axis),) * n_pool
        pspecs = self.family.partition_specs(self.tp_axis, self.ep_axis)
        if self.weight_policy.scaled:
            # scaled weight policies add a w_scale leaf per target; its
            # spec shards exactly like the out dim of its weight
            # (serve/weight_quant.py) — zero new collectives
            pspecs = augment_weight_specs(pspecs, self._weight_targets)
        # MoE families widen every program's return by one trailing
        # routing-stats dict, computed from the replicated router masks
        # — identical on every rank, so a single replicated prefix spec
        # covers the whole pytree.
        moe_out = (P(),) if self._moe_on else ()

        # prefill body: (params, *pools, ids, start, t0, row, cow_src,
        #                cow_len, key[, lora, scale]) -> pools + 2 outs
        # decode  body: (params, *pools, tok, pos, tables, key
        #                [, lora, scale]) -> pools + 2 outs
        # verify  body: (params, *pools, ids, starts, tail_lens, tables,
        #                key[, lora, scale]) -> pools + 2 outs
        lora_specs = ((self._lora_specs, P())
                      if self.adapters is not None else ())
        smapped = cc.shard_map_fn(
            body, self.mesh,
            in_specs=((pspecs,) + pool_specs
                      + (P(),) * n_rest + lora_specs),
            out_specs=pool_specs + moe_out + (P(), P()))
        return jax.jit(smapped, donate_argnums=donate)

    # ------------------------------------------------------------------
    # multi-tenant LoRA (serve/adapters.py)
    # ------------------------------------------------------------------
    def _adapter_shape_check(self, entry) -> None:
        """An adapter must target a subset of this engine's packed
        paths with matching [L, in, r] / [L, r, out] factors and rank
        within the ladder — checked at submit so a bad tenant file
        fails its request, never a shared engine step. Factors at
        paths the engine is NOT configured to pack are an error, not
        an omission: silently dropping a trained target would diverge
        from the adapter's merged-weights golden."""
        from quintnet_tpu.serve.adapters import adapter_factor_paths

        packed = set(self._lora_paths)
        unserved = [p for p in adapter_factor_paths(entry.tree)
                    if p not in packed]
        if unserved:
            raise ValueError(
                f"adapter {entry.adapter_id!r} trains "
                f"{['.'.join(p) for p in unserved]} which this engine "
                f"does not serve (lora_targets={self.lora_targets}) — "
                f"its output would silently diverge from the merged "
                f"weights")
        found = 0
        for path in self._lora_paths:
            node = tree_at(entry.tree, path)
            if node is None:
                continue
            found += 1
            a, b = np.asarray(node["a"]), np.asarray(node["b"])
            L, fin, fout = self._lora_shapes[path]
            r = a.shape[-1]
            ok = (a.shape == (L, fin, r) and b.shape == (L, r, fout))
            if not ok:
                raise ValueError(
                    f"adapter {entry.adapter_id!r} factor shapes at "
                    f"{'.'.join(path)} ({a.shape}, {b.shape}) do not "
                    f"match this engine's blocks "
                    f"([{L}, {fin}, r], [{L}, r, {fout}])")
            if r != entry.rank:
                raise ValueError(
                    f"adapter {entry.adapter_id!r} rank mismatch at "
                    f"{'.'.join(path)}: factors have r={r}, config "
                    f"says {entry.rank}")
        if found == 0:
            raise ValueError(
                f"adapter {entry.adapter_id!r} targets none of this "
                f"engine's LoRA paths {self.lora_targets}")
        if entry.rank > self.lora_max_rank:
            raise ValueError(
                f"adapter {entry.adapter_id!r} rank {entry.rank} "
                f"exceeds the engine's top rank bucket "
                f"{self.lora_max_rank} (lora_max_rank)")

    def validate_adapter(self, adapter_id: str) -> None:
        """Fail-fast surface: is ``adapter_id`` servable by this engine
        right now? Raises ValueError/KeyError otherwise. The entry is
        pinned for the duration of the check — reading ``entry.tree``
        unpinned would race a concurrent LRU eviction into a spurious
        rejection — and released before returning."""
        if self.adapters is None:
            raise ValueError(
                "this engine was built without adapters "
                "(ServeEngine(adapters=AdapterRegistry(...))); "
                "cannot serve adapter_id requests")
        entry = self.adapters.acquire(adapter_id)
        try:
            self._adapter_shape_check(entry)
        finally:
            self.adapters.release(adapter_id)

    def _zero_slot_update(self) -> Dict:
        """An all-zeros single-slot update tree (unbinding, warmup)."""
        R = self.lora_max_rank
        return {p: {"a": np.zeros((L, fin, R), np.float32),
                    "b": np.zeros((L, R, fout), np.float32)}
                for p, (L, fin, fout) in self._lora_shapes.items()}

    def _apply_pack_update(self, slot: int, updates: Dict) -> None:
        """Write one slot's rows into the device-resident pack (one
        jitted dispatch, old pack donated). The args cache is cleared
        FIRST: its verify entry aliases the pack tensors directly, and
        a donated buffer must have no other live reference."""
        self._lora_args_cache.clear()
        self._lora_dev = self._pack_update(
            self._lora_dev, jnp.int32(slot),
            {p: updates[p] for p in self._lora_paths})

    def _bind_slot_adapter(self, slot: int, adapter_id: str) -> None:
        """Pack the adapter's factors into the slot's rows of the
        device-resident stacked [L, S, in, R] / [L, S, R, out] tensors
        (rank-padded with zeros; targets the adapter does not train
        stay zero = base behavior for that matmul). Only THIS slot's
        rows ship to the device."""
        entry = self.adapters.ensure_resident(adapter_id)
        tp = (1 if self.mesh is None
              else self.mesh.shape[self.tp_axis])
        R = self.lora_max_rank
        updates = self._zero_slot_update()
        for path in self._lora_paths:
            node = tree_at(entry.tree, path)
            if node is None:
                continue
            a = np.asarray(node["a"])
            b = np.asarray(node["b"])
            if self.family.lora_layout is not None:
                b = np.asarray(self.family.lora_layout(path, b, tp))
            r = a.shape[-1]
            updates[path]["a"][:, :, :r] = a
            updates[path]["b"][:, :r, :] = b
        self._apply_pack_update(slot, updates)
        self._lora_scale[slot] = entry.scale
        self._slot_rank[slot] = entry.rank
        self._slot_adapter[slot] = adapter_id

    def _unbind_slot_adapter(self, slot: int) -> None:
        if self._slot_adapter[slot] is None:
            return
        self._apply_pack_update(slot, self._zero_slot_update())
        self._lora_scale[slot] = 0.0
        self._slot_rank[slot] = 0
        self._slot_adapter[slot] = None

    def _decode_rank_bucket(self) -> int:
        """Smallest ladder bucket covering the largest bound adapter
        rank among occupied slots (the smallest bucket when the batch
        is all base-model — zero factors at any width are exact)."""
        top = max((int(self._slot_rank[s]) for s in self._active_slots()),
                  default=0)
        for b in self.lora_rank_buckets:
            if b >= top:
                return b
        raise AssertionError(
            f"bound rank {top} exceeds the top bucket — submit-time "
            f"validation should have rejected the adapter")

    def _lora_args(self, kind: str, *, slot: Optional[int] = None,
                   rank_bucket: Optional[int] = None):
        """The (packed tree, scales) argument pair for one program
        call, viewed/sliced from the device-resident masters and cached
        until a binding changes (slices are device-side copies — no
        host traffic on rebuild; the scales are uploaded from a copy, as
        ``StepPhases.upload`` does, since a binding rewrites the host
        array while a program of the step may still read it):

        - ``decode``: full [S]-slot pack at ``rank_bucket`` width (the
          top bucket passes the masters through unsliced);
        - ``verify``: full pack at the TOP bucket (one program family);
        - ``prefill``: the admitted slot's [1]-row slice at the top
          bucket (one request per prefill call).
        """
        if kind == "prefill":
            key = ("prefill", slot)
            if key not in self._lora_args_cache:
                flat = {p: {"a": d["a"][:, slot:slot + 1],
                            "b": d["b"][:, slot:slot + 1]}
                        for p, d in self._lora_dev.items()}
                self._lora_args_cache[key] = (
                    nest(flat),
                    jnp.asarray(self._lora_scale[slot:slot + 1].copy()))
            return self._lora_args_cache[key]
        R = (rank_bucket if kind == "decode" else self.lora_max_rank)
        key = (kind, R)
        if key not in self._lora_args_cache:
            if R == self.lora_max_rank:
                flat = dict(self._lora_dev)
            else:
                flat = {p: {"a": d["a"][..., :R],
                            "b": d["b"][:, :, :R, :]}
                        for p, d in self._lora_dev.items()}
            self._lora_args_cache[key] = (
                nest(flat), jnp.asarray(self._lora_scale.copy()))
        return self._lora_args_cache[key]

    # ------------------------------------------------------------------
    # submission / results
    # ------------------------------------------------------------------
    def limits(self) -> Dict[str, int]:
        """The static admissibility surface as a JSON-able dict — what
        a REMOTE dispatcher needs to run :func:`check_admissible`
        without an engine in its process (the process fleet's hello
        handshake ships this, fleet/proc.py)."""
        return {"max_seq_len": self.max_seq_len,
                "prefill_len": self.prefill_len,
                "usable_blocks": self.pool.usable_blocks,
                "block_size": self.pool.block_size,
                "max_slots": self.max_slots,
                "chunked_prefill": self.chunked_prefill,
                "prefix_cache": self.prefix_cache,
                "kv_tier": self.kv_tier is not None}

    def _check_admissible(self, prompt: np.ndarray,
                          max_new_tokens: int) -> None:
        """Submit-time rejection of requests the engine can NEVER run."""
        check_admissible(prompt.size, max_new_tokens, **self.limits())

    def _enqueue(self, req: Request) -> int:
        req.submit_time = self.clock()
        self._results[req.rid] = req
        self.scheduler.submit(req)
        return req.rid

    def _pin_adapter(self, adapter_id: Optional[str]) -> None:
        """Submit-time pin + validation: the adapter loads (if
        evicted), its refcount rises for the request's lifetime — a
        pinned adapter is never an LRU eviction candidate — and its
        factor shapes are checked against this engine's blocks so a bad
        tenant file fails ITS request at the front door."""
        if adapter_id is None:
            return
        if self.adapters is None:
            raise ValueError(
                "this engine was built without adapters "
                "(ServeEngine(adapters=AdapterRegistry(...))); "
                "cannot serve adapter_id requests")
        entry = self.adapters.acquire(adapter_id)
        try:
            self._adapter_shape_check(entry)
        except Exception:
            self.adapters.release(adapter_id)
            raise

    def submit(self, prompt, max_new_tokens: int, *, priority: int = 0,
               key=None, on_token=None,
               adapter_id: Optional[str] = None,
               deadline_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               prefill_only: bool = False) -> int:
        """Queue one request; returns its id. ``key``: per-request
        sampling key (defaults to fold_in(key(0), rid)) — pass the SAME
        key an independent ``gpt2_generate`` call would get to reproduce
        it token-for-token. ``adapter_id``: serve this request through
        the named LoRA adapter (serve/adapters.py; None = base model) —
        the adapter is pinned in the registry until the request
        finishes. ``deadline_s``: whole-request latency budget from
        now, enforced DURING decode, not only at admission — a request
        whose deadline lapses mid-generation is retired with a typed
        :class:`DeadlineExceeded` (its blocks published back to the
        prefix cache) instead of burning pool capacity on a stream
        nobody is waiting for. ``trace_id``: the request's
        observability identity (quintnet_tpu/obs/) — pass the id an
        upstream surface (fleet, front door) already assigned so spans
        recorded here continue that timeline; defaults to an
        engine-local id. Inert: never influences output."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self._check_admissible(prompt, max_new_tokens)
        if self._recurrent:
            _refuse_for_state(self.family, prefill_only=prefill_only)
        _refuse_for_window(self.family, prefill_only=prefill_only)
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(
                f"deadline_s={deadline_s} already expired at submit")
        self._pin_adapter(adapter_id)
        rid = self._rid_counter
        self._rid_counter += 1
        if key is None:
            key = jax.random.fold_in(jax.random.key(0), rid)
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      priority=int(priority),
                      arrival=self._arrival_counter, on_token=on_token,
                      adapter_id=adapter_id,
                      deadline=(None if deadline_s is None
                                else self.clock() + float(deadline_s)),
                      trace_id=trace_id or f"req-{rid}")
        self._arrival_counter += 1
        req.key_data = np.asarray(jax.random.key_data(key))
        req.prefill_only = bool(prefill_only)
        if self.tracer is not None:
            self.tracer.event(req.trace_id, "submit", rid=rid,
                              prompt_len=int(prompt.size),
                              max_new_tokens=int(max_new_tokens),
                              adapter_id=adapter_id,
                              priority=int(priority))
        return self._enqueue(req)

    def restore_progress(self, progress: RequestProgress, *,
                         on_token=None, prefill_only: bool = False) -> int:
        """Admit a request MIGRATED from another engine of the same
        (family, params): resume from its exported
        :class:`RequestProgress` (see :meth:`export_progress`). The
        resume path is the preemption path — the next admission
        prefills ``prompt + generated`` (minus any prefix-cache hit:
        an engine that has served the prefix resumes nearly for free)
        and keeps sampling from the checkpointed key, so the
        continuation is token-identical to the run the exporting engine
        would have produced. Returns this engine's (new) request id;
        ``on_token`` fires only for tokens generated HERE
        (already-exported tokens were delivered by the exporter).
        ``prefill_only``: serve only the prefill phase — commit and
        emit the first token (real last flag), then retire with the
        blocks published (the disaggregated fleet's prefill-pool
        dispatch; see :class:`Request`.prefill_only)."""
        prompt = np.asarray(progress.prompt, np.int32).reshape(-1)
        if self._recurrent:
            _refuse_for_state(self.family, prefill_only=prefill_only)
        _refuse_for_window(self.family, prefill_only=prefill_only)
        if progress.key_data is None:
            raise ValueError(
                "progress.key_data is required to restore a request "
                "(without it the continuation could not reproduce the "
                "original sampling stream)")
        if len(progress.generated) >= progress.max_new_tokens:
            raise ValueError(
                f"nothing left to generate: {len(progress.generated)} of "
                f"{progress.max_new_tokens} tokens already produced")
        self._check_admissible(prompt, progress.max_new_tokens)
        # the migrated request keeps its adapter binding: this engine's
        # registry loads the adapter from its source if it has never
        # served (or has evicted) the tenant — the cold-replica path
        self._pin_adapter(progress.adapter_id)
        rid = self._rid_counter
        self._rid_counter += 1
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=int(progress.max_new_tokens),
                      priority=int(progress.priority),
                      arrival=self._arrival_counter, on_token=on_token,
                      adapter_id=progress.adapter_id,
                      deadline=(None if progress.deadline_s is None
                                else self.clock()
                                + float(progress.deadline_s)),
                      trace_id=progress.trace_id or f"req-{rid}")
        self._arrival_counter += 1
        req.generated = list(progress.generated)
        req.key_data = np.array(progress.key_data, copy=True)
        req.preemptions = int(progress.preemptions)
        req.prefill_only = bool(prefill_only)
        if self.tracer is not None:
            # the migrated timeline CONTINUES here under the same
            # trace id the exporting engine (or the journal) carried
            self.tracer.event(req.trace_id, "restore", rid=rid,
                              generated=len(req.generated),
                              preemptions=req.preemptions,
                              adapter_id=req.adapter_id)
        return self._enqueue(req)

    def result(self, rid: int) -> np.ndarray:
        req = self._results[rid]
        if req.state != FINISHED:
            raise RuntimeError(f"request {rid} not finished "
                               f"(state={req.state})")
        if req.error is not None:
            raise req.error
        return req.output_ids()

    def request(self, rid: int) -> Request:
        return self._results[rid]

    @property
    def has_work(self) -> bool:
        return (bool(self.scheduler.waiting)
                or any(r is not None for r in self._slot_req))

    # ------------------------------------------------------------------
    # step loop
    # ------------------------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req) if r is not None]

    def _emit(self, req: Request, token: int, *, last: bool) -> None:
        if req.on_token is not None:
            req.on_token(req.rid, int(token), last)

    def _clear_slot(self, slot: int) -> None:
        st = self._slot_chunk[slot]
        if st is not None and st.cow_pinned:
            # the admission plan's COW-source pin is normally released
            # right after the first chunk copies from it; a slot
            # cleared before any chunk ran (preempt/deadline) must
            # release it here or the block leaks a refcount forever
            self.pool.release([st.cow_src])
        self._slot_chunk[slot] = None
        self._slot_req[slot] = None
        self.pool.window_release(slot)
        self._slot_blocks[slot] = []
        self._tables[slot] = 0
        self._tok[slot] = 0
        self._pos[slot] = 0
        if self.adapters is not None:
            self._unbind_slot_adapter(slot)

    def _release_slot_blocks(self, slot: int) -> None:
        """Publish this slot's valid-KV prefix into the prefix index,
        then drop the slot's references. ``self._pos[slot]`` is exactly
        the number of positions holding valid KV (prefill writes
        ``t0``, every decode step writes one more before pos
        increments), and ``output_ids()[:pos]`` are their token ids.
        The request's adapter id namespaces the publish — KV written
        under an adapter is only ever a hit for that adapter. Publish
        must precede release: release RETAINS published blocks (LRU)
        instead of freeing them."""
        req = self._slot_req[slot]
        blocks = self._slot_blocks[slot]
        self.pool.publish(req.output_ids(), blocks, int(self._pos[slot]),
                          namespace=req.adapter_id)
        self.pool.release(blocks)

    def _retire(self, slot: int) -> int:
        req = self._slot_req[slot]
        self._release_slot_blocks(slot)
        self._clear_slot(slot)
        return self._finish(req)

    def _finish(self, req: Request) -> int:
        """The request's end, once its slot is given back."""
        req.state = FINISHED
        req.finish_time = self.clock()
        self.metrics.record_finish(req.finish_time - req.submit_time,
                                   adapter_id=req.adapter_id)
        if self.tracer is not None:
            self.tracer.event(req.trace_id, "finish", rid=req.rid,
                              generated=len(req.generated),
                              preemptions=req.preemptions,
                              handed_off=req.handed_off)
        if req.adapter_id is not None:
            self.adapters.release(req.adapter_id)  # submit-time pin
        return req.rid

    def _fail_request(self, req: Request,
                      error: BaseException) -> None:
        """Terminal typed failure: the request is FINISHED but
        ``result()`` raises ``error``. No token is emitted — the typed
        error is the stream's terminal signal (an ``is_last`` token was
        never produced)."""
        req.error = error
        req.state = FINISHED
        req.finish_time = self.clock()
        if req.adapter_id is not None:
            self.adapters.release(req.adapter_id)  # submit-time pin

    def _sweep_deadlines(self, finished: List[int]) -> None:
        """Retire every request whose deadline has passed — RUNNING
        slots included, which is the point: admission-time checks catch
        a request that arrives late, but only a per-step sweep stops
        the engine from spending decode steps and pool blocks on a
        stream whose client has already timed out. The slot's valid KV
        is PUBLISHED before release (the prefix chain is still good —
        a retry of the same prompt re-prefills almost nothing)."""
        now = self.clock()
        for slot in self._active_slots():
            req = self._slot_req[slot]
            if req.deadline is None or now < req.deadline:
                continue
            self._release_slot_blocks(slot)
            self._clear_slot(slot)
            self._fail_request(req, DeadlineExceeded(
                f"request {req.rid} exceeded its deadline after "
                f"{len(req.generated)}/{req.max_new_tokens} tokens; "
                f"retired mid-decode (blocks published)",
                rid=req.rid, generated=len(req.generated)))
            self.metrics.record_deadline_exceeded()
            if self.tracer is not None:
                self.tracer.event(req.trace_id, "deadline_exceeded",
                                  generated=len(req.generated),
                                  where="running")
            finished.append(req.rid)
        expired = [r for r in self.scheduler.waiting
                   if r.deadline is not None and now >= r.deadline]
        for req in expired:
            self.scheduler.waiting.remove(req)
            # a PROMOTING request dies like any waiting one — whatever
            # its promotion already landed stays published (cache is
            # never wasted), the rest of the plan is abandoned
            self._promoting.pop(req.rid, None)
            self._fail_request(req, DeadlineExceeded(
                f"request {req.rid} still waiting at its deadline; "
                f"never admitted", rid=req.rid, generated=0))
            self.metrics.record_deadline_exceeded()
            if self.tracer is not None:
                self.tracer.event(req.trace_id, "deadline_exceeded",
                                  generated=0, where="waiting")
            finished.append(req.rid)

    # ---- host-tier promotion (serve/kv_tier.py) ----------------------
    def _start_promotion(self, req: Request) -> bool:
        """Probe the combined device+host chain for the queue head; on
        a host-hit (host-resident boundaries would extend the device
        chain) park the request in the PROMOTING state with the plan
        of keys to re-import. Same lookup cap as the admission plan
        (``len(tokens) - 1``: at least one token is always
        prefilled)."""
        tokens = req.output_ids()
        covered, keys = self.pool.plan_promotion(
            tokens, max_tokens=len(tokens) - 1,
            namespace=req.adapter_id)
        if not keys:
            return False
        req.state = PROMOTING
        self._promoting[req.rid] = PromotionState(req=req, keys=keys)
        if self.tracer is not None:
            self.tracer.event(req.trace_id, "kv_promote",
                              phase="start", blocks=len(keys),
                              covered_tokens=int(covered))
        return True

    def _feed_promotions(self) -> None:
        """Advance every in-flight promotion by at most the per-step
        block budget (shared across promotions): host->device copies
        land while OTHER slots keep decoding — the chunk feed's budget
        discipline applied to memcpy. A completed promotion flips its
        request back to WAITING, where this same step's admission loop
        finds the promoted chain as an ordinary device prefix hit. A
        promotion that can make no progress while nothing is running
        (the pool cannot yield a block and no retirement will free
        one) is force-finished — admission's cache-cold fallback is
        always correct, so the degradation is re-prefill, never a
        wedge."""
        budget = self._promote_budget_blocks
        for rid in list(self._promoting):
            if budget <= 0:
                break
            st = self._promoting[rid]
            req = st.req
            if req.state != PROMOTING:  # failed while parked (sweep)
                self._promoting.pop(rid, None)
                continue
            taken, blocks = self.pool.promote_chain(
                st.keys[st.next:], max_blocks=budget)
            st.next += taken
            budget -= blocks
            if blocks and self.tracer is not None:
                self.tracer.event(req.trace_id, "kv_promote",
                                  phase="feed", blocks=blocks,
                                  remaining=st.remaining)
            if st.done or (taken == 0 and blocks == 0
                           and not self._active_slots()):
                self._promoting.pop(rid, None)
                self._promotion_done.add(req.rid)
                req.state = WAITING
                if self.tracer is not None:
                    self.tracer.event(req.trace_id, "kv_promote",
                                      phase="done",
                                      promoted_keys=st.next)

    def peek_kv_chain(self, tokens, *,
                      namespace: Optional[str] = None) -> int:
        """Token positions this engine could serve warm for ``tokens``
        (device chain + host-tier extension). Read-only and cheap —
        the fleet's ``kv_peek`` RPC (tier peer lookup) calls this on
        every candidate replica before choosing whom to pull a chain
        from."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        return self.pool.peek_chain_tokens(tokens, namespace=namespace)

    def _preempt(self, slot: int) -> None:
        """Evict: checkpoint progress host-side (generated tokens are
        already there; the evolved PRNG key rides key_data), publish +
        release the blocks (the published chain usually survives until
        resume, making the re-prefill nearly free), requeue at the head
        of the line."""
        req = self._slot_req[slot]
        req.key_data = self._key_data[slot].copy()
        self._release_slot_blocks(slot)
        self._clear_slot(slot)
        req.preemptions += 1
        self.metrics.record_preempt()
        if self.tracer is not None:
            self.tracer.event(req.trace_id, "preempt",
                              generated=len(req.generated),
                              preemptions=req.preemptions)
        self.scheduler.push_front(req)

    def _append_token(self, req: Request, token: int) -> bool:
        """Record one sampled token; returns True when the request is
        done (EOS or token budget)."""
        req.generated.append(int(token))
        if req.adapter_id is not None:
            self.metrics.record_adapter_token(req.adapter_id)
        now = self.clock()
        if req.first_token_time is None:
            req.first_token_time = now
            self.metrics.record_first_token(
                now - req.submit_time, adapter_id=req.adapter_id)
        elif req.last_token_time is not None:
            # inter-token gap: the starvation signal a monolithic
            # prefill inflates and the chunk budget bounds
            self.metrics.record_itl(now - req.last_token_time)
        req.last_token_time = now
        done = (req.remaining_new_tokens <= 0
                or (self.eos_token_id is not None
                    and int(token) == self.eos_token_id))
        self._emit(req, token, last=done)
        return done

    def _bucket_for(self, tail_len: int) -> int:
        """Smallest prefill bucket that holds ``tail_len`` tokens."""
        for b in self.prefill_buckets:
            if b >= tail_len:
                return b
        raise AssertionError(
            f"tail {tail_len} exceeds the largest bucket "
            f"{self.prefill_buckets[-1]} — _check_admissible should "
            f"have rejected this request")

    def _state_row(self, slot: int) -> Tuple:
        """What a recurrent or window family's prefill takes beside the
        rest: the row of the per-slot buffers its request owns (its
        slot). Nothing for a family whose sequences are blocks only."""
        return (np.int32(slot),) if self._slot_buffers else ()

    def _allocate_slot(self, slot: int, req: Request):
        """The admission prologue both prefill paths share: resolve
        the plan the scheduler's budget check approved (same step, no
        pool mutation in between; recomputed only for direct callers
        in tests), pin the cached chain FIRST — the private-block
        acquire below may evict refcount-zero cached blocks, and
        without the pin it could evict the very chain this admission
        is about to reference — then acquire the private blocks and
        build the slot's table row. Returns the plan."""
        t0 = req.total_len
        plan = req.admit_plan or self.pool.plan_admission(
            req.output_ids(), t0 + 1, namespace=req.adapter_id)
        req.admit_plan = None
        self.pool.acquire_cached(plan.pinned_blocks)
        new = self.pool.acquire(plan.n_new_blocks)
        assert new is not None  # admission checked the budget
        blocks = plan.shared_blocks + new
        if self._window:
            # both kinds or neither: the ring with the blocks
            self.pool.window_acquire(slot, req.rid)
        self._slot_req[slot] = req
        self._slot_blocks[slot] = blocks
        row = np.zeros((self.table_width,), np.int32)
        row[:len(blocks)] = blocks
        self._tables[slot] = row
        return plan

    def _trace_admit(self, req: Request, plan, *, evictions: int,
                     chunked: bool) -> None:
        """Span hook shared by both admission paths: close the queue
        wait and record the AdmitPlan outcome — prefix-hit tokens,
        COW, evictions the allocation forced — the facts that explain
        a slow TTFT after the fact."""
        tr = self.tracer
        if tr is None:
            return
        now = self.clock()
        tr.add(req.trace_id, "queue", t0=req.submit_time, t1=now,
               preemptions=req.preemptions)
        tr.event(req.trace_id, "admit",
                 cached_tokens=int(plan.cached_tokens),
                 shared_blocks=len(plan.shared_blocks),
                 new_blocks=int(plan.n_new_blocks),
                 cow=plan.cow_src is not None,
                 cow_len=int(plan.cow_len),
                 evictions_forced=int(evictions),
                 chunked=chunked, adapter_id=req.adapter_id)

    # ------------------------------------------------------------------
    # MoE routing-stats ledger (serve/metrics.py)
    # ------------------------------------------------------------------
    def _attended_rows(self, sentinel, last) -> int:
        """Pool positions x layers the paged layers of the decode or
        verify program ``sentinel`` READ in a step whose rows' last
        positions were ``last`` [max_slots] (0 for a row that sat out):
        each row rounded up to the program's read granule — a key block
        where it walks the row's live blocks, the table's whole width
        where it still gathers a view."""
        width = self.table_width * self.pool.block_size
        g = self._read_granule.get(sentinel.fn.__name__) or width
        read = np.minimum((np.asarray(last, np.int64) // g + 1) * g, width)
        return int(read.sum()) * self.pool.n_layers

    def _pop_moe(self, pools, *, note: bool = True, decode: bool = False):
        """Split the trailing routing-stats dict off a MoE program's
        pool outputs (serve/families.py widens every MoE program's
        return by one) and bank it for the step ledger, marked with
        whether the DECODE program produced it — on the device, its copy
        to the host started, read with the step's other outputs
        (:meth:`_fetch`). Dense families pass through untouched; warmup
        calls pass ``note=False`` so compile-time probes never pollute
        the serving numbers."""
        if not self._moe_on:
            return pools
        *pools, st = pools
        if note:
            for leaf in jax.tree.leaves(st):
                leaf.copy_to_host_async()
            self._moe_dev.append({**st, "decode": decode})
        return tuple(pools)

    # ------------------------------------------------------------------
    # the step's one read
    # ------------------------------------------------------------------
    def _fetch(self, outs=None):
        """ONE blocking device-to-host read, one ``host_sync``: ``outs``
        (the decode's or the verify's outputs), the first token and key
        of every row whose prefill ended since the last read, and the
        routing stats banked since (to ``_moe_acc``). Returns ``outs``
        and ``[(first, tok0, key2)]`` on the host; commits nothing."""
        firsts, self._firsts = self._firsts, []
        stats, self._moe_dev = self._moe_dev, []
        with self._phases.wait(1):
            outs, got, stats = jax.device_get(
                (outs, [(f.tok0, f.key2) for f in firsts], stats))
        self._moe_acc.extend(stats)
        return outs, [(f, int(t), np.array(k))
                      for f, (t, k) in zip(firsts, got)]

    def _prefill_ended(self, slot: int, req: Request, tok0, key2,
                       finished: List[int]) -> None:
        """The prefill of ``slot``'s request ended with its first token
        ``tok0`` and key ``key2``, both on the device: queue them for
        the step's read. A row known to end on that token (a one-token
        budget, the prefill-only phase) gives its slot back now, blocks
        published, as it always did — the next admission may take
        both; it finishes at the read. Every other row keeps its slot
        and rides this step's decode, joined on the device. An engine
        that speculates, or runs under a mesh, reads at once
        (``_join_on_device``)."""
        self.metrics.record_admit()
        tok0.copy_to_host_async()
        key2.copy_to_host_async()
        keeps = not (req.prefill_only or req.remaining_new_tokens <= 1)
        if not keeps:
            self._release_slot_blocks(slot)
            self._clear_slot(slot)
        self._firsts.append(_FirstToken(slot, req, tok0, key2, keeps))
        if not self._join_on_device:
            _, firsts = self._fetch()
            with self._phases.phase("commit"):
                self._commit_firsts(firsts, finished)

    def _commit_firsts(self, firsts, finished: List[int]) -> List[int]:
        """Commit the first tokens :meth:`_fetch` read, in the order the
        prefills ended: append (EOS and the token budget end the
        request), the prefill-only handoff, the slot's token and key.
        Returns the slots whose row ended on its first token while it
        held the slot (an EOS): retired at its prompt's length, so the
        published chain holds committed positions only, and whatever
        the step's decode computed for it is dropped."""
        ended = []
        for f, tok0, key2 in firsts:
            req = f.req
            done = self._append_token(req, tok0)
            if not done and req.prefill_only:
                # disaggregated prefill phase: the first token is
                # committed and emitted with its REAL last flag above
                # (max_new was never capped, so EOS and one-token
                # budgets retired via ``done``); what remains is
                # decode-pool work, and the chain its blocks published
                # is exactly the handoff payload export_kv_chain ships
                req.handed_off = True
                done = True
            if not f.keeps_slot:
                finished.append(self._finish(req))
                continue
            self._tok[f.slot] = tok0
            self._key_data[f.slot] = key2
            if done:
                ended.append(f.slot)
                finished.append(self._retire(f.slot))
        return ended

    def _drain_moe(self) -> Dict[str, object]:
        """Fold the routing stats banked since the last step boundary
        into ``record_step`` kwargs. expert_tokens counts routed demand
        BEFORE the capacity cut — the honest skew signal (post-cut
        counts saturate at capacity under a hot expert)."""
        acc, self._moe_acc = self._moe_acc, []
        if not acc:
            return {}
        out = {
            "moe_expert_tokens": np.sum(
                [a["expert_tokens"] for a in acc], axis=0),
            "moe_routed_tokens": float(
                np.sum([a["assigned"] for a in acc])),
            "moe_dropped_tokens": float(
                np.sum([a["dropped"] for a in acc])),
            "moe_router_entropy": float(
                np.mean([a["entropy"] for a in acc])),
        }
        if "held_rows" not in acc[0]:
            return out
        # the dropless router over the experts HELD here (nn/moe.py):
        # the step's routings that landed on them, the (layer, expert)
        # pairs among them that received a row, the routings that went
        # to experts held elsewhere — and the same of the decode
        # program alone, whose weight reads they decide
        first, held = self.moe_args.experts_held or (
            0, self.moe_args.n_experts)
        dec = [a for a in acc if a["decode"]]
        out.update(
            expert_rows=float(np.sum([a["held_rows"] for a in acc])),
            experts_touched=float(np.sum([a["touched"] for a in acc])),
            routed_elsewhere=float(np.sum([a["elsewhere"] for a in acc])),
            # the (row tile, expert) visits of the grouped-matmul
            # kernel (nn/moe._expert_rows): over experts_touched, how
            # often an expert's weights met the matrix unit again; 0
            # where the programs keep ragged_dot
            expert_tile_visits=float(
                np.sum([a["tile_visits"] for a in acc])),
            # group-limited routers: the tokens x layers none of whose
            # kept groups has an expert held here
            **({"tokens_without_held_group": float(
                np.sum([a["no_held_group"] for a in acc]))}
               if "no_held_group" in acc[0] else {}),
            decode_expert_rows=float(np.sum([a["held_rows"] for a in dec])),
            decode_experts_touched=float(
                np.sum([a["touched"] for a in dec])),
            decode_expert_tile_visits=float(
                np.sum([a["tile_visits"] for a in dec])),
            decode_held_expert_tokens=np.sum(
                [a["expert_tokens"][first:first + held] for a in dec],
                axis=0) if dec else np.zeros((held,)))
        return out

    def _admit_one(self, slot: int, req: Request,
                   finished: List[int]) -> Tuple[int, int]:
        """Admit ``req`` into ``slot``: reuse the longest cached prefix
        chain, prefill only the uncached tail in the smallest bucket
        that holds it (the first token: :meth:`_prefill_ended`).
        Returns (tail tokens prefilled, cached tokens reused)."""
        ph = self._phases
        t0 = req.total_len
        tokens = req.output_ids()
        ev0 = self.pool.cache_evictions
        plan = self._allocate_slot(slot, req)
        self._trace_admit(req, plan,
                          evictions=self.pool.cache_evictions - ev0,
                          chunked=False)
        with ph.phase("prefill"):
            row = self._tables[slot]

            start = plan.cached_tokens
            tail = tokens[start:t0]
            bucket = self._bucket_for(len(tail))
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :len(tail)] = tail
            extra = ()
            if self.adapters is not None:
                # bind BEFORE the prefill: the tail runs under the
                # request's adapter (a base request leaves the slot's
                # rows zero — exactly the base program)
                if req.adapter_id is not None:
                    self._bind_slot_adapter(slot, req.adapter_id)
                extra = self._lora_args("prefill", slot=slot)
            args = ph.upload(
                ids, np.int32(start), np.int32(t0), row,
                np.int32(plan.cow_src if plan.cow_src is not None else 0),
                np.int32(plan.cow_len), req.key_data,
                *self._state_row(slot))
            with ph.phase("dispatch"):
                *pools, tok0, key2 = self._prefills[bucket](
                    self.params, *self.pool.caches(), *args, *extra)
            self.pool.update(*self._pop_moe(pools))
            if plan.cow_src is not None:
                # the COW source was pinned only for the copy above
                self.pool.release([plan.cow_src])
            self._pos[slot] = t0
            if self.tracer is not None:
                self.tracer.event(req.trace_id, "prefill",
                                  tokens=len(tail), bucket=bucket,
                                  start=int(start))
            self._prefill_ended(slot, req, tok0, key2, finished)
        return len(tail), start

    # ------------------------------------------------------------------
    # chunked prefill (serve/longctx.py)
    # ------------------------------------------------------------------
    def _admit_slot_chunked(self, slot: int, req: Request) -> int:
        """Chunked admission: allocate the request's WHOLE block table
        up front — the prompt-length ceiling becomes pool capacity, not
        the compile ladder — but run no prefill compute yet;
        :meth:`_feed_chunks` streams the uncached tail through the
        bucket programs under the per-step token budget. Returns the
        prefix-cache hit (positions already resident)."""
        from quintnet_tpu.serve.longctx import ChunkState

        t0 = req.total_len
        ev0 = self.pool.cache_evictions
        plan = self._allocate_slot(slot, req)
        self._trace_admit(req, plan,
                          evictions=self.pool.cache_evictions - ev0,
                          chunked=True)
        # mid-prefill invariants: _pos counts exactly the positions
        # holding valid KV (so publish-on-preempt/deadline stays
        # correct), and the PRNG key has NOT advanced — sampling
        # happens once, on the final chunk — so an export mid-prefill
        # carries the submit key and resumes bit-identically anywhere
        self._pos[slot] = plan.cached_tokens
        self._tok[slot] = 0
        self._key_data[slot] = np.array(req.key_data, copy=True)
        req.prefilled = plan.cached_tokens
        if self.adapters is not None and req.adapter_id is not None:
            self._bind_slot_adapter(slot, req.adapter_id)
        self._slot_chunk[slot] = ChunkState(
            next=plan.cached_tokens, t0=t0, cow_src=plan.cow_src,
            cow_len=plan.cow_len, cow_pinned=plan.cow_src is not None)
        return plan.cached_tokens

    def _run_chunk(self, slot: int, req: Request, st, n: int,
                   finished: List[int]) -> None:
        """One ``n``-token chunk through the smallest covering bucket
        program — the SAME compiled ``prefill_from`` call a prefix-
        cache tail uses, at dynamic offset ``st.next``. Intermediate
        chunks discard the program's sampled token and split key (the
        chain must advance exactly once per prefill); the final chunk
        adopts both, exactly like a single-shot admission
        (:meth:`_prefill_ended`)."""
        ph = self._phases
        with ph.phase("prefill"):
            tokens = req.output_ids()
            chunk = tokens[st.next:st.next + n]
            bucket = self._bucket_for(n)
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :n] = chunk
            cow = st.cow_pinned
            extra = (self._lora_args("prefill", slot=slot)
                     if self.adapters is not None else ())
            args = ph.upload(
                ids, np.int32(st.next), np.int32(st.next + n),
                self._tables[slot], np.int32(st.cow_src if cow else 0),
                np.int32(st.cow_len if cow else 0), self._key_data[slot],
                *self._state_row(slot))
            with ph.phase("dispatch"):
                *pools, tok0, key2 = self._prefills[bucket](
                    self.params, *self.pool.caches(), *args, *extra)
            self.pool.update(*self._pop_moe(pools))
            if cow:
                # the COW source was pinned only for the copy above
                self.pool.release([st.cow_src])
                st.cow_pinned = False
            st.next += n
            st.chunks_done += 1
            self._pos[slot] = st.next
            req.prefilled = st.next
            if self.tracer is not None:
                self.tracer.event(req.trace_id, "prefill_chunk",
                                  tokens=int(n), bucket=bucket,
                                  start=st.next - n, final=st.done)
            if not st.done:
                return  # intermediate chunk: tok0/key2 discarded
            self._slot_chunk[slot] = None
            self._prefill_ended(slot, req, tok0, key2, finished)

    def _feed_chunks(self, finished: List[int]) -> Tuple[int, int]:
        """Stream queued chunk work through the bucket programs — at
        most ``prefill_chunk_budget`` prompt tokens this step (the
        Sarathi-Serve knob: bounded prefill work per iteration keeps
        the decode step below emitting every step). Oldest admissions
        first, whole budget to one request before the next (finishing
        a prefill early beats fair-sharing TTFT across all of them).
        Returns (prompt tokens prefilled, chunk invocations)."""
        budget = self.prefill_chunk_budget
        top = self.prefill_buckets[-1]
        tokens_done = chunks = 0
        order = sorted(
            (s for s in self._active_slots()
             if self._slot_chunk[s] is not None),
            key=lambda s: self._slot_req[s].admit_seq)
        for slot in order:
            req = self._slot_req[slot]
            st = self._slot_chunk[slot]
            while budget > 0 and self._slot_chunk[slot] is st:
                n = min(st.remaining, top, budget)
                self._run_chunk(slot, req, st, n, finished)
                budget -= n
                tokens_done += n
                chunks += 1
            if budget <= 0:
                break
        return tokens_done, chunks

    def _grow_or_preempt(self, finished: List[int]) -> None:
        """Ensure every active slot holds the block its next write
        position needs; evict the youngest admission when the pool is
        dry (the allocator transparently evicts LRU cached blocks
        before that). Oldest requests are grown first so eviction
        pressure lands on the youngest (least sunk work). Before it
        evicts, the first tokens still on the device are read and
        committed: the youngest is likely a row admitted this step,
        whose checkpoint needs them, and a first token that ends its
        request frees its blocks instead."""
        order = sorted(self._active_slots(),
                       key=lambda s: self._slot_req[s].admit_seq)
        for slot in order:
            while self._slot_req[slot] is not None:
                need = self.pool.blocks_for(int(self._pos[slot]) + 1)
                if len(self._slot_blocks[slot]) >= need:
                    break
                got = self.pool.acquire(1)
                if got is not None:
                    self._tables[slot][len(self._slot_blocks[slot])] = got[0]
                    self._slot_blocks[slot].extend(got)
                    continue
                if self._firsts:
                    _, firsts = self._fetch()
                    with self._phases.phase("commit"):
                        self._commit_firsts(firsts, finished)
                    continue
                running = [self._slot_req[s] for s in self._active_slots()]
                victim = Scheduler.preempt_victim(running)
                if victim is self._slot_req[slot] and len(running) == 1:
                    raise RuntimeError(
                        f"KV pool too small for a single request of "
                        f"length {int(self._pos[slot]) + 1} "
                        f"(usable blocks: {self.pool.usable_blocks}, "
                        f"block_size: {self.pool.block_size})")
                vslot = next(s for s in self._active_slots()
                             if self._slot_req[s] is victim)
                self._preempt(vslot)

    # ------------------------------------------------------------------
    # speculative decoding (serve/spec.py)
    # ------------------------------------------------------------------
    def _propose_drafts(self, active: List[int]):
        """Ask the n-gram drafter for every active slot's continuation.
        Returns ``{slot: draft np.ndarray}`` when at least one slot
        drafted >= spec.min_draft tokens (the verify step is worth a
        wider program), else None (plain decode). Drafts are capped so
        the commit can never overrun the token budget: at most
        ``remaining_new_tokens - 1`` drafted tokens leaves room for
        the mandatory bonus token."""
        if self.drafter is None:
            return None
        drafts: Dict[int, np.ndarray] = {}
        worthwhile = False
        for slot in active:
            req = self._slot_req[slot]
            cap = min(self.spec.max_draft, req.remaining_new_tokens - 1)
            d = (self.drafter.draft(req.output_ids(), cap)
                 if cap >= 1 else np.zeros((0,), np.int32))
            drafts[slot] = d
            if len(d) >= self.spec.min_draft:
                worthwhile = True
        return drafts if worthwhile else None

    def _verify_step(self, active: List[int],
                     drafts: Dict[int, np.ndarray],
                     finished: List[int]) -> Tuple[int, int, int, int]:
        """One batched verify: write every slot's run (last token +
        draft) through the paged pool, read back per-position candidate
        tokens + the PRNG split chain, commit the longest matching
        prefix + one bonus token per slot, roll back the rest.

        Block accounting: blocks the speculative tail needs beyond the
        slot's committed holding are acquired TENTATIVE (drafts shrink
        when the pool cannot cover them — speculation degrades, never
        preempts); after acceptance the blocks the new committed length
        reaches are committed, the rest rolled back, so published
        chains never observe draft slots. Returns (committed tokens,
        drafted tokens, accepted draft tokens, attended rows)."""
        S = self.max_slots
        tentative: Dict[int, List[int]] = {}
        for slot in active:
            d = drafts[slot]
            pos = int(self._pos[slot])
            have = len(self._slot_blocks[slot])
            # shrink the draft until its tail blocks are acquirable
            while len(d):
                need = self.pool.blocks_for(pos + len(d) + 1) - have
                if need <= 0 or self.pool.can_acquire(need):
                    break
                d = d[:-1]
            drafts[slot] = d
            need = max(0, self.pool.blocks_for(pos + len(d) + 1) - have)
            got = self.pool.tentative_acquire(need) if need else []
            assert got is not None  # can_acquire checked just above
            tentative[slot] = got
            self._tables[slot][have:have + len(got)] = got

        # bucket by the SURVIVING drafts: pool pressure may have shrunk
        # every proposal, and the narrower program is the cheaper one
        k_bucket = self.spec.bucket_for(
            max(len(drafts[s]) for s in active))
        P = k_bucket + 1
        ids = np.zeros((S, P), np.int32)
        starts = np.zeros((S,), np.int32)
        tail_lens = np.zeros((S,), np.int32)
        for slot in active:
            d = drafts[slot]
            ids[slot, 0] = self._tok[slot]
            ids[slot, 1:1 + len(d)] = d
            starts[slot] = int(self._pos[slot])
            tail_lens[slot] = len(d) + 1

        extra = (self._lora_args("verify")
                 if self.adapters is not None else ())
        ph = self._phases
        args = ph.upload(ids, starts, tail_lens, self._tables,
                         self._key_data)
        with ph.phase("dispatch"):
            *pools, toks, chain = self._verifies[k_bucket](
                self.params, *self.pool.caches(), *args, *extra)
        attended = self._attended_rows(self._verifies[k_bucket],
                                       starts + k_bucket)
        self.pool.update(*self._pop_moe(pools))
        (toks, chain), firsts = self._fetch((toks, chain))
        with ph.phase("commit"):
            self._commit_firsts(firsts, finished)
            return (*self._commit_verified(active, drafts, tentative, toks,
                                           chain, finished), attended)

    def _commit_verified(self, active, drafts, tentative, toks, chain,
                         finished) -> Tuple[int, int, int]:
        """The host half of :meth:`_verify_step`: per slot, commit the
        longest matching prefix + the bonus token, adopt the key after
        exactly that many splits, resolve the tentative blocks."""
        committed = drafted = accepted = 0
        for slot in active:
            d = drafts[slot]
            t = toks[slot]
            a = 0
            while a < len(d) and int(t[a]) == int(d[a]):
                a += 1
            # commit candidates t[0..a] — each is exactly the token
            # plain decode would have produced there — stopping early
            # on EOS / token budget (_append_token's own done rule)
            pos0 = int(self._pos[slot])
            c = 0
            done = False
            while c <= a and not done:
                done = self._append_token(self._slot_req[slot], int(t[c]))
                c += 1
            self._tok[slot] = int(t[c - 1])
            self._pos[slot] = pos0 + c
            # adopt the key after exactly c splits: rejected drafts
            # consume no randomness (the bit-parity contract)
            self._key_data[slot] = chain[slot, c - 1]
            # resolve the tentative tail: blocks the committed length
            # reaches stay, the speculative remainder rolls back
            have0 = len(self._slot_blocks[slot])
            got = tentative[slot]
            keep = max(0, min(len(got),
                              self.pool.blocks_for(pos0 + c) - have0))
            if keep:
                self.pool.commit_tentative(got[:keep])
                self._slot_blocks[slot].extend(got[:keep])
            if got[keep:]:
                self.pool.rollback_tentative(got[keep:])
                self._tables[slot][have0 + keep:have0 + len(got)] = 0
            committed += c
            drafted += len(d)
            # committed draft tokens: all of t[0..c-1] except the bonus
            # token at position a — which is only reached when the whole
            # matched prefix committed (an EOS/budget stop inside the
            # draft commits drafted tokens only)
            accepted += min(c, a)
            if self.tracer is not None:
                self.tracer.event(self._slot_req[slot].trace_id,
                                  "verify", committed=c,
                                  drafted=len(d), accepted=min(c, a))
            if done:
                finished.append(self._retire(slot))
        return committed, drafted, accepted

    def step(self) -> List[int]:
        """One scheduler iteration: admit -> (chunked mode) feed
        budget-capped prefill chunks -> grow/preempt -> one decode
        step for every GENERATING slot -> retire finished rows.
        Returns the request ids that finished this step.

        The step runs under a ``qn.serve.step`` annotation and every
        part of it inside a phase (obs/spans.py): a host span on the
        profiler's clock when a session is on, and always the step's
        exclusive phase times in the flight-recorder ring."""
        with jax.profiler.StepTraceAnnotation(
                SERVE_STEP, step_num=self.metrics.steps + 1):
            return self._step()

    def _step(self) -> List[int]:
        finished: List[int] = []
        prefill_tokens = 0
        prefix_hit_tokens = 0
        # flight recorder (obs/recorder.py): the step's wall window is
        # read from the injectable clock WITHOUT any device drain —
        # the recorder must never add blocking to the step loop, so it
        # times dispatch + whatever blocking the step itself did. Until
        # another phase opens, the step is in ``schedule``.
        ph = self._phases
        rec_t0 = ph.begin()
        m = self.metrics
        rec_admitted0 = m.admitted
        rec_preempted0 = m.preempted

        with ph.phase("schedule"):
            # 0. deadline enforcement — running slots AND the waiting queue
            self._sweep_deadlines(finished)

            # 1a. host-tier promotion feed: stream at most the per-step
            # block budget of host->device chain re-imports (the PROMOTING
            # queue head) — decode below still runs for every generating
            # slot, so promotions never stall in-flight streams
            if self._promoting:
                self._feed_promotions()

            # 1. admissions — chunked mode allocates slot + table only
            # (the budget-capped chunk feed below does the compute); plain
            # mode prefills the whole tail here, as always
            while not self._admissions_paused:
                free = self._free_slots()
                if self.kv_tier is not None:
                    w = self.scheduler.waiting
                    # third admission outcome, host-hit: the head's chain
                    # extends onto the host tier — park it PROMOTING (one
                    # round per admission try) instead of re-prefilling
                    # what the tier still holds
                    if (w and w[0].state == WAITING
                            and w[0].rid not in self._promotion_done
                            and self._start_promotion(w[0])):
                        break
                req = self.scheduler.next_admission(len(free))
                if req is None:
                    break
                self._promotion_done.discard(req.rid)
                slot = free[0]
                if self.chunked_prefill:
                    prefix_hit_tokens += self._admit_slot_chunked(slot, req)
                else:
                    tail, hit = self._admit_one(slot, req, finished)
                    prefill_tokens += tail
                    prefix_hit_tokens += hit

            # 1b. chunk feed (chunked mode): at most prefill_chunk_budget
            # prompt tokens through the bucket programs this step — the
            # decode step below still runs for every generating slot, so
            # in-flight streams emit a token per step no matter how long
            # the prompt being prefilled is (Sarathi-Serve)
            prefill_chunks = 0
            if self.chunked_prefill:
                fed, prefill_chunks = self._feed_chunks(finished)
                prefill_tokens += fed

            # 2. block growth / preemption for the upcoming writes
            self._grow_or_preempt(finished)

        # 3. one decode step for every GENERATING slot (mid-prefill
        # slots sit out — their first token comes from their final
        # chunk) — or, when the drafter found a worthwhile proposal,
        # ONE batched verify step scoring every decoding slot's draft
        # (slots with no draft ride along with a 1-token run,
        # bit-equal to decode)
        active = self._active_slots()
        decoding = [s for s in active if self._slot_chunk[s] is None]
        prefilling = [s for s in active
                      if self._slot_chunk[s] is not None]
        # what the decode program reads of the pool this step: every
        # position the rows that ride it hold
        context_tokens = int(self._pos[decoding].sum())
        # a window family's decode reads, in rows x layers: every
        # position of the global layers, at most the window's of the
        # sliding ones LIVE — and ``window_read_rows`` the ring rows
        # its program reads (below)
        window_attrs = {}
        if self._window:
            w = self.family.window
            window_attrs = {
                "global_rows": context_tokens * self.pool.n_layers,
                "window_rows": int(np.minimum(
                    self._pos[decoding], w.window).sum()) * w.n_layers}
            # the ring rows each slot's row of the decode program holds
            # once it has written: none for a row that sits out
            ring_held = np.zeros(self.max_slots, np.int64)
            ring_held[decoding] = np.minimum(self._pos[decoding] + 1, w.ring)
        # bytes of recurrent state this step's programs read and wrote:
        # every decoding slot's once each way, and each prefilled
        # slot's per chunk program that ran (a chunk that starts at 0
        # reads nothing it keeps, a floor's worth of difference)
        per_slot = self.pool.state_bytes_per_slot
        state_bytes = 2 * per_slot * (
            len(decoding) + (prefill_chunks if self.chunked_prefill
                             else m.admitted - rec_admitted0))
        decode_tokens = 0
        draft_tokens = accepted_draft = attended_rows = joined_rows = 0
        spec_step = False
        if decoding:
            drafts = self._propose_drafts(decoding)
            if drafts is not None:
                spec_step = True
                (decode_tokens, draft_tokens, accepted_draft,
                 attended_rows) = self._verify_step(decoding, drafts,
                                                    finished)
            else:
                # structural tier invariant: the plain decode dispatch
                # performs NO pool acquires, so it can never trigger a
                # demotion copy — the snapshot below proves it per
                # step (surfaced as decode_blocked_demotions, pinned
                # at 0 by the bench gate)
                demo0 = (self.kv_tier.demotions
                         if self.kv_tier is not None else 0)
                if self.adapters is None:
                    sentinel, extra = self._decode, ()
                else:
                    R = self._decode_rank_bucket()
                    sentinel = self._decodes[R]
                    extra = self._lora_args("decode", rank_bucket=R)
                tok, pos, tables = self._tok, self._pos, self._tables
                if prefilling:
                    # mid-prefill rows must look INACTIVE to the
                    # decode program: zero table/pos routes their
                    # write to the null block (their real table must
                    # not take a garbage token at position _pos, which
                    # the next chunk would otherwise have to overwrite)
                    tok = tok.copy()
                    pos = pos.copy()
                    tables = tables.copy()
                    for s in prefilling:
                        tok[s] = 0
                        pos[s] = 0
                        tables[s] = 0
                d_tok, *args, d_keys = ph.upload(tok, pos, tables,
                                                 self._key_data)
                # rows whose prefill ended this step: first token and
                # key into the decode's rows on the device
                joined = [f for f in self._firsts if f.keeps_slot]
                joined_rows = len(joined)
                if joined:
                    slots = ph.upload(*(np.int32(f.slot) for f in joined))
                    with ph.phase("dispatch"):
                        for f, s in zip(joined, slots):
                            d_tok, d_keys = self._join_row(
                                d_tok, d_keys, s, f.tok0, f.key2)
                with ph.phase("dispatch"):
                    *pools, nxt, key2 = sentinel(
                        self.params, *self.pool.caches(), d_tok, *args,
                        d_keys, *extra)
                attended_rows = self._attended_rows(sentinel, pos)
                self.pool.update(*self._pop_moe(pools, decode=True))
                (nxt, key2), firsts = self._fetch((nxt, key2))
                key2 = np.array(key2)
                with ph.phase("commit"):
                    ended = self._commit_firsts(firsts, finished)
                    for s in prefilling + ended:
                        # a mid-prefill slot's chain must not advance —
                        # its one split happens on its final chunk; a
                        # row that ended on its first token keeps the
                        # prefill's key
                        key2[s] = self._key_data[s]
                    self._key_data = key2
                    for slot in decoding:
                        if slot in ended:
                            continue
                        token = int(nxt[slot])
                        self._tok[slot] = token
                        self._pos[slot] += 1
                        decode_tokens += 1
                        req = self._slot_req[slot]
                        if self.tracer is not None:
                            self.tracer.event(
                                req.trace_id, "decode",
                                token=token, pos=int(self._pos[slot]))
                        if self._append_token(req, token):
                            finished.append(self._retire(slot))
                    if self.kv_tier is not None:
                        self._decode_blocked_demotions += (
                            self.kv_tier.demotions - demo0)
        if self._firsts or self._moe_dev:
            # a step that decoded nothing reads what its prefills left
            _, firsts = self._fetch()
            with ph.phase("commit"):
                self._commit_firsts(firsts, finished)

        # 4. metrics — MoE families additionally drain the routing
        # stats their programs returned this step (per-expert demand,
        # capacity drops, router entropy) into the same ledger
        with ph.phase("commit"):
            moe_kw = self._drain_moe() if self._moe_on else {}
            tier = self.kv_tier
            self.metrics.record_step(
                running=len(self._active_slots()),
                waiting=len(self.scheduler.waiting),
                kv_blocks_used=self.pool.num_used,
                kv_blocks_total=self.pool.usable_blocks,
                kv_pool_bytes=self.pool.pool_bytes,
                kv_bytes_per_token=self.pool.bytes_per_token,
                weight_bytes=self.weight_bytes,
                weights_dtype=self.weights_dtype,
                prefill_tokens=prefill_tokens,
                decode_tokens=decode_tokens,
                prefix_hit_tokens=prefix_hit_tokens,
                spec_step=spec_step,
                draft_tokens=draft_tokens,
                accepted_draft_tokens=accepted_draft,
                prefill_chunks=prefill_chunks,
                kv_cache_evictions=self.pool.cache_evictions,
                kv_demotions=0 if tier is None else tier.demotions,
                kv_promotions=0 if tier is None else tier.promotions,
                kv_host_evictions=0 if tier is None else tier.evictions,
                host_hit_tokens=(0 if tier is None
                                 else tier.promoted_tokens),
                host_tier_bytes=0 if tier is None else tier.bytes_used,
                decode_blocked_demotions=self._decode_blocked_demotions,
                # the ledger's names; the ring's attrs take the held-
                # expert counts too
                **{k: v for k, v in moe_kw.items()
                   if k.startswith("moe_")})
        if self._ring_read and decoding:
            # known once the decode program is traced (the first step
            # that decoded, where there was no warm-up): every row of
            # the program reads its ring rounded up to the granule, at
            # least one granule
            g = self._ring_read
            trips = np.maximum(-(-ring_held // g), 1)
            window_attrs["window_read_rows"] = (
                int(trips.sum()) * g * self.family.window.n_layers)
        rec_t1 = ph.end()
        if self._recorder is not None:
            self._recorder.record(StepRecord(
                step=m.steps, t0=rec_t0, t1=rec_t1,
                running=m.running, waiting=m.waiting,
                decoding=len(decoding), prefilling=len(prefilling),
                admitted=m.admitted - rec_admitted0,
                finished=len(finished),
                preempted=m.preempted - rec_preempted0,
                kv_blocks_used=m.kv_blocks_used,
                kv_blocks_total=m.kv_blocks_total,
                prefill_tokens=prefill_tokens,
                decode_tokens=decode_tokens,
                prefix_hit_tokens=prefix_hit_tokens,
                prefill_chunks=prefill_chunks,
                spec_step=spec_step, draft_tokens=draft_tokens,
                accepted_draft_tokens=accepted_draft,
                phases=ph.seconds, host_syncs=ph.host_syncs,
                h2d_bytes=ph.h2d_bytes, context_tokens=context_tokens,
                state_bytes=state_bytes,
                attrs={**window_attrs,
                       # joined_rows: rows whose first token reached the
                       # decode on the device (the step's one read)
                       **({"attended_rows": attended_rows,
                           "joined_rows": joined_rows}
                          if decoding else {}),
                       # the step that recompiled, and what (the
                       # compile listener of obs/spans.py filled it)
                       **({"compiled": ph.compiled}
                          if ph.compiled else {}),
                       **{k: (v.tolist() if isinstance(v, np.ndarray)
                              else v) for k, v in moe_kw.items()}}))
        if self.log_every:
            self.metrics.log_step(self.logger, every=self.log_every)
        return finished

    def _warmup_calls(self):
        """(sentinel, arguments) of EVERY program — each prefill
        bucket, each decode program, each verify bucket — with all-zero
        block tables: every write scatters into the pool's null block.
        Lazy, so a caller that runs the programs (which donate the
        pool) finds the live pool in each next tuple."""
        zrow = jnp.zeros((self.table_width,), jnp.int32)
        lora_on = self.adapters is not None
        p_extra = self._lora_args("prefill", slot=0) if lora_on else ()
        # a recurrent or window family's warmup prefill writes its
        # per-slot buffers' null row (the one past the slots), as its
        # KV goes to block 0
        p_state = ((jnp.int32(self.max_slots),) if self._slot_buffers
                   else ())
        for b, sentinel in self._prefills.items():
            yield sentinel, (
                self.params, *self.pool.caches(),
                jnp.zeros((1, b), jnp.int32), jnp.int32(0), jnp.int32(1),
                zrow, jnp.int32(0), jnp.int32(0),
                jnp.asarray(jax.random.key_data(jax.random.key(0))),
                *p_state, *p_extra)
        for R, sentinel in self._decodes.items():
            extra = (self._lora_args("decode", rank_bucket=R)
                     if lora_on else ())
            yield sentinel, (
                self.params, *self.pool.caches(), jnp.asarray(self._tok),
                jnp.asarray(self._pos), jnp.asarray(self._tables),
                jnp.asarray(self._key_data), *extra)
        v_extra = self._lora_args("verify") if lora_on else ()
        for k, sentinel in self._verifies.items():
            yield sentinel, (
                self.params, *self.pool.caches(),
                jnp.zeros((self.max_slots, k + 1), jnp.int32),
                jnp.zeros((self.max_slots,), jnp.int32),
                jnp.zeros((self.max_slots,), jnp.int32),
                jnp.zeros((self.max_slots, self.table_width), jnp.int32),
                jnp.asarray(self._key_data), *v_extra)

    @setup_span("warmup")
    def warmup(self) -> None:
        """Compile EVERY prefill bucket and the decode step before
        serving traffic (benches call this so XLA compiles never land
        inside a timed window). Each program is invoked once with an
        all-zero block table — every write scatters into the pool's
        null block, the sampled tokens are discarded, and no request,
        slot, or metric state is touched. Sizing warmup *prompts* to
        hit each bucket cannot cover the largest bucket when
        ``prefill_len`` sits within the admission margin of the
        previous one; calling the programs directly can. The row-join
        (``serve_join_row``) is compiled here too, where it can run.

        The whole is a ``qn.setup.warmup`` span on the start-up record
        (obs/spans.py) and each program's call a child of it, from the
        call until it returns: what JAX traced, lowered and compiled or
        loaded for the program is charged to the child, and the rest of
        its time is the dispatch of a first run nobody waits for."""
        if self.adapters is not None:
            # compile the pack-maintenance program too (a zero write is
            # a no-op on the zeroed pack): the first real bind must not
            # be the first compile
            with warmup_program(self._pack_update.__name__):
                self._apply_pack_update(0, self._zero_slot_update())
        first = None
        for sentinel, args in self._warmup_calls():
            with warmup_program(sentinel.fn.__name__):
                *pools, tokens, keys = sentinel(*args)
            self.pool.update(*self._pop_moe(pools, note=False))
            first = first or (tokens, keys)     # a prefill's: it is first
        if self._join_on_device:
            self._warmup_join(*first)

    def _warmup_join(self, tok0, key2) -> None:
        """Compile the row-join, fed a prefill's first token and key as
        a step feeds it."""
        with warmup_program(self._join_row.__name__):
            self._join_row(jnp.asarray(self._tok),
                           jnp.asarray(self._key_data),
                           jnp.asarray(np.int32(0)), tok0, key2)

    def program_texts(self) -> List[str]:
        """The compiled text of every program (a second lowering: a
        load from the compile cache where it is warm). What
        obs/scopes.write_scope_maps wants beside an xplane, so that
        ``tools/trace_view.py --xplane`` can name device time by
        scope."""
        return [sentinel.fn.lower(*args).compile().as_text()
                for sentinel, args in self._warmup_calls()]

    def run(self, *, max_steps: Optional[int] = None) -> None:
        """Step until all submitted work is finished (or ``max_steps``)."""
        steps = 0
        while self.has_work:
            if max_steps is not None and steps >= max_steps:
                break
            self.step()
            steps += 1

    # ------------------------------------------------------------------
    # pause / drain / progress export (the fleet's migration surface)
    # ------------------------------------------------------------------
    @property
    def admissions_paused(self) -> bool:
        return self._admissions_paused

    def pause_admissions(self) -> None:
        """Stop admitting from the waiting queue; active slots keep
        decoding. NOTE: while paused, ``run()`` would spin if only
        waiting work remains (``has_work`` counts the queue) — pair
        pausing with :meth:`drain` / :meth:`step`, not ``run()``."""
        self._admissions_paused = True

    def resume_admissions(self) -> None:
        self._admissions_paused = False

    def drain(self, *, max_steps: Optional[int] = None) -> List[int]:
        """Finish the ACTIVE slots without admitting anything new:
        pause admissions and step until no slot is occupied. Waiting
        requests stay queued — export them (:meth:`export_progress`)
        for migration, or :meth:`resume_admissions` to keep serving.
        Returns the rids finished during the drain."""
        self.pause_admissions()
        finished: List[int] = []
        steps = 0
        while self._active_slots():
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"drain: {len(self._active_slots())} slot(s) still "
                    f"active after {max_steps} steps")
            finished.extend(self.step())
            steps += 1
        return finished

    def export_progress(self) -> List[RequestProgress]:
        """Snapshot every UNFINISHED request's host-side resume payload
        (running slots + waiting queue), in arrival order. For running
        slots the evolved PRNG key is checkpointed from the last
        completed step — the same state :meth:`_preempt` saves — so the
        export is exact at any step boundary, including after the
        owning worker died between steps (the fleet's kill-migration
        path). Read-only: the engine's own state is untouched."""
        now = self.clock()
        out: List[RequestProgress] = []
        for slot in self._active_slots():
            req = self._slot_req[slot]
            req.key_data = self._key_data[slot].copy()
            out.append(req.progress(now=now))
        for req in self.scheduler.waiting:
            out.append(req.progress(now=now))
        out.sort(key=lambda p: p.rid)
        if self.tracer is not None:
            for p in out:
                self.tracer.event(p.trace_id, "export",
                                  generated=len(p.generated),
                                  prefilled=int(p.prefilled))
        return out

    # ------------------------------------------------------------------
    # KV chain export/import — the disaggregated handoff surface
    # ------------------------------------------------------------------
    def export_kv_chain(self, tokens, *, namespace: Optional[str] = None,
                        trace_id: Optional[str] = None) -> Optional[Dict]:
        """The pool's published chain for ``tokens`` as host data
        (:meth:`KVPool.export_chain`) — what a prefill replica ships
        to a decode replica after a ``prefill_only`` retirement
        published the request's blocks. ``None`` when the chain is
        gone (evicted under pressure): the handoff caller falls back
        to local re-prefill, which is always correct — the chain is
        cache, not state."""
        if self._recurrent:
            _refuse_for_state(self.family, kv_chain=True)
        _refuse_for_window(self.family, kv_chain=True)
        chain = self.pool.export_chain(tokens, namespace=namespace)
        if self.tracer is not None:
            self.tracer.event(trace_id, "kv_export",
                              found=chain is not None,
                              n_tokens=(0 if chain is None
                                        else int(chain["n_tokens"])),
                              namespace=namespace)
        return chain

    def import_kv_chain(self, chain: Dict, *,
                        namespace: Optional[str] = None,
                        trace_id: Optional[str] = None) -> int:
        """Admit a transferred chain into this engine's pool as a warm
        prefix hit (:meth:`KVPool.import_chain`); the next admission
        for the prefix re-prefills ~1 token instead of the whole
        prompt. Returns positions now cached (0 = pool full or cache
        off — the caller re-prefills locally). Raises ``ValueError``
        on a geometry/policy mismatch: mixed engine specs in one
        fleet are a deployment error, not a retryable fault."""
        if self._recurrent:
            _refuse_for_state(self.family, kv_chain=True)
        _refuse_for_window(self.family, kv_chain=True)
        n = self.pool.import_chain(chain, namespace=namespace)
        if self.tracer is not None:
            self.tracer.event(trace_id, "kv_import",
                              n_tokens=int(n), namespace=namespace)
        return n

    # ------------------------------------------------------------------
    def compile_stats(self) -> Dict[str, int]:
        """Compiled-program counts for the bounded-compile invariant
        (tests/test_serve.py): ``decode`` must stay at 1 (adapter-blind
        engines) or at most ``len(lora_rank_buckets)`` (adapters armed
        — one program per rank bucket), ``prefill`` — the TOTAL across
        buckets — at most ``len(prefill_buckets)``, and (speculation
        on) ``verify`` at most ``len(spec.buckets)``, no matter how
        requests OR ADAPTERS come and go. Counted by the
        RecompileSentinels (distinct abstract signatures seen =
        programs jit compiled). The ``verify`` key appears only on
        spec-enabled engines — a spec-off engine's stats are
        byte-identical to the pre-speculation surface."""
        out = {"prefill": sum(s.compile_count
                              for s in self._prefills.values()),
               "decode": sum(s.compile_count
                             for s in self._decodes.values())}
        if self.spec is not None:
            out["verify"] = sum(s.compile_count
                                for s in self._verifies.values())
        return out

    def compile_sentinels(self) -> Dict[str, RecompileSentinel]:
        """The per-bucket prefill sentinels (``prefill[<width>]``), the
        per-bucket verify sentinels (``verify[<k>]``, spec-enabled
        engines only) and the decode sentinel(s) — one ``decode`` key
        for adapter-blind engines, ``decode[r<rank>]`` per rank bucket
        with adapters armed — for callers that aggregate the promise
        across engines (fleet.assert_compile_count)."""
        out: Dict[str, RecompileSentinel] = {
            f"prefill[{b}]": s for b, s in self._prefills.items()}
        for k, s in self._verifies.items():
            out[f"verify[{k}]"] = s
        if self.adapters is None:
            out["decode"] = self._decode
        else:
            for r, s in self._decodes.items():
                out[f"decode[r{r}]"] = s
        return out

    def compile_counts(self) -> Dict[str, int]:
        """Per-sentinel compile counts keyed like
        :meth:`compile_sentinels` — the JSON-able form that crosses a
        process boundary (the process fleet's stats frame,
        fleet/proc.py) so per-replica compile accounting survives the
        sentinels living in another address space."""
        return {k: s.compile_count
                for k, s in self.compile_sentinels().items()}

    def assert_compile_count(self, prefill: int = 1, decode: int = 1,
                             verify: Optional[int] = None):
        """Raise RecompileError unless exactly ``decode`` decode
        programs and ``prefill`` prefill programs IN TOTAL across the
        buckets were compiled (each bucket is additionally capped at
        one by its own sentinel at call time). ``verify``: exact total
        across the verify buckets; None accepts any total up to
        ``len(spec.buckets)`` — traffic legitimately decides which
        draft-length buckets ever trigger. With adapters armed,
        ``decode`` is the exact total across the RANK buckets the same
        way. Either way the global bound holds: programs <= prefill
        buckets + verify buckets + (1 decode per rank bucket)."""
        if self.adapters is None:
            self._decode.assert_compile_count(decode)
        else:
            d_total = sum(s.compile_count
                          for s in self._decodes.values())
            if d_total != decode:
                detail = ", ".join(
                    f"r{r}: {s.compile_count}"
                    for r, s in sorted(self._decodes.items()))
                raise RecompileError(
                    f"serve.decode: expected {decode} compiled "
                    f"rank-bucket program(s) in total, observed "
                    f"{d_total} ({detail})")
        total = sum(s.compile_count for s in self._prefills.values())
        if total != prefill:
            detail = ", ".join(
                f"bucket {b}: {s.compile_count}"
                for b, s in sorted(self._prefills.items()))
            raise RecompileError(
                f"serve.prefill: expected {prefill} compiled bucket "
                f"program(s) in total, observed {total} ({detail})")
        v_total = sum(s.compile_count for s in self._verifies.values())
        v_cap = verify if verify is not None else len(self._verifies)
        if (verify is not None and v_total != verify) or v_total > v_cap:
            detail = ", ".join(
                f"bucket {k}: {s.compile_count}"
                for k, s in sorted(self._verifies.items()))
            raise RecompileError(
                f"serve.verify: expected "
                f"{verify if verify is not None else f'<= {v_cap}'} "
                f"compiled bucket program(s) in total, observed "
                f"{v_total} ({detail})")
