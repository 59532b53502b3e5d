"""Serving benchmark: replay a synthetic request trace through the
continuous-batching engine (quintnet_tpu/serve/) and report
throughput + latency as ONE JSON line:

  {"metric": "serve_gpt2_tiny_tokens_per_sec", "value": N,
   "unit": "tok/s", "rc": 0, "extras": {"ttft_p50_s": ..,
   "ttft_p95_s": .., "peak_kv_utilization": .., ...}}

Arrivals are a Poisson process in ENGINE-STEP time (inter-arrival ~
Exp(rate)). Two trace shapes:

- default: prompt lengths uniform in [min_prompt, max_prompt] — the
  mixed-length staggered workload the one-shot batch decoders
  (models/gpt2_generate.py) cannot serve without padding everything to
  the longest request;
- ``--prefix-share``: N users x ONE shared system prompt
  (``--shared-prefix`` tokens) + short unique tails — the
  real-traffic shape (system prompts, few-shot templates) the prefix
  cache exists for. This mode replays the SAME trace through a
  cache-ON and a cache-OFF engine and reports both: the record's value
  is cache-on tok/s, ``extras`` carries the cache-off numbers, the
  speedup, and the hit rate;
- ``--spec-trace``: repetitive prompts (each a short random pattern
  tiled to length — templated/greedy-friendly text) where n-gram
  self-drafting should accept long drafts. Replays the SAME trace
  through a speculation-ON and a speculation-OFF engine (both greedy)
  and reports both: the record's value is spec-on tok/s, ``extras``
  carries the spec-off numbers, the speedup, the draft acceptance
  rate and ``tokens_per_decode_step`` — the committed-tokens-per-
  program-invocation number that makes the speculation win legible
  without reading raw metrics;
- ``--long-trace``: the short Poisson mix PLUS ``--long-prompts``
  document-length prompts (``--long-prompt`` tokens — longer than the
  chunked engine's whole prefill window) arriving mid-decode. Replays
  the SAME trace through a chunked-prefill engine
  (``chunked_prefill=True``, per-step ``--chunk-budget``) and the
  stall-prone monolithic baseline (prefill window widened to swallow
  the prompt in one program call). The headline comparison is decode
  tok/s DURING the long-prefill window — how fast everyone else's
  streams move while a document is read in — plus inter-token-latency
  tails (a monolithic prefill appears as one giant gap in every
  concurrent stream);
- ``--kv-capacity``: the EQUAL-POOL-BYTES capacity A/B (quantized KV,
  serve/kv_quant.py) over the ``--prefix-share`` trace shape: side A
  is an f32 pool at ``--num-blocks``; side B is the ``--kv-dtype``
  (int8 unless set otherwise) pool given exactly the SAME byte
  budget — which buys it ~4x the blocks. Capacity is concurrency:
  the record's value is the quantized side's tok/s, ``vs_baseline``
  the usable-blocks ratio at equal bytes, and extras carry the
  structural evidence (preemptions, cache evictions, hit rates, peak
  utilization, both pools' bytes);
- ``--tier-trace``: the tiered-KV A/B (serve/kv_tier.py) over a
  MANY-TENANT prefix set sized ``--tier-prefix-ratio`` x the usable
  device pool (``--tier-prefixes`` distinct system prompts visited
  round-robin with unique tails, ``--tier-repeats`` visits each): by
  the time a prefix is revisited its chain has been LRU-evicted from
  the device pool, so side A (host tier armed, ``--tier-bytes``)
  demotes on eviction and re-promotes on the host-hit while side B
  (evict-only: the identical engine, tier off) re-prefills from
  scratch. The record's value is tiered tok/s, ``vs_baseline`` the
  tok/s ratio, and extras carry the gates: warm hit rate vs the
  evict-only hit rate, TTFT both sides, the tier ledger
  (demotions/promotions/host bytes/host evictions), and the
  structural ``decode_blocked_demotions == 0`` — demotion copies
  never stall a decode step;
- ``--lora-trace``: N tenants spread round-robin over ``--adapters``
  LoRA adapters (trained variants of one base model, saved through
  the real safetensors path) — the multi-tenant scenario
  serve/adapters.py exists for. The A side serves the WHOLE mixed
  trace through ONE multi-LoRA engine (heterogeneous adapters batched
  into shared decode steps); the B side is the merged-weight
  baseline: one DEDICATED engine per adapter serving only its
  tenant's requests, walls summed — what multi-tenancy costs without
  adapter batching. The record's value is multi-LoRA tok/s; extras
  carry the merged totals, the speedup, and the structural signal
  ``merged_decode_steps / decode_steps`` (shared steps do the work of
  many dedicated ones, independent of wall-clock noise).

Every mode's extras carry ``decode_steps`` and
``tokens_per_decode_step`` (decode_tokens / decode_steps).

- ``--obs-ab``: the observability overhead A/B (quintnet_tpu/obs/):
  the SAME default Poisson trace replayed through an engine with the
  flight recorder armed (per-request Tracer + per-step StepRecorder)
  and one without. Observation is contractually inert on tokens
  (bit-identity is pinned in tests/test_obs.py); this mode prices the
  host-side overhead — the record's value is obs-on tok/s,
  ``vs_baseline`` the on/off ratio (the committed artifact gates it
  >= 0.95), and extras carry the trace summary (spans, ring depth).
  ``--trace-out FILE`` additionally writes the obs-on replay's ring +
  spans as Chrome trace-event JSON loadable in Perfetto
  (tools/trace_view.py renders; also accepted standalone with the
  default trace).

Modes:
  python tools/serve_bench.py --synthetic              # tiny cfg, CPU-ok
  python tools/serve_bench.py --synthetic --model llama
  python tools/serve_bench.py --synthetic --prefix-share
  python tools/serve_bench.py --synthetic --prefix-cache off   # A/B
  python tools/serve_bench.py --synthetic --spec-trace         # A/B
  python tools/serve_bench.py --synthetic --long-trace         # A/B
  python tools/serve_bench.py --synthetic --spec on    # default trace
  python tools/serve_bench.py --model gpt2             # 124M random init
  python tools/serve_bench.py --synthetic --steps 3    # smoke (CI runs
      this — tests/test_serve_bench.py — so the CLI can never rot)

``--steps N`` caps the engine-step budget (unfinished requests are
reported, not an error); default runs the trace to completion.
``--out FILE`` appends the record to an artifacts JSON list the same
way bench.py artifacts are kept (bench.last_known_result scans them —
the serve bench gets the same staleness story as the training bench).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_model(args, params=None):
    """(family, params) for the bench config — separate from
    build_engine so the --lora-trace branch can materialise the base
    params ONCE (for adapter construction and merged baselines)
    without allocating a throwaway engine's KV pool."""
    import jax

    from quintnet_tpu.serve import gpt2_family, llama_family

    # synthetic-config overrides (--n-layer & co): the default tiny
    # model is too small for prefill compute to matter — the
    # prefix-share acceptance run uses a taller/wider synthetic config
    # so the cached-vs-recomputed prefill difference is the signal
    syn_kw = {k: v for k, v in (
        ("n_layer", args.n_layer), ("n_embd", args.n_embd),
        ("n_head", args.n_head), ("n_positions", args.n_positions),
        ("vocab_size", args.vocab_size)) if v is not None}
    if getattr(args, "experts", 0):
        # --experts N makes the synthetic config an MoE one (both
        # config families carry the same field names)
        syn_kw.update(n_experts=args.experts,
                      expert_top_k=args.expert_top_k,
                      capacity_factor=args.capacity_factor,
                      expert_capacity=args.expert_capacity)
    if args.model == "gpt2":
        from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init

        cfg = (GPT2Config.tiny(**{"n_layer": 2, **syn_kw})
               if args.synthetic else GPT2Config.base())
        if params is None:
            params = gpt2_init(jax.random.key(args.seed), cfg)
        family = gpt2_family(cfg)
    elif args.model == "llama":
        from quintnet_tpu.models.llama import LlamaConfig, llama_init

        lkw = {{"n_layer": "n_layers", "n_embd": "dim",
                "n_head": "n_heads", "n_positions": "n_positions",
                "vocab_size": "vocab_size",
                "n_experts": "n_experts", "expert_top_k": "expert_top_k",
                "capacity_factor": "capacity_factor",
                "expert_capacity": "expert_capacity"}[k]: v
               for k, v in syn_kw.items()}
        cfg = (LlamaConfig.tiny(**{"n_layers": 2, **lkw})
               if args.synthetic else LlamaConfig())
        if params is None:
            params = llama_init(jax.random.key(args.seed), cfg)
        family = llama_family(cfg)
    else:
        raise SystemExit(f"unknown --model {args.model}")
    return family, params


def build_engine(args, *, prefix_cache: bool, spec: bool = False,
                 params=None, adapters=None, max_seq=None,
                 prefill_len=None, chunked_prefill: bool = False,
                 prefill_chunk_budget=None, kv_dtype=None,
                 num_blocks=None, attn_kernel=None,
                 kv_tier_bytes: int = 0,
                 kv_tier_promote_budget_bytes=None,
                 weights_dtype=None):
    from quintnet_tpu.serve import ServeEngine, SpecConfig

    family, params = build_model(args, params=params)
    max_prompt = (args.shared_prefix + args.max_tail
                  if args.prefix_share or args.kv_capacity
                  or args.tier_trace
                  else args.max_prompt)
    if max_seq is None:
        max_seq = min(max_prompt + args.max_new, family.max_positions)
    return ServeEngine(
        family, params, max_slots=args.slots, block_size=args.block_size,
        num_blocks=(num_blocks if num_blocks is not None
                    else args.num_blocks),
        max_seq_len=max_seq,
        prefill_len=prefill_len, chunked_prefill=chunked_prefill,
        prefill_chunk_budget=prefill_chunk_budget,
        eos_token_id=args.eos, temperature=args.temperature,
        policy=args.policy, prefix_cache=prefix_cache,
        kv_dtype=kv_dtype if kv_dtype is not None else args.kv_dtype,
        weights_dtype=(weights_dtype if weights_dtype is not None
                       else args.weights_dtype),
        attn_kernel=(attn_kernel if attn_kernel is not None
                     else args.kernel),
        spec=SpecConfig(max_draft=args.max_draft) if spec else None,
        adapters=adapters, lora_max_rank=args.lora_rank,
        kv_tier_bytes=kv_tier_bytes,
        kv_tier_promote_budget_bytes=kv_tier_promote_budget_bytes)


def poisson_arrivals(rng, n: int, rate: float):
    t, out = 0.0, []
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        out.append(int(t))
    return out


def poisson_trace(args, vocab_size: int):
    """[(arrival_step, prompt, max_new)] sorted by arrival."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    arrivals = poisson_arrivals(rng, args.requests, args.rate)
    trace = []
    for t in arrivals:
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        prompt = rng.integers(0, vocab_size, (n,)).astype(np.int32)
        trace.append((t, prompt, args.max_new))
    return trace


def repetitive_trace(args, vocab_size: int):
    """Greedy-friendly prompts for the speculation A/B. ``--pattern N``
    tiles a short random per-request pattern to the sampled prompt
    length (templated/repetitive text); ``--pattern 0`` keeps prompts
    random — with greedy sampling the draftable repetition then comes
    from the CONTINUATIONS (greedy decoding settles into repetitive
    runs/cycles, which is exactly the structure prompt-lookup drafts
    from — long ``--max-new`` lets that phase dominate)."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    arrivals = poisson_arrivals(rng, args.requests, args.rate)
    trace = []
    for t in arrivals:
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        if args.pattern > 0:
            pat = rng.integers(0, vocab_size,
                               (args.pattern,)).astype(np.int32)
            prompt = np.tile(pat, -(-n // args.pattern))[:n]
        else:
            prompt = rng.integers(0, vocab_size, (n,)).astype(np.int32)
        trace.append((t, prompt, args.max_new))
    return trace


def prefix_share_trace(args, vocab_size: int):
    """N users x one shared system prompt + short unique tails."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, vocab_size,
                          (args.shared_prefix,)).astype(np.int32)
    arrivals = poisson_arrivals(rng, args.requests, args.rate)
    trace = []
    for t in arrivals:
        n = int(rng.integers(args.min_tail, args.max_tail + 1))
        tail = rng.integers(0, vocab_size, (n,)).astype(np.int32)
        trace.append((t, np.concatenate([shared, tail]), args.max_new))
    return trace


def tier_trace_gen(args, vocab_size: int):
    """MANY-TENANT prefix churn for the tiered-KV A/B: P distinct
    system prompts visited round-robin with unique tails,
    ``--tier-repeats`` visits each. P is sized so the prefix set
    costs ``--tier-prefix-ratio`` x the usable device pool (or pinned
    by ``--tier-prefixes``) — the revisit gap is P whole prefixes, so
    by the time prefix j comes around again the device LRU has
    destroyed its chain: the tiered engine serves the revisit from
    host RAM, the evict-only engine re-prefills from scratch.
    Resolves ``args.tier_prefixes`` to the chosen P as a side effect
    so the run() branch can report it. [(t, prompt, max_new)]"""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    blocks_per_prefix = -(-args.shared_prefix // args.block_size)
    usable = max(args.num_blocks - 1, 1)  # minus the reserved null
    if not args.tier_prefixes:
        args.tier_prefixes = max(2, round(
            args.tier_prefix_ratio * usable / blocks_per_prefix))
    prefixes = [rng.integers(0, vocab_size,
                             (args.shared_prefix,)).astype(np.int32)
                for _ in range(args.tier_prefixes)]
    n_requests = args.tier_repeats * args.tier_prefixes
    arrivals = poisson_arrivals(rng, n_requests, args.rate)
    trace = []
    for j, t in enumerate(arrivals):
        n = int(rng.integers(args.min_tail, args.max_tail + 1))
        tail = rng.integers(0, vocab_size, (n,)).astype(np.int32)
        trace.append(
            (t, np.concatenate([prefixes[j % args.tier_prefixes], tail]),
             args.max_new))
    return trace


def hot_expert_trace(args, vocab_size: int):
    """Skewed-routing traffic for the ``--moe-trace`` A/B: every
    request tiles the SAME short token pattern to its sampled prompt
    length, so the router scores the same few hidden states over and
    over — routed demand concentrates on that pattern's favourite
    experts (and the greedy continuations settle into repetitive
    cycles, concentrating decode-time routing the same way). The
    diverse side of the A/B is the plain Poisson trace: random
    prompts spread demand across the expert set."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    pat = rng.integers(0, vocab_size,
                       (max(args.pattern, 1),)).astype(np.int32)
    arrivals = poisson_arrivals(rng, args.requests, args.rate)
    trace = []
    for t in arrivals:
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        trace.append((t, np.tile(pat, -(-n // len(pat)))[:n],
                      args.max_new))
    return trace


def long_trace(args, vocab_size: int):
    """The decode-starvation workload: the default short Poisson mix
    PLUS ``--long-prompts`` document-length prompts arriving while the
    shorts are mid-decode. Entries are (t, prompt, max_new, is_long) —
    the replayer uses the flag to carve out the window during which a
    long prompt is being prefilled (that window is where a monolithic
    prefill stalls every concurrent stream and a chunked one does
    not)."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    trace = [(t, p, m, False)
             for (t, p, m) in poisson_trace(args, vocab_size)]
    for i in range(args.long_prompts):
        t = 2 + i * args.long_spacing
        p = rng.integers(0, vocab_size,
                         (args.long_prompt,)).astype(np.int32)
        trace.append((t, p, args.max_new, True))
    return sorted(trace, key=lambda e: e[0])


def replay_long(engine, trace, args) -> dict:
    """Like :func:`replay`, but per-step instrumented: wall time and
    decode tokens are additionally accumulated over the steps during
    which some long prompt is submitted but has not yet produced its
    first token — the long-prefill window. ``decode tokens / window
    wall`` is the number the chunked-vs-monolithic A/B is about:
    how fast everyone ELSE's streams move while a document is being
    read in. Each step blocks on the pool before reading the clock so
    the per-step wall measures device work, not dispatch."""
    import time

    import jax

    engine.warmup()
    engine.metrics = type(engine.metrics)(clock=engine.clock)

    submitted = 0
    step = 0
    long_rids = []
    win_wall = 0.0
    win_decode = 0
    t0 = time.perf_counter()
    while submitted < len(trace) or engine.has_work:
        if args.steps is not None and step >= args.steps:
            break
        while submitted < len(trace) and trace[submitted][0] <= step:
            _, prompt, max_new, is_long = trace[submitted]
            rid = engine.submit(prompt, max_new)
            if is_long:
                long_rids.append(rid)
            submitted += 1
        in_window = any(
            engine.request(r).first_token_time is None
            for r in long_rids)
        d0 = engine.metrics.decode_tokens
        s0 = time.perf_counter()
        engine.step()
        jax.block_until_ready(engine.pool.caches())
        dt = time.perf_counter() - s0
        if in_window:
            win_wall += dt
            win_decode += engine.metrics.decode_tokens - d0
        step += 1
    # every step blocked on the pool above; drain once more so the
    # whole-replay wall measures device work, not dispatch (QT106)
    jax.block_until_ready(engine.pool.caches())
    wall = time.perf_counter() - t0

    s = engine.metrics.summary()
    s["wall_s"] = round(wall, 4)
    s["tokens_per_sec"] = (round(s["gen_tokens"] / wall, 2) if wall > 0
                           else 0.0)
    s["submitted"] = submitted
    s["long_window_wall_s"] = round(win_wall, 4)
    s["long_window_decode_tokens"] = win_decode
    s["decode_tps_during_long_prefill"] = (
        round(win_decode / win_wall, 2) if win_wall > 0 else 0.0)
    return s


def lora_trace(args, vocab_size: int):
    """The default Poisson trace with each request bound round-robin
    to one of ``--adapters`` tenants: [(t, prompt, max_new, aid)]."""
    trace = poisson_trace(args, vocab_size)
    return [(t, p, m, f"tenant-{i % args.adapters}")
            for i, (t, p, m) in enumerate(trace)]


def make_adapters(args, params, tmpdir: str):
    """--adapters trained LoRA variants of the base model, each saved
    through the real safetensors path (the registry's input contract).
    Returns {adapter_id: (merged_params, path)} — merged weights feed
    the dedicated-baseline engines."""
    import os

    import jax
    import numpy as np

    from quintnet_tpu.models.lora import (LoRAConfig, lora_init,
                                          lora_merge_tree, save_lora)

    out = {}
    for i in range(args.adapters):
        cfg = LoRAConfig(rank=args.lora_rank, alpha=2.0 * args.lora_rank)
        lora = lora_init(jax.random.key(1000 + i), params["blocks"], cfg)
        lora = jax.tree.map(
            lambda l, s=i: l + 0.02 * jax.random.normal(
                jax.random.key(2000 + s), l.shape), lora)
        path = os.path.join(tmpdir, f"tenant-{i}.safetensors")
        save_lora(lora, cfg, path)
        out[f"tenant-{i}"] = (lora_merge_tree(params, lora, cfg), path)
    return out


def replay(engine, trace, args) -> dict:
    """Warm up (compile EVERY prefill bucket + the decode step OUTSIDE
    the timed window — engine.warmup() invokes each program against
    the null block directly, so no bucket can be missed), reset the
    ledgers, replay the trace, return the summary with a
    device-drained wall clock."""
    import time

    import jax

    engine.warmup()
    engine.metrics = type(engine.metrics)(clock=engine.clock)

    submitted = 0
    step = 0
    t0 = time.perf_counter()
    while submitted < len(trace) or engine.has_work:
        if args.steps is not None and step >= args.steps:
            break
        while submitted < len(trace) and trace[submitted][0] <= step:
            _, prompt, max_new, *rest = trace[submitted]
            # --lora-trace entries carry the tenant binding as a 4th
            # element (None rides the base model)
            engine.submit(prompt, max_new,
                          adapter_id=rest[0] if rest else None)
            submitted += 1
        engine.step()
        step += 1
    # the throughput wall clock must cover DEVICE work, not dispatch:
    # drain the in-flight pool writes before reading the clock (the
    # metrics' own wall starts at the first step's completion, which
    # also silently excluded the first prefill+decode from the window)
    jax.block_until_ready(engine.pool.caches())
    wall = time.perf_counter() - t0

    s = engine.metrics.summary()
    s["wall_s"] = round(wall, 4)
    s["tokens_per_sec"] = (round(s["gen_tokens"] / wall, 2) if wall > 0
                           else 0.0)
    s["submitted"] = submitted
    return s


def _common_extras(args, s: dict) -> dict:
    return {
        "ttft_p50_s": s["ttft_s"]["p50"],
        "ttft_p95_s": s["ttft_s"]["p95"],
        "latency_p50_s": s["latency_s"]["p50"],
        "latency_p95_s": s["latency_s"]["p95"],
        "peak_kv_utilization": s["peak_kv_utilization"],
        "kv_pool_bytes": s["kv_pool_bytes"],
        "kv_bytes_per_token": s["kv_bytes_per_token"],
        "peak_running": s["peak_running"],
        "steps": s["steps"],
        "requests": args.requests,
        "submitted": s["submitted"],
        "finished": s["finished"],
        "preempted": s["preempted"],
        "decode_tokens": s["decode_tokens"],
        "prefill_tokens": s["prefill_tokens"],
        "prefix_hit_tokens": s["prefix_hit_tokens"],
        "prefill_tokens_saved": s["prefill_tokens_saved"],
        "prefix_hit_rate": s["prefix_hit_rate"],
        "gen_tokens": s["gen_tokens"],
        "decode_steps": s["decode_steps"],
        "tokens_per_decode_step": s["tokens_per_decode_step"],
        "wall_s": s["wall_s"],
        "model": args.model,
        "synthetic": bool(args.synthetic),
        "slots": args.slots,
        "block_size": args.block_size,
        "num_blocks": args.num_blocks,
        "rate": args.rate,
    }


def _arm_obs(engine, ring_capacity: int = 4096):
    """Attach the flight recorder to a bench engine; returns
    (tracer, recorder)."""
    from quintnet_tpu.obs import StepRecorder, Tracer

    engine.tracer = Tracer(clock=engine.clock, max_traces=4096)
    engine.recorder = StepRecorder(capacity=ring_capacity,
                                   clock=engine.clock)
    return engine.tracer, engine.recorder


def _write_trace_out(path: str, tracer, recorder) -> dict:
    """Write the replay's ring + spans as validated Chrome trace-event
    JSON (Perfetto-loadable); returns the trace summary extras."""
    import json as _json

    from tools.trace_view import chrome_trace, validate_chrome_trace

    ring = recorder.snapshot()
    traces = tracer.snapshot()
    trace = chrome_trace(ring, traces, label="serve_bench")
    n_events = validate_chrome_trace(trace)
    with open(path, "w") as f:
        _json.dump(trace, f)
    return {"trace_out": path, "trace_events": n_events}


def _obs_summary(tracer, recorder) -> dict:
    snap = tracer.snapshot()
    return {
        "obs_traces": len(snap),
        "obs_spans": sum(len(v) for v in snap.values()),
        "obs_ring_steps": len(recorder),
        "obs_ring_total": recorder.total,
    }


def run(args) -> dict:
    tag = "tiny" if args.synthetic else "full"

    if args.obs_ab:
        # observability overhead A/B over the SAME default trace:
        # flight recorder armed vs off. Tokens are contractually
        # bit-identical either way (tests/test_obs.py); what this
        # prices is the host-side span/ring bookkeeping.
        prefix_cache = args.prefix_cache == "on"
        # a throwaway UNTIMED replay first: process-level warm-up
        # (first-touch jit plumbing, allocator growth) is several
        # times the effect being measured and would otherwise be
        # charged entirely to whichever side runs first. After it,
        # obs-on is timed before obs-off — any residual ordering
        # advantage goes to the OFF side, keeping the committed
        # >= 0.95 ratio conservative.
        eng_warm = build_engine(args, prefix_cache=prefix_cache)
        trace = poisson_trace(args, eng_warm.family.cfg.vocab_size)
        replay(eng_warm, trace, args)
        del eng_warm
        eng_on = build_engine(args, prefix_cache=prefix_cache)
        tracer, recorder = _arm_obs(eng_on)
        s_on = replay(eng_on, trace, args)
        eng_off = build_engine(args, prefix_cache=prefix_cache)
        s_off = replay(eng_off, trace, args)
        extras = _common_extras(args, s_on)
        extras.update(_obs_summary(tracer, recorder))
        ratio = (round(s_on["tokens_per_sec"]
                       / s_off["tokens_per_sec"], 3)
                 if s_off["tokens_per_sec"] else 0.0)
        extras.update({
            "obs_ab": True,
            "obs_off_tokens_per_sec": s_off["tokens_per_sec"],
            "obs_off_wall_s": s_off["wall_s"],
            "obs_off_gen_tokens": s_off["gen_tokens"],
            # the overhead gate: obs-on throughput / obs-off (the
            # committed artifact pins >= 0.95)
            "obs_on_ratio": ratio,
        })
        if args.trace_out:
            extras.update(_write_trace_out(args.trace_out, tracer,
                                           recorder))
        return {
            "metric": f"serve_{args.model}_{tag}_obs_tokens_per_sec",
            "value": s_on["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": ratio,
            "rc": 0,
            "extras": extras,
        }

    if args.kernel_ab:
        # fused-kernel A/B over the SAME default trace. Two committed
        # signals, both wall-noise-free: (1) every request's token
        # stream is IDENTICAL across backends (the kernel is
        # bit-parity-pinned against the gathered-view oracle), and
        # (2) the jaxpr auditor proves the pallas programs issue ZERO
        # full-row block-table gathers where the xla ones issue 2 (4
        # under a scaled KV policy) per layer — the structural
        # HBM-traffic win the kernel exists for. CPU wall clocks ride
        # along for the record but are NOT gated: off-TPU the kernel
        # runs in the Pallas interpreter, which prices emulation.
        import jax.numpy as _jnp

        from quintnet_tpu.analysis import gathered_view_gathers

        prefix_cache = args.prefix_cache == "on"
        spec = args.spec == "on"
        eng_warm = build_engine(args, prefix_cache=prefix_cache,
                                spec=spec, attn_kernel="xla")
        trace = poisson_trace(args, eng_warm.family.cfg.vocab_size)
        replay(eng_warm, trace, args)   # process warm-up, untimed
        del eng_warm
        eng_p = build_engine(args, prefix_cache=prefix_cache,
                             spec=spec, attn_kernel="pallas")
        s_p = replay(eng_p, trace, args)
        eng_x = build_engine(args, prefix_cache=prefix_cache,
                             spec=spec, attn_kernel="xla")
        s_x = replay(eng_x, trace, args)
        # token-identity is THE signal this mode exists to report, so
        # a divergence (different lengths, an unfinished or errored
        # request on one side) must come back as token_identical=false
        # with a count — never a traceback
        n = min(s_p["finished"], s_x["finished"])
        mismatched = 0
        for r in range(n):
            try:
                a, b = eng_p.result(r), eng_x.result(r)
            except Exception:
                mismatched += 1
                continue
            if a.shape != b.shape or not (a == b).all():
                mismatched += 1
        token_identical = (n == len(trace) and mismatched == 0)

        def _gathers(eng):
            caches = eng.pool.caches()
            dargs = (eng.params, *caches, _jnp.asarray(eng._tok),
                     _jnp.asarray(eng._pos), _jnp.asarray(eng._tables),
                     _jnp.asarray(eng._key_data))
            return gathered_view_gathers(
                eng._decode.fn, *dargs,
                num_blocks=eng.pool.num_blocks,
                table_width=eng.table_width)

        gx, gp = _gathers(eng_x), _gathers(eng_p)
        extras = _common_extras(args, s_p)
        ratio = (round(s_p["tokens_per_sec"] / s_x["tokens_per_sec"], 3)
                 if s_x["tokens_per_sec"] else 0.0)
        extras.update({
            "kernel_ab": True,
            "attn_kernel": "pallas",
            "kv_dtype": args.kv_dtype,
            "token_identical": bool(token_identical),
            "compared_requests": int(n),
            "mismatched_requests": int(mismatched),
            # THE structural gate (CI-pinned): full-row block-table
            # gathers per decode program
            "xla_gathered_view_gathers": int(gx),
            "pallas_gathered_view_gathers": int(gp),
            "xla_tokens_per_sec": s_x["tokens_per_sec"],
            "xla_wall_s": s_x["wall_s"],
            "xla_finished": s_x["finished"],
            "cpu_interpret_mode": _paged_kernel_module().INTERPRET,
            "speedup_vs_xla": ratio,
        })
        return {
            "metric": f"serve_{args.model}_{tag}_kernel_tokens_per_sec",
            "value": s_p["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": ratio,
            "rc": 0,
            "extras": extras,
        }

    if args.kv_capacity:
        # equal-pool-BYTES capacity A/B over the shared-prefix trace
        # (quantized KV, serve/kv_quant.py): the f32 reference keeps
        # --num-blocks; the --kv-dtype side gets every block the SAME
        # byte budget buys (int8 blocks cost ~1/4, so ~4x blocks).
        # Capacity is concurrency: at equal bytes the quantized pool
        # should admit without preempting and retain the shared-prefix
        # chain (higher hit rate) where the f32 pool thrashes.
        from quintnet_tpu.serve.kv_quant import make_policy

        family, params = build_model(args)
        dims = dict(n_layers=family.n_layers,
                    n_kv_heads=family.n_kv_heads,
                    head_dim=family.head_dim, block_size=args.block_size)
        q_name = args.kv_dtype if args.kv_dtype != "f32" else "int8"
        byte_budget = args.num_blocks * make_policy(
            "f32").bytes_per_block(**dims)
        q_blocks = byte_budget // make_policy(q_name).bytes_per_block(
            **dims)
        eng_f = build_engine(args, prefix_cache=True, params=params,
                             kv_dtype="f32")
        trace = prefix_share_trace(args, eng_f.family.cfg.vocab_size)
        s_f = replay(eng_f, trace, args)
        eng_q = build_engine(args, prefix_cache=True, params=params,
                             kv_dtype=q_name, num_blocks=int(q_blocks))
        s_q = replay(eng_q, trace, args)
        extras = _common_extras(args, s_q)
        ratio = round((q_blocks - 1) / max(args.num_blocks - 1, 1), 3)
        extras.update({
            "kv_capacity": True,
            "kv_dtype": q_name,
            "shared_prefix": args.shared_prefix,
            "pool_bytes_budget": int(byte_budget),
            "f32_num_blocks": args.num_blocks,
            "q_num_blocks": int(q_blocks),
            "f32_usable_blocks": args.num_blocks - 1,
            "q_usable_blocks": int(q_blocks) - 1,
            # THE equal-bytes capacity signal (usable = minus the
            # reserved null block)
            "usable_blocks_ratio": ratio,
            "f32_pool_bytes": s_f["kv_pool_bytes"],
            "q_pool_bytes": s_q["kv_pool_bytes"],
            "q_kv_bytes_per_token": s_q["kv_bytes_per_token"],
            "f32_kv_bytes_per_token": s_f["kv_bytes_per_token"],
            "f32_tokens_per_sec": s_f["tokens_per_sec"],
            "f32_wall_s": s_f["wall_s"],
            # the structural win at equal bytes: fewer preemptions,
            # fewer cache evictions, higher hit rate, lower peak
            # pressure — concurrency the f32 pool could not hold
            "f32_preempted": s_f["preempted"],
            "q_preempted": s_q["preempted"],
            # NOTE hit-rate/prefill comparisons are confounded under
            # pressure, in BOTH directions: an f32 preemption-resume
            # re-prefills through its own published chain (extra
            # booked hits), and the starved f32 queue serializes
            # admissions until retired requests have PUBLISHED the
            # shared chain (late admission sees a warmer cache, while
            # the quantized side's higher concurrency admits before
            # the first publish). The unconfounded cache-retention
            # signal is the EVICTION count: evicted chains are future
            # hits destroyed, and only the starved pool evicts.
            "f32_prefix_hit_rate": s_f["prefix_hit_rate"],
            "q_prefix_hit_rate": s_q["prefix_hit_rate"],
            "f32_prefill_tokens": s_f["prefill_tokens"],
            "f32_prefix_hit_tokens": s_f["prefix_hit_tokens"],
            "f32_cache_evictions": eng_f.pool.cache_evictions,
            "q_cache_evictions": eng_q.pool.cache_evictions,
            "f32_peak_kv_utilization": s_f["peak_kv_utilization"],
            "q_peak_kv_utilization": s_q["peak_kv_utilization"],
            "f32_peak_running": s_f["peak_running"],
            "q_peak_running": s_q["peak_running"],
            "f32_finished": s_f["finished"],
        })
        return {
            "metric": f"serve_{args.model}_{tag}_kvcap_tokens_per_sec",
            "value": s_q["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": ratio,
            "rc": 0,
            "extras": extras,
        }

    if args.weights_ab:
        # weight-quant A/B (serve/weight_quant.py) over the SAME
        # default Poisson trace: f32 weights vs the --weights-dtype
        # packed side, everything else equal (same init, same KV pool,
        # same arrivals). Decode at serving batch sizes is
        # weight-bandwidth-bound, so the committed signals are
        # STRUCTURAL: the targeted-node byte ratio (~3.9x for int8
        # before the per-channel-scale overhead) and the paged
        # teacher-forced NLL delta under original vs packed params —
        # CPU walls are recorded but never the gate (off-TPU the
        # bandwidth saving prices emulation, not the policy).
        import jax as _jax
        import numpy as np

        from quintnet_tpu.serve.kv_pool import KVPool
        from quintnet_tpu.serve.kv_quant import paged_eval_nll
        from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                     present_targets,
                                                     quantize_params)

        family, params = build_model(args)
        q_name = (args.weights_dtype if args.weights_dtype != "f32"
                  else "int8")
        prefix_cache = args.prefix_cache == "on"
        eng_f = build_engine(args, prefix_cache=prefix_cache,
                             params=params, weights_dtype="f32")
        trace = poisson_trace(args, eng_f.family.cfg.vocab_size)
        s_f = replay(eng_f, trace, args)
        eng_q = build_engine(args, prefix_cache=prefix_cache,
                             params=params, weights_dtype=q_name)
        s_q = replay(eng_q, trace, args)

        # quality: the SAME held-out rows scored through a fresh f32
        # KV pool under both param trees — the delta isolates the
        # weight rounding (KV layout held fixed)
        rng = np.random.default_rng(args.seed + 1)
        rows = rng.integers(
            0, family.cfg.vocab_size,
            (4, min(24, family.max_positions - 1))).astype(np.int32)
        targets = present_targets(params, family.weight_targets)
        qparams = quantize_params(params, targets,
                                  make_weight_policy(q_name))

        def _fresh_pool():
            return KVPool(n_layers=family.n_layers,
                          n_kv_heads=family.n_kv_heads,
                          head_dim=family.head_dim,
                          block_size=args.block_size,
                          num_blocks=args.num_blocks)

        nll_f = paged_eval_nll(family, params, _fresh_pool(), rows)
        nll_q = paged_eval_nll(family, qparams, _fresh_pool(), rows)

        extras = _common_extras(args, s_q)
        ratio = (round(eng_f.weight_bytes / eng_q.weight_bytes, 3)
                 if eng_q.weight_bytes else 0.0)
        extras.update({
            "weights_ab": True,
            "weights_dtype": q_name,
            "f32_weight_bytes": int(eng_f.weight_bytes),
            "q_weight_bytes": int(eng_q.weight_bytes),
            # THE structural gate (CI-pinned): targeted-node bytes,
            # f32 over packed — scale overhead included
            "weight_bytes_ratio": ratio,
            "eval_nll_f32": round(float(nll_f), 6),
            "eval_nll_q": round(float(nll_q), 6),
            "eval_nll_delta": round(float(nll_q - nll_f), 6),
            "f32_tokens_per_sec": s_f["tokens_per_sec"],
            "f32_wall_s": s_f["wall_s"],
            "f32_finished": s_f["finished"],
            "cpu_wall_not_gated": _jax.default_backend() != "tpu",
        })
        return {
            "metric": (f"serve_{args.model}_{tag}"
                       "_weights_tokens_per_sec"),
            "value": s_q["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": (round(s_q["tokens_per_sec"]
                                  / s_f["tokens_per_sec"], 3)
                            if s_f["tokens_per_sec"] else 0.0),
            "rc": 0,
            "extras": extras,
        }

    if args.tier_trace:
        # tiered-KV A/B (serve/kv_tier.py) over the many-tenant churn
        # trace: the SAME engine twice — host tier armed vs evict-only
        # — so every delta is the tier. The host budget defaults to 4x
        # the device pool's bytes (the spill-to-abundant-host-RAM
        # regime the tier is for); --tier-bytes pins it.
        from quintnet_tpu.serve.kv_quant import make_policy

        family, params = build_model(args)
        dims = dict(n_layers=family.n_layers,
                    n_kv_heads=family.n_kv_heads,
                    head_dim=family.head_dim, block_size=args.block_size)
        per_block = make_policy(args.kv_dtype).bytes_per_block(**dims)
        tier_bytes = int(args.tier_bytes
                         or 4 * args.num_blocks * per_block)
        promote_bytes = (args.tier_promote_blocks * per_block
                         if args.tier_promote_blocks else None)
        eng_t = build_engine(args, prefix_cache=True, params=params,
                             kv_tier_bytes=tier_bytes,
                             kv_tier_promote_budget_bytes=promote_bytes)
        trace = tier_trace_gen(args, eng_t.family.cfg.vocab_size)
        s_t = replay(eng_t, trace, args)
        eng_e = build_engine(args, prefix_cache=True, params=params)
        s_e = replay(eng_e, trace, args)
        # THE structural gate: a demotion copy must never ride a plain
        # decode dispatch — the tier's whole latency contract
        assert s_t["decode_blocked_demotions"] == 0, \
            "demotion blocked a decode step"
        extras = _common_extras(args, s_t)
        ratio = round(s_t["tokens_per_sec"]
                      / max(s_e["tokens_per_sec"], 1e-9), 3)
        extras.update({
            "tier_trace": True,
            "kv_dtype": args.kv_dtype,
            "shared_prefix": args.shared_prefix,
            "tier_prefixes": args.tier_prefixes,
            "tier_repeats": args.tier_repeats,
            "tier_byte_budget": tier_bytes,
            "tier_promote_blocks": args.tier_promote_blocks,
            "requests": len(trace),
            # the tier ledger (tiered side)
            "kv_demotions": s_t["kv_demotions"],
            "kv_promotions": s_t["kv_promotions"],
            "kv_host_evictions": s_t["kv_host_evictions"],
            "host_hit_tokens": s_t["host_hit_tokens"],
            "host_hit_rate": s_t["host_hit_rate"],
            "host_tier_bytes": s_t["host_tier_bytes"],
            "decode_blocked_demotions": s_t["decode_blocked_demotions"],
            "kv_cache_evictions": s_t["kv_cache_evictions"],
            # the A/B: a revisited prefix is a host hit on the tiered
            # side (promotion memcpy + tail prefill) and a cold
            # re-prefill on the evict-only side — hit rate and TTFT
            # are the committed wins
            "warm_hit_rate": s_t["prefix_hit_rate"],
            "evict_only_hit_rate": s_e["prefix_hit_rate"],
            "evict_only_ttft_p50_s": s_e["ttft_s"]["p50"],
            "evict_only_ttft_p95_s": s_e["ttft_s"]["p95"],
            "evict_only_tokens_per_sec": s_e["tokens_per_sec"],
            "evict_only_wall_s": s_e["wall_s"],
            "evict_only_prefill_tokens": s_e["prefill_tokens"],
            "evict_only_cache_evictions": s_e["kv_cache_evictions"],
            "evict_only_finished": s_e["finished"],
            "evict_only_preempted": s_e["preempted"],
            "speedup_vs_evict_only": ratio,
        })
        return {
            "metric": f"serve_{args.model}_{tag}_tier_tokens_per_sec",
            "value": s_t["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": ratio,
            "rc": 0,
            "extras": extras,
        }

    if args.moe_trace:
        # MoE routing A/B over the SAME engine config: a DIVERSE trace
        # (random prompts spread routed demand over the expert set) vs
        # a HOT-EXPERT trace (every request tiles one shared pattern —
        # skewed routing concentrates demand and drives capacity
        # drops). Wall clocks are reported, never gated; the gates are
        # structural: the routing ledger must account exactly and the
        # compile bound must not move (MoE adds zero programs).
        if not args.synthetic:
            raise SystemExit("--moe-trace needs --synthetic (the MoE "
                             "fields extend the tiny config)")
        family, params = build_model(args)
        eng_d = build_engine(args, prefix_cache=True, params=params)
        trace_d = poisson_trace(args, family.cfg.vocab_size)
        s_d = replay(eng_d, trace_d, args)
        eng_h = build_engine(args, prefix_cache=True, params=params)
        trace_h = hot_expert_trace(args, family.cfg.vocab_size)
        s_h = replay(eng_h, trace_h, args)
        for s in (s_d, s_h):
            # the ledger reads program outputs — it must account
            # exactly: per-expert demand sums to the routed total,
            # and drops never exceed it
            assert (sum(s["moe_expert_tokens"].values())
                    == s["moe_routed_tokens"]), "routing ledger leak"
            assert 0 <= s["moe_dropped_tokens"] <= s["moe_routed_tokens"]
        for eng in (eng_d, eng_h):
            # warmup compiles every ladder bucket once; MoE must not
            # add a single program beyond that bound
            eng.assert_compile_count(prefill=len(eng._prefills))
        extras = _common_extras(args, s_h)
        ratio = round(s_h["tokens_per_sec"]
                      / max(s_d["tokens_per_sec"], 1e-9), 3)
        extras.update({
            "moe_trace": True,
            "experts": args.experts,
            "expert_top_k": args.expert_top_k,
            "capacity_factor": args.capacity_factor,
            "expert_capacity": args.expert_capacity,
            "pattern": args.pattern,
            "compile_counts": eng_h.compile_stats(),
            # hot (skewed-routing) side — the committed skew evidence
            "hot_expert_skew": s_h["moe_expert_skew"],
            "hot_drop_rate": s_h["moe_drop_rate"],
            "hot_routed_tokens": s_h["moe_routed_tokens"],
            "hot_dropped_tokens": s_h["moe_dropped_tokens"],
            "hot_router_entropy": s_h["moe_router_entropy"],
            "hot_expert_tokens": s_h["moe_expert_tokens"],
            # diverse side — the balanced baseline
            "diverse_expert_skew": s_d["moe_expert_skew"],
            "diverse_drop_rate": s_d["moe_drop_rate"],
            "diverse_routed_tokens": s_d["moe_routed_tokens"],
            "diverse_dropped_tokens": s_d["moe_dropped_tokens"],
            "diverse_router_entropy": s_d["moe_router_entropy"],
            "diverse_expert_tokens": s_d["moe_expert_tokens"],
            "diverse_tokens_per_sec": s_d["tokens_per_sec"],
            "diverse_wall_s": s_d["wall_s"],
            "hot_vs_diverse": ratio,
        })
        return {
            "metric": f"serve_{args.model}_{tag}_moe_tokens_per_sec",
            "value": s_h["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": ratio,
            "rc": 0,
            "extras": extras,
        }

    if args.prefix_share:
        # A/B over the SAME shared-prefix trace: cache-on vs cache-off
        eng_on = build_engine(args, prefix_cache=True)
        trace = prefix_share_trace(args, eng_on.family.cfg.vocab_size)
        s_on = replay(eng_on, trace, args)
        eng_off = build_engine(args, prefix_cache=False)
        s_off = replay(eng_off, trace, args)
        extras = _common_extras(args, s_on)
        extras.update({
            "prefix_share": True,
            "shared_prefix": args.shared_prefix,
            "min_tail": args.min_tail,
            "max_tail": args.max_tail,
            "cache_off_tokens_per_sec": s_off["tokens_per_sec"],
            "cache_off_ttft_p50_s": s_off["ttft_s"]["p50"],
            "cache_off_ttft_p95_s": s_off["ttft_s"]["p95"],
            "cache_off_prefill_tokens": s_off["prefill_tokens"],
            "cache_off_wall_s": s_off["wall_s"],
            "speedup_vs_cache_off": (
                round(s_on["tokens_per_sec"]
                      / s_off["tokens_per_sec"], 3)
                if s_off["tokens_per_sec"] else 0.0),
        })
        return {
            "metric": f"serve_{args.model}_{tag}_prefix_share_"
                      "tokens_per_sec",
            "value": s_on["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": extras["speedup_vs_cache_off"],
            "rc": 0,
            "extras": extras,
        }

    if args.spec_trace:
        # A/B over the SAME repetitive trace: speculation on vs off
        eng_on = build_engine(args, prefix_cache=args.prefix_cache == "on",
                              spec=True)
        trace = repetitive_trace(args, eng_on.family.cfg.vocab_size)
        s_on = replay(eng_on, trace, args)
        eng_off = build_engine(args, prefix_cache=args.prefix_cache == "on",
                               spec=False)
        s_off = replay(eng_off, trace, args)
        extras = _common_extras(args, s_on)
        extras.update({
            "spec_trace": True,
            "spec": True,
            "pattern": args.pattern,
            "max_draft": args.max_draft,
            "spec_steps": s_on["spec_steps"],
            "draft_tokens": s_on["draft_tokens"],
            "accepted_draft_tokens": s_on["accepted_draft_tokens"],
            "draft_acceptance_rate": s_on["draft_acceptance_rate"],
            "spec_off_tokens_per_sec": s_off["tokens_per_sec"],
            "spec_off_decode_steps": s_off["decode_steps"],
            "spec_off_tokens_per_decode_step":
                s_off["tokens_per_decode_step"],
            "spec_off_wall_s": s_off["wall_s"],
            "speedup_vs_spec_off": (
                round(s_on["tokens_per_sec"] / s_off["tokens_per_sec"], 3)
                if s_off["tokens_per_sec"] else 0.0),
        })
        return {
            "metric": f"serve_{args.model}_{tag}_spec_tokens_per_sec",
            "value": s_on["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": extras["speedup_vs_spec_off"],
            "rc": 0,
            "extras": extras,
        }

    if args.long_trace:
        # A/B over the SAME long-document + short-decode-mix trace:
        # chunked prefill (budgeted, Sarathi) vs the stall-prone
        # monolithic baseline (prefill window widened to swallow the
        # whole prompt in one program call). The headline number is
        # decode tok/s DURING the long-prefill window — how fast
        # everyone else's streams move while a document is read in.
        max_seq = args.long_prompt + args.max_new
        budget = args.chunk_budget or args.prefill_window
        eng_ch = build_engine(args, prefix_cache=args.prefix_cache == "on",
                              max_seq=max_seq,
                              prefill_len=args.prefill_window,
                              chunked_prefill=True,
                              prefill_chunk_budget=budget)
        trace = long_trace(args, eng_ch.family.cfg.vocab_size)
        s_ch = replay_long(eng_ch, trace, args)
        eng_mono = build_engine(args,
                                prefix_cache=args.prefix_cache == "on",
                                max_seq=max_seq, prefill_len=max_seq)
        s_mono = replay_long(eng_mono, trace, args)
        extras = _common_extras(args, s_ch)
        ratio = (round(s_ch["decode_tps_during_long_prefill"]
                       / s_mono["decode_tps_during_long_prefill"], 3)
                 if s_mono["decode_tps_during_long_prefill"] else 0.0)
        extras.update({
            "long_trace": True,
            "long_prompts": args.long_prompts,
            "long_prompt": args.long_prompt,
            "prefill_window": args.prefill_window,
            "chunk_budget": budget,
            "prefill_chunks": s_ch["prefill_chunks"],
            "chunk_steps": s_ch["chunk_steps"],
            "chunk_tokens_per_step": s_ch["chunk_tokens_per_step"],
            "itl_p95_s": s_ch["itl_s"]["p95"],
            "itl_p99_s": s_ch["itl_s"]["p99"],
            "long_window_wall_s": s_ch["long_window_wall_s"],
            "long_window_decode_tokens":
                s_ch["long_window_decode_tokens"],
            "decode_tps_during_long_prefill":
                s_ch["decode_tps_during_long_prefill"],
            "unchunked_tokens_per_sec": s_mono["tokens_per_sec"],
            "unchunked_itl_p95_s": s_mono["itl_s"]["p95"],
            "unchunked_itl_p99_s": s_mono["itl_s"]["p99"],
            "unchunked_long_window_wall_s":
                s_mono["long_window_wall_s"],
            "unchunked_long_window_decode_tokens":
                s_mono["long_window_decode_tokens"],
            "unchunked_decode_tps_during_long_prefill":
                s_mono["decode_tps_during_long_prefill"],
            "unchunked_finished": s_mono["finished"],
            # THE acceptance signal: concurrent decode throughput
            # while a long prompt prefills, chunked / monolithic
            "decode_tps_ratio_vs_unchunked": ratio,
            "itl_p99_ratio_vs_unchunked": (
                round(s_mono["itl_s"]["p99"] / s_ch["itl_s"]["p99"], 3)
                if s_ch["itl_s"]["p99"] else 0.0),
        })
        return {
            "metric": f"serve_{args.model}_{tag}_long_tokens_per_sec",
            "value": s_ch["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": ratio,
            "rc": 0,
            "extras": extras,
        }

    if args.lora_trace:
        import tempfile

        from quintnet_tpu.serve import AdapterRegistry

        prefix_cache = args.prefix_cache == "on"
        spec = args.spec == "on"
        tmpdir = tempfile.mkdtemp(prefix="serve_bench_lora_")
        # A: ONE multi-LoRA engine serving the whole mixed-tenant trace
        _family, base_params = build_model(args)
        tenants = make_adapters(args, base_params, tmpdir)
        registry = AdapterRegistry()
        for aid, (_merged, path) in tenants.items():
            registry.register(aid, path)
        eng_lora = build_engine(args, prefix_cache=prefix_cache,
                                spec=spec, params=base_params,
                                adapters=registry)
        trace = lora_trace(args, eng_lora.family.cfg.vocab_size)
        s_on = replay(eng_lora, trace, args)
        # B: the merged-weight baseline — one DEDICATED engine per
        # tenant serving only its own requests (no cross-tenant
        # batching possible); walls and counters summed. The same
        # --spec/--prefix-cache settings apply to both sides.
        merged_wall = merged_gen = merged_steps = merged_dsteps = 0
        for aid, (merged, _path) in tenants.items():
            sub = [(t, p, m) for (t, p, m, a) in trace if a == aid]
            eng_m = build_engine(args, prefix_cache=prefix_cache,
                                 spec=spec, params=merged)
            s_m = replay(eng_m, sub, args)
            merged_wall += s_m["wall_s"]
            merged_gen += s_m["gen_tokens"]
            merged_steps += s_m["steps"]
            merged_dsteps += s_m["decode_steps"]
        merged_tps = (round(merged_gen / merged_wall, 2)
                      if merged_wall > 0 else 0.0)
        extras = _common_extras(args, s_on)
        extras.update({
            "lora_trace": True,
            "adapters": args.adapters,
            "lora_rank": args.lora_rank,
            "spec": spec,
            "prefix_cache": prefix_cache,
            "per_adapter": s_on["adapters"],
            "merged_tokens_per_sec": merged_tps,
            "merged_gen_tokens": merged_gen,
            "merged_wall_s": round(merged_wall, 4),
            "merged_decode_steps": merged_dsteps,
            "merged_steps": merged_steps,
            # the wall-noise-free signal: one shared multi-LoRA decode
            # step does the work of many dedicated-engine steps
            "decode_step_ratio_vs_merged": (
                round(merged_dsteps / s_on["decode_steps"], 3)
                if s_on["decode_steps"] else 0.0),
            "speedup_vs_merged": (
                round(s_on["tokens_per_sec"] / merged_tps, 3)
                if merged_tps else 0.0),
        })
        return {
            "metric": f"serve_{args.model}_{tag}_lora_tokens_per_sec",
            "value": s_on["tokens_per_sec"],
            "unit": "tok/s",
            "vs_baseline": extras["speedup_vs_merged"],
            "rc": 0,
            "extras": extras,
        }

    prefix_cache = args.prefix_cache == "on"
    spec = args.spec == "on"
    engine = build_engine(args, prefix_cache=prefix_cache, spec=spec)
    obs = None
    if args.trace_out:
        obs = _arm_obs(engine)     # standalone Perfetto export
    trace = poisson_trace(args, engine.family.cfg.vocab_size)
    s = replay(engine, trace, args)
    extras = _common_extras(args, s)
    extras["prefix_cache"] = prefix_cache
    extras["spec"] = spec
    extras["kv_dtype"] = args.kv_dtype
    extras["weights_dtype"] = args.weights_dtype
    extras["attn_kernel"] = args.kernel
    if obs is not None:
        extras.update(_obs_summary(*obs))
        extras.update(_write_trace_out(args.trace_out, *obs))
    if spec:
        extras.update({
            "spec_steps": s["spec_steps"],
            "draft_tokens": s["draft_tokens"],
            "accepted_draft_tokens": s["accepted_draft_tokens"],
            "draft_acceptance_rate": s["draft_acceptance_rate"],
        })
    return {
        "metric": f"serve_{args.model}_{tag}_tokens_per_sec",
        "value": s["tokens_per_sec"],
        "unit": "tok/s",
        "vs_baseline": 1.0,
        "rc": 0,
        "extras": extras,
    }


def _paged_kernel_module():
    """ops/paged_attention.py as a MODULE (``quintnet_tpu.ops``
    re-exports the function under the same name, so attribute access
    finds the function)."""
    import importlib

    return importlib.import_module("quintnet_tpu.ops.paged_attention")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2", choices=("gpt2", "llama"))
    ap.add_argument("--synthetic", action="store_true",
                    help="tiny random-init config (CPU-testable); "
                         "runs the Pallas paged kernel in INTERPRET "
                         "mode (recorded as cpu_interpret_mode) — "
                         "without this flag the kernel compiles for "
                         "real and a non-TPU process fails at lowering")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate (requests per engine step)")
    ap.add_argument("--steps", type=int, default=None,
                    help="cap on engine steps (default: run to completion)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=64)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--eos", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--policy", default="fcfs", choices=("fcfs", "priority"))
    ap.add_argument("--prefix-cache", default="on", choices=("on", "off"),
                    help="prefix-cache A/B switch for the default trace")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=("f32", "bf16", "int8", "fp8",
                             "fake_quant"),
                    help="KV-pool layout policy (serve/kv_quant.py): "
                         "int8 stores blocks quantized with per-block-"
                         "per-head scales, dequantized inside the "
                         "gathered-view attention kernels; fp8 is "
                         "unscaled float8_e4m3fn passthrough")
    ap.add_argument("--weights-dtype", default="f32",
                    choices=("f32", "bf16", "int8", "fp8",
                             "fake_quant"),
                    help="packed-weight layout policy "
                         "(serve/weight_quant.py): int8/fp8 store the "
                         "serving matmul weights with per-output-"
                         "channel absmax scales, dequantized inside "
                         "the dot (nn/layers.quantized_matmul)")
    ap.add_argument("--weights-ab", action="store_true",
                    help="weight-quant A/B over the default trace: "
                         "f32 weights vs --weights-dtype (int8 unless "
                         "set otherwise), everything else equal; the "
                         "committed gates are the targeted-node byte "
                         "ratio and the paged_eval_nll delta — CPU "
                         "walls recorded, never gated")
    ap.add_argument("--kernel", default="xla",
                    choices=("xla", "pallas"),
                    help="serving attention backend "
                         "(ops/paged_attention.py): 'xla' is the "
                         "gathered-view oracle, 'pallas' the fused "
                         "block-table-walking kernel (interpret mode "
                         "off-TPU)")
    ap.add_argument("--kernel-ab", action="store_true",
                    help="replay the SAME default trace through an "
                         "xla and a pallas engine: token-identity + "
                         "the auditor-verified structural win (zero "
                         "gathered-view gathers) are the committed "
                         "signals; CPU walls are recorded but NOT the "
                         "gate (interpret mode prices emulation, not "
                         "the kernel)")
    ap.add_argument("--kv-capacity", action="store_true",
                    help="equal-pool-BYTES capacity A/B over the "
                         "shared-prefix trace: f32 at --num-blocks vs "
                         "--kv-dtype (int8 unless set otherwise) at "
                         "however many blocks the same bytes buy")
    ap.add_argument("--tier-trace", action="store_true",
                    help="tiered-KV A/B over a many-tenant prefix-"
                         "churn trace: host tier armed (demote on "
                         "evict, promote on host-hit) vs the identical"
                         " evict-only engine; the prefix set is sized "
                         "--tier-prefix-ratio x the device pool so "
                         "every revisit has been evicted")
    ap.add_argument("--tier-prefixes", type=int, default=None,
                    help="distinct system prompts in the --tier-trace "
                         "(default: auto-sized from the ratio)")
    ap.add_argument("--tier-prefix-ratio", type=float, default=3.5,
                    help="prefix-set footprint as a multiple of the "
                         "usable device pool (--tier-trace)")
    ap.add_argument("--tier-repeats", type=int, default=3,
                    help="visits per prefix in the --tier-trace")
    ap.add_argument("--tier-bytes", type=int, default=None,
                    help="host-tier byte budget (--tier-trace; "
                         "default: 4x the device pool's bytes)")
    ap.add_argument("--tier-promote-blocks", type=int, default=None,
                    help="promotion budget in blocks per engine step "
                         "(--tier-trace; default: the engine's own)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="shared-system-prompt trace, reported cache-on "
                         "vs cache-off over the same trace")
    ap.add_argument("--spec", default="off", choices=("on", "off"),
                    help="speculative decoding (n-gram self-drafting + "
                         "batched verify) for the default trace")
    ap.add_argument("--spec-trace", action="store_true",
                    help="repetitive greedy-friendly trace, reported "
                         "spec-on vs spec-off over the same trace")
    ap.add_argument("--pattern", type=int, default=8,
                    help="repeated-pattern length (--spec-trace prompts)")
    ap.add_argument("--long-trace", action="store_true",
                    help="long-document + short-decode-mix trace, "
                         "reported chunked-prefill vs monolithic "
                         "(widened single-bucket) over the same trace")
    ap.add_argument("--long-prompts", type=int, default=2,
                    help="long prompts in the --long-trace")
    ap.add_argument("--long-prompt", type=int, default=192,
                    help="long-prompt length (--long-trace); must "
                         "exceed --prefill-window to exercise chunking")
    ap.add_argument("--long-spacing", type=int, default=24,
                    help="engine steps between long arrivals")
    ap.add_argument("--prefill-window", type=int, default=64,
                    help="chunked engine's prefill_len (top bucket)")
    ap.add_argument("--chunk-budget", type=int, default=None,
                    help="prefill tokens per engine step (default: "
                         "--prefill-window)")
    ap.add_argument("--lora-trace", action="store_true",
                    help="multi-tenant LoRA trace: requests spread over "
                         "--adapters adapters through ONE multi-LoRA "
                         "engine, vs dedicated merged-weight engines "
                         "per adapter over the same trace")
    ap.add_argument("--adapters", type=int, default=4,
                    help="distinct LoRA adapters in the --lora-trace")
    ap.add_argument("--lora-rank", type=int, default=4,
                    help="rank of the synthetic --lora-trace adapters "
                         "(and the engine's top rank bucket)")
    ap.add_argument("--max-draft", type=int, default=8,
                    help="max drafted tokens per request per step "
                         "(pins the largest verify bucket)")
    ap.add_argument("--shared-prefix", type=int, default=None,
                    help="shared system-prompt length (--prefix-share; "
                         "default 36 for --synthetic, 96 for full "
                         "configs — tiny models have few positions)")
    ap.add_argument("--min-tail", type=int, default=4,
                    help="min unique-tail length (--prefix-share)")
    ap.add_argument("--max-tail", type=int, default=12,
                    help="max unique-tail length (--prefix-share)")
    ap.add_argument("--n-layer", type=int, default=None,
                    help="synthetic-config depth override")
    ap.add_argument("--n-embd", type=int, default=None,
                    help="synthetic-config width override")
    ap.add_argument("--n-head", type=int, default=None,
                    help="synthetic-config head-count override")
    ap.add_argument("--n-positions", type=int, default=None,
                    help="synthetic-config max-positions override")
    ap.add_argument("--vocab-size", type=int, default=None,
                    help="synthetic-config vocab override")
    ap.add_argument("--moe-trace", action="store_true",
                    help="MoE routing A/B: diverse Poisson trace vs "
                         "hot-expert (one shared tiled pattern) trace "
                         "through the same MoE engine; value = hot-side "
                         "tok/s, vs_baseline = hot/diverse")
    ap.add_argument("--experts", type=int, default=0,
                    help="expert count for the synthetic config (0 = "
                         "dense; --moe-trace defaults this to 4)")
    ap.add_argument("--expert-top-k", type=int, default=2,
                    help="routed experts per token (--experts)")
    ap.add_argument("--capacity-factor", type=float, default=1.25,
                    help="expert capacity slack multiplier (--experts)")
    ap.add_argument("--expert-capacity", type=int, default=None,
                    help="hard per-expert token capacity override "
                         "(--experts; default: derived from the factor)")
    ap.add_argument("--obs-ab", action="store_true",
                    help="observability overhead A/B over the default "
                         "trace: flight recorder (obs/) armed vs off; "
                         "value = obs-on tok/s, vs_baseline = on/off")
    ap.add_argument("--trace-out", default=None,
                    help="write the replay's flight-recorder ring + "
                         "request spans as Chrome trace-event JSON "
                         "(Perfetto-loadable; arms obs on the timed "
                         "engine)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="append the record to this artifacts JSON file")
    args = ap.parse_args()
    if args.moe_trace and not args.experts:
        args.experts = 4
    if args.shared_prefix is None:
        args.shared_prefix = 36 if args.synthetic else 96
    if args.long_trace and args.synthetic and args.n_positions is None:
        # the tiny config's default positions cannot hold a document;
        # size it to the trace instead of failing admission
        args.n_positions = args.long_prompt + args.max_new + 16

    from quintnet_tpu.core.runtime import enable_compilation_cache

    enable_compilation_cache()  # before first backend use
    if args.synthetic:
        # the tiny CPU configuration: the kernel never chooses
        # interpret mode for itself, so this mode does, in the open
        _paged_kernel_module().INTERPRET = True

    out = run(args)
    line = json.dumps(out)
    print(line)
    if args.out:
        records = []
        if os.path.exists(args.out):
            try:
                with open(args.out) as f:
                    prev = json.load(f)
                records = prev if isinstance(prev, list) else [prev]
            except (OSError, json.JSONDecodeError):
                records = []
        records.append(out)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)


if __name__ == "__main__":
    main()
