"""Fleet benchmark: replay a bursty request trace against a
multi-replica serving fleet (quintnet_tpu/fleet/) once per routing
policy, with a mid-trace replica kill and an over-capacity burst, and
report one JSON line per policy:

  {"metric": "fleet_gpt2_tiny_tokens_per_sec", "value": N,
   "unit": "tok/s", "rc": 0, "extras": {"policy": "least_work",
   "ttft_p50_s": .., "ttft_p99_s": .., "shed_rate": ..,
   "migrations": .., ...}}

The trace front-loads ``--burst`` requests in one instantaneous spike
(what sheds: the fleet absorbs queue + dispatch windows and REJECTS
the rest with a typed Overloaded — the queue never grows past
``--max-pending``), then Poisson arrivals (inter-arrival ~
Exp(rate) seconds) for the remainder. ``--kill-at-step K`` arms an
``ft.ChaosMonkey`` (mode='raise') against ``--kill-replica`` AFTER
warmup, so the victim dies at its K-th replay step and its in-flight
requests migrate — finished counts include them, token-identical
(tests/test_fleet.py holds the identity; here we count).

``--process`` replays the SAME trace through the cross-process fleet
(quintnet_tpu/fleet/proc.py): each replica is its own spawned OS
process behind the wire protocol, the armed kill is a mode='hard'
``os._exit`` — the process vanishes mid-run with no cleanup, the
SIGKILL story — and the dispatcher's write-ahead journal migrates the
victim's in-flight requests to survivors (finished == accepted).
Reported tokens come from the dispatcher's journal
(``tokens_delivered``), which survives replica deaths; the metric name
gains a ``proc`` tag so the thread and process records never alias.

``--disagg`` runs the disaggregation A/B (quintnet_tpu/fleet/proc.py
``pools=``): the same steady-decode trace + long-prefill burst through
a disaggregated prefill/decode fleet AND a colocated fleet of equal
size, each also replayed without the burst. The reported value is the
disaggregated side's SELF-interference (decode ITL p99, burst /
no-burst — the "burst must not move decode ITL" bound); the
matched-load comparison vs colocated is ``burst_itl_p99_vs_colocated``
(< 1 = the dedicated prefill pool wins under the same burst on the
same box; see run_disagg for why the two modes' self-ratios are not
directly comparable on shared cores). Structural isolation —
``disagg_pool_prefill_tokens`` — is the noise-free signal: every long
prefill must land on the prefill pool (DistServe/Splitwise;
artifacts/fleet_r16.json).

``--slo`` replays the SAME interference trace with the judgment layer
armed (quintnet_tpu/obs/slo.py + signals.py): one shared objective
set is CALIBRATED off the clean no-burst replays — each signal's BEST
baseline across the two modes, x mult (TTFT p99 <= mult x baseline;
relative, so the contract travels across machines) — then both modes
replay the burst under the armed SLO engine + signal bus (+ the
observe-only rebalance planner on the disaggregated side). The record
is the typed-event story: the burst trips the fast+slow TTFT burn
windows, the breach names the prefill pool, the planner recommends
decode→prefill and the revert after recovery — and the colocated
fleet ALSO burns the ITL budget the disaggregated one holds, which is
the DistServe goodput argument as events instead of a human reading
fleet_r16.json (artifacts/slo_r17.json).

Modes:
  python tools/fleet_bench.py --synthetic                # tiny, CPU-ok
  python tools/fleet_bench.py --synthetic --requests 6 \
      --policies least_work                              # CI smoke
  python tools/fleet_bench.py --synthetic --out artifacts/fleet_r08.json
  python tools/fleet_bench.py --synthetic --process \
      --out artifacts/fleet_r12.json                     # process fleet
  python tools/fleet_bench.py --synthetic --disagg \
      --out artifacts/fleet_r16.json                     # interference A/B
  python tools/fleet_bench.py --synthetic --slo \
      --out artifacts/slo_r17.json                       # SLO replay

``--out FILE`` appends the records to an artifacts JSON list
(bench.last_known_result scans them — same staleness story as the
serve/train benches).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def model_setup(model: str, synthetic: bool, seed: int,
                n_positions=None, n_embd=None):
    """THE single source of the benched model: (family, params). Both
    modes — the thread factory and the process children, each in their
    own interpreter — construct the model HERE from the same seed, so
    they cannot drift apart and every replica holds identical
    (family, params), the migration-contract precondition.
    ``n_positions`` widens the synthetic gpt2 context (the --disagg
    trace needs prompts long enough for a prefill burst to hurt)."""
    import jax

    from quintnet_tpu.serve import gpt2_family, llama_family

    if model == "gpt2":
        from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init

        if synthetic:
            kw = {}
            if n_positions is not None:
                kw["n_positions"] = int(n_positions)
            if n_embd is not None:
                # the --disagg interference probe needs a prefill that
                # actually costs something; width is the cheapest lever
                kw.update(n_embd=int(n_embd),
                          n_head=max(2, int(n_embd) // 64))
            cfg = GPT2Config.tiny(n_layer=2, **kw)
        else:
            cfg = GPT2Config.base()
        return gpt2_family(cfg), gpt2_init(jax.random.key(seed), cfg)
    if model == "llama":
        from quintnet_tpu.models.llama import LlamaConfig, llama_init

        cfg = (LlamaConfig.tiny(n_layers=2) if synthetic
               else LlamaConfig())
        return llama_family(cfg), llama_init(jax.random.key(seed), cfg)
    raise SystemExit(f"unknown --model {model}")


def build_engine(*, model="gpt2", synthetic=True, seed=0, slots=2,
                 block_size=16, num_blocks=64, max_seq_len=40,
                 eos=None, temperature=0.0, n_positions=None,
                 n_embd=None, kv_dtype=None):
    """One replica engine, DETERMINISTIC in its kwargs — the builder
    the process fleet's spawn children load by file path."""
    from quintnet_tpu.serve import ServeEngine

    family, params = model_setup(model, synthetic, seed,
                                 n_positions=n_positions, n_embd=n_embd)
    return ServeEngine(
        family, params, max_slots=slots, block_size=block_size,
        num_blocks=num_blocks,
        max_seq_len=min(max_seq_len, family.max_positions),
        kv_dtype=kv_dtype, eos_token_id=eos, temperature=temperature)


def engine_kwargs(args) -> dict:
    return {"model": args.model, "synthetic": bool(args.synthetic),
            "seed": args.seed, "slots": args.slots,
            "block_size": args.block_size,
            "num_blocks": args.num_blocks,
            "max_seq_len": args.max_prompt + args.max_new,
            "eos": args.eos, "temperature": args.temperature}


def vocab_size(args) -> int:
    """Vocab for trace generation WITHOUT materializing params (the
    process mode's parent never builds a model)."""
    if args.model == "gpt2":
        from quintnet_tpu.models.gpt2 import GPT2Config

        return (GPT2Config.tiny(n_layer=2) if args.synthetic
                else GPT2Config.base()).vocab_size
    from quintnet_tpu.models.llama import LlamaConfig

    return (LlamaConfig.tiny(n_layers=2) if args.synthetic
            else LlamaConfig()).vocab_size


def build_factory(args):
    """Thread-mode factory: model_setup() called ONCE, params shared
    by every replica engine in this process (the process mode cannot
    share — each child runs the same model_setup from the same seed,
    which is the point)."""
    from quintnet_tpu.serve import ServeEngine

    family, params = model_setup(args.model, bool(args.synthetic),
                                 args.seed)
    max_seq = min(args.max_prompt + args.max_new, family.max_positions)

    def factory():
        return ServeEngine(
            family, params, max_slots=args.slots,
            block_size=args.block_size, num_blocks=args.num_blocks,
            max_seq_len=max_seq, eos_token_id=args.eos,
            temperature=args.temperature)

    return factory, family.cfg.vocab_size


def make_trace(args, vocab_size: int):
    """[(delay_s_before_submit, prompt, max_new)]: the first ``burst``
    arrivals are instantaneous (delay 0 — the shedding spike), the rest
    Poisson-spaced."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    trace = []
    for i in range(args.requests):
        delay = 0.0 if i < args.burst else rng.exponential(1.0 / args.rate)
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        prompt = rng.integers(0, vocab_size, (n,)).astype(np.int32)
        trace.append((delay, prompt, args.max_new))
    return trace


def run_policy(args, policy: str, factory, vocab_size: int) -> dict:
    import time

    import numpy as np

    import jax

    from quintnet_tpu.fleet import Overloaded, ServeFleet
    from quintnet_tpu.ft import ChaosMonkey

    fleet = ServeFleet(
        factory, n_replicas=args.replicas, policy=policy,
        max_pending=args.max_pending, max_dispatch=args.max_dispatch,
        trip_after=args.trip_after)
    # warmup: compile every replica's prefill+decode OUTSIDE the timed
    # window — one full request lifecycle per replica, routed there
    # deterministically by pausing the others — then reset all ledgers
    for rep in fleet.replicas:
        for other in fleet.replicas:
            other.resume() if other is rep else other.pause()
        fleet.generate([np.ones((args.min_prompt,), "int32")],
                       max_new_tokens=2, timeout=600)
    fleet.resume_all()
    fleet.reset_metrics()

    monkey = None
    if args.kill_at_step is not None:
        monkey = ChaosMonkey(kill_at_step=args.kill_at_step, mode="raise",
                             target=args.kill_replica)
        fleet.arm_chaos(monkey)

    trace = make_trace(args, vocab_size)
    fids = []
    t0 = time.perf_counter()
    for delay, prompt, max_new in trace:
        if delay:
            time.sleep(delay)
        try:
            fids.append(fleet.submit(prompt, max_new))
        except Overloaded:
            pass                       # counted in fleet.summary()
    for fid in fids:
        try:
            fleet.result(fid, timeout=args.timeout_s)
        except Overloaded:
            pass
    jax.block_until_ready(
        [rep.engine.pool.caches() for rep in fleet.replicas])
    wall = time.perf_counter() - t0

    s = fleet.summary()
    fleet.drain(timeout=args.timeout_s)
    eng = s["engine"]
    gen_tokens = eng["gen_tokens"]
    tag = "tiny" if args.synthetic else "full"
    return {
        "metric": f"fleet_{args.model}_{tag}_tokens_per_sec",
        "value": round(gen_tokens / wall, 2) if wall > 0 else 0.0,
        "unit": "tok/s",
        "vs_baseline": 1.0,
        "rc": 0,
        "extras": {
            "policy": policy,
            "replicas": args.replicas,
            "requests": args.requests,
            "submitted": s["submitted"],
            "accepted": s["accepted"],
            "finished": s["finished"],
            "shed": s["shed"],
            "shed_rate": s["shed_rate"],
            "migrations": s["migrations"],
            "replica_deaths": s["replica_deaths"],
            "restarts": s["restarts"],
            "ttft_p50_s": s["ttft_s"]["p50"],
            "ttft_p99_s": s["ttft_s"]["p99"],
            "latency_p50_s": s["latency_s"]["p50"],
            "latency_p99_s": s["latency_s"]["p99"],
            "gen_tokens": gen_tokens,
            "engine_steps": eng["steps"],
            "preempted": eng["preempted"],
            "wall_s": round(wall, 4),
            "kill_at_step": args.kill_at_step,
            "kill_replica": args.kill_replica,
            "burst": args.burst,
            "max_pending": args.max_pending,
            "rate": args.rate,
            "slots": args.slots,
            "model": args.model,
            "synthetic": bool(args.synthetic),
        },
    }


def run_policy_process(args, policy: str) -> dict:
    """One replay through the CROSS-PROCESS fleet: spawn --replicas
    engine processes, warm every compiled program over the wire, arm a
    mode='hard' chaos kill (abrupt process exit, no cleanup — the
    SIGKILL story) in the target child, replay the same bursty trace,
    and report from the dispatcher's journal — which is why
    finished == accepted survives the kill."""
    import time

    from quintnet_tpu.fleet import Overloaded, ProcessFleet
    from quintnet_tpu.fleet.health import Backoff

    spec = {"file": os.path.abspath(__file__), "func": "build_engine",
            "kwargs": engine_kwargs(args)}
    fleet = ProcessFleet(
        spec, n_replicas=args.replicas, policy=policy,
        max_pending=args.max_pending, max_dispatch=args.max_dispatch,
        trip_after=args.trip_after, heartbeat_s=0.05,
        backoff=Backoff(base_s=0.02, cap_s=0.5), name_prefix="r")
    try:
        # compile every child's full program set OUTSIDE the timed
        # window (one warmup RPC per replica), then fresh ledgers
        fleet.warmup()
        fleet.reset_metrics()
        if args.kill_at_step is not None:
            fleet.arm_chaos(args.kill_replica,
                            {"kill_at_step": args.kill_at_step,
                             "mode": "hard"})

        trace = make_trace(args, vocab_size(args))
        fids = []
        t0 = time.perf_counter()
        for delay, prompt, max_new in trace:
            if delay:
                time.sleep(delay)
            try:
                fids.append(fleet.submit(prompt, max_new))
            except Overloaded:
                pass                   # counted in fleet.summary()
        for fid in fids:
            try:
                fleet.result(fid, timeout=args.timeout_s)
            except Overloaded:
                pass
        # no device lives in THIS process: every token in the journal
        # was already streamed over a socket by a child whose step
        # completed — the wall delta is true end-to-end serving time
        wall = time.perf_counter() - t0  # qtcheck: ok[QT106]

        s = fleet.summary()
    finally:
        fleet.drain(timeout=args.timeout_s)
    gen_tokens = s["tokens_delivered"]
    engines = s.get("engines", {})
    tag = "tiny" if args.synthetic else "full"
    return {
        "metric": f"fleet_proc_{args.model}_{tag}_tokens_per_sec",
        "value": round(gen_tokens / wall, 2) if wall > 0 else 0.0,
        "unit": "tok/s",
        "vs_baseline": 1.0,
        "rc": 0,
        "extras": {
            "policy": policy,
            "process": True,
            "replicas": args.replicas,
            "requests": args.requests,
            "submitted": s["submitted"],
            "accepted": s["accepted"],
            "finished": s["finished"],
            "shed": s["shed"],
            "shed_rate": s["shed_rate"],
            "migrations": s["migrations"],
            "replica_deaths": s["replica_deaths"],
            "stalls": s["stalls"],
            "restarts": s["restarts"],
            "ttft_p50_s": s["ttft_s"]["p50"],
            "ttft_p99_s": s["ttft_s"]["p99"],
            "latency_p50_s": s["latency_s"]["p50"],
            "latency_p99_s": s["latency_s"]["p99"],
            "gen_tokens": gen_tokens,
            "live_engine_steps": sum(e["steps"]
                                     for e in engines.values()),
            "engines_reporting": len(engines),
            "wall_s": round(wall, 4),
            "kill_at_step": args.kill_at_step,
            "kill_replica": args.kill_replica,
            "burst": args.burst,
            "max_pending": args.max_pending,
            "rate": args.rate,
            "slots": args.slots,
            "model": args.model,
            "synthetic": bool(args.synthetic),
        },
    }


# ---------------------------------------------------------------------------
# --disagg: TTFT-vs-ITL interference A/B (disaggregated vs colocated)
# ---------------------------------------------------------------------------


def _disagg_engine_kwargs(args) -> dict:
    """Engine spec for the interference A/B: context wide enough for
    the long-prefill burst, pool sized so nothing preempts."""
    # the window must hold BOTH trace populations: long burst prompts
    # AND the steady prompts (which --max-prompt can size past the
    # burst length)
    max_seq = max(args.burst_prompt_len, args.max_prompt) + args.max_new
    return {"model": args.model, "synthetic": bool(args.synthetic),
            "seed": args.seed, "slots": args.slots,
            "block_size": args.block_size,
            "num_blocks": args.num_blocks,
            "max_seq_len": max_seq, "n_positions": max_seq,
            "n_embd": args.disagg_n_embd,
            "kv_dtype": args.kv_dtype,
            "eos": args.eos, "temperature": args.temperature}


def _replay_itl(args, fleet, vocab: int, *, burst: bool,
                seed: int) -> dict:
    """One replay against an ALREADY-WARM fleet: ``--steady`` short
    decode-heavy requests submitted at t=0, then (burst replays only)
    ``--burst-prompts`` long-prefill requests mid-decode. Inter-token
    gaps are timestamped AT THE DISPATCHER as tokens stream in — the
    client-visible ITL, which is exactly what a monolithic prefill on
    a colocated replica inflates and a dedicated prefill pool must
    not."""
    import threading
    import time

    import numpy as np

    from quintnet_tpu.fleet import Overloaded

    rng = np.random.default_rng(seed)
    marks = {}          # steady fid -> token arrival timestamps
    lock = threading.Lock()

    def on_token(fid, tok, last):  # appends only; contractually quick
        with lock:
            # setdefault: a first token can land before the submit
            # call returns and the fid is registered below — a plain
            # KeyError here would be SWALLOWED by FleetRequest.deliver
            # (client callbacks must not read as replica faults) and
            # silently drop timestamps, shifting the per[:2]
            # admission-gap trim onto steady-state gaps
            marks.setdefault(fid, []).append(time.perf_counter())

    fleet.reset_metrics()
    fids, burst_fids = [], []
    for i in range(args.steady):
        # staggered arrivals: the prefill pool (and the handoff path)
        # stays periodically busy through BOTH replays, so the
        # no-burst baseline carries the same steady-state load as the
        # burst replay and the ratio isolates the BURST, not the
        # difference between an idle and a working prefill pool
        if i:
            time.sleep(args.steady_gap_s)
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        prompt = rng.integers(0, vocab, (n,)).astype(np.int32)
        fid = fleet.submit(prompt, args.max_new, on_token=on_token)
        with lock:
            marks.setdefault(fid, [])
        fids.append(fid)
    if burst:
        time.sleep(args.burst_delay_s)
        for _ in range(args.burst_prompts):
            # the burst is TTFT-bound prefill work (max_new=1): on a
            # disaggregated fleet it lives and dies in the prefill
            # pool — which is the isolation claim under test. The
            # steady requests above exercise the full handoff path
            # (prefill pool -> KV transfer -> decode pool) either way.
            prompt = rng.integers(
                0, vocab, (args.burst_prompt_len,)).astype(np.int32)
            try:
                burst_fids.append(fleet.submit(prompt, 1))
            except Overloaded:
                pass
    for fid in fids + burst_fids:
        fleet.result(fid, timeout=args.timeout_s)
    gaps, first_gaps = [], []
    with lock:
        for ts in marks.values():
            per = [b - a for a, b in zip(ts, ts[1:])]
            # the first two gaps straddle the admission boundary —
            # on a disaggregated fleet that includes the one-time KV
            # handoff (a TTFT-class cost, reported separately below),
            # on any fleet the admission prefill of the cohort itself.
            # Steady-state decode ITL — the thing a prefill burst must
            # not disturb — is everything after
            first_gaps.extend(per[:2])
            gaps.extend(per[2:])
    gaps.sort()
    s = fleet.summary()
    # the NOISE-FREE structural signal: where did prefill compute
    # actually run? On a disaggregated fleet the decode pool's
    # engines prefill only warm-hit tails (~1 token per handed-off
    # request) — the burst's long prefills must all land on the
    # prefill pool. Wall-clock ITL wobbles on a loaded CPU box; token
    # accounting does not.
    pool_of = {r.name: r.pool for r in fleet.replicas}
    pool_prefill = {}
    for name, eng in s.get("engines", {}).items():
        pool = pool_of.get(name, "any")
        pool_prefill[pool] = (pool_prefill.get(pool, 0)
                              + int(eng.get("prefill_tokens", 0)))
    return {
        "pool_prefill_tokens": pool_prefill,
        "itl_p99_s": (round(float(np.percentile(gaps, 99)), 5)
                      if gaps else 0.0),
        "itl_p50_s": (round(float(np.percentile(gaps, 50)), 5)
                      if gaps else 0.0),
        "ttft_p99_s": s["ttft_s"]["p99"],
        "ttft_p50_s": s["ttft_s"]["p50"],
        "first_gap_max_s": (round(max(first_gaps), 5)
                            if first_gaps else 0.0),
        "gaps": len(gaps),
        "finished": s["finished"],
        "accepted": s["accepted"],
        "handoffs": s["handoffs"],
        "handoff_transfers": s["handoff_transfers"],
        "handoff_fallbacks": s["handoff_fallbacks"],
    }


def run_disagg(args) -> dict:
    """The disaggregation A/B at matched load: the SAME steady trace +
    long-prefill burst replayed through (a) a disaggregated fleet —
    dedicated prefill pool absorbing the burst, decode pool streaming
    undisturbed, KV chains handed off over the wire — and (b) a
    colocated fleet of the same total replica count, where the burst's
    monolithic prefills stall whichever replicas take them. Each mode
    also replays WITHOUT the burst for its own baseline, so the
    reported signal is the interference RATIO (burst ITL p99 /
    no-burst ITL p99) — self-normalized per mode, which is what makes
    it comparable on a noisy CPU box."""
    import time

    from quintnet_tpu.fleet import ProcessFleet
    from quintnet_tpu.fleet.retry import RetryPolicy

    vocab = vocab_size(args)
    spec = {"file": os.path.abspath(__file__), "func": "build_engine",
            "kwargs": _disagg_engine_kwargs(args)}
    n_total = args.prefill_replicas + args.decode_replicas
    results = {}
    for mode in ("disagg", "colocated"):
        kw = (dict(pools={"prefill": args.prefill_replicas,
                          "decode": args.decode_replicas})
              if mode == "disagg" else dict(n_replicas=n_total))
        fleet = ProcessFleet(
            spec, policy="least_work", max_pending=args.max_pending,
            max_dispatch=args.max_dispatch, heartbeat_s=0.05,
            handoff_retry=RetryPolicy(base_s=0.02, cap_s=0.5,
                                      max_attempts=3),
            name_prefix="r", **kw)
        try:
            fleet.warmup()
            # throwaway warm replay: first-use costs that are not the
            # steady-state story (KV-import scatter compiles on the
            # decode replicas, allocator warm-up) must not land inside
            # a measured window — same discipline as serve_bench's
            # warm-lifecycle-first A/B
            import argparse as _ap

            # capped at the run's own --max-new: the engines are sized
            # for THAT window, and a longer warm request would be
            # rejected as inadmissible (prompt+max_new > max_seq_len)
            warm = _ap.Namespace(**{**vars(args), "steady": 2,
                                    "max_new": min(4, args.max_new)})
            _replay_itl(warm, fleet, vocab, burst=False,
                        seed=args.seed + 7919)
            for burst in (False, True):
                results[(mode, burst)] = _replay_itl(
                    args, fleet, vocab, burst=burst,
                    seed=args.seed + (1 if burst else 0))
        finally:
            fleet.drain(timeout=args.timeout_s)

    def ratio(mode):
        base = results[(mode, False)]["itl_p99_s"]
        loud = results[(mode, True)]["itl_p99_s"]
        return round(loud / base, 4) if base > 0 else 0.0

    def vs_colocated(burst):
        d = results[("disagg", burst)]["itl_p99_s"]
        c = results[("colocated", burst)]["itl_p99_s"]
        return round(d / c, 4) if c > 0 else 0.0

    tag = "tiny" if args.synthetic else "full"
    d_burst, c_burst = results[("disagg", True)], \
        results[("colocated", True)]
    # Two complementary signals. The headline value is the
    # disaggregated side's SELF-interference (burst p99 / its own
    # no-burst p99) — the "burst must not move decode ITL" bound.
    # The matched-load comparison vs colocated is the ABSOLUTE
    # burst-time p99 ratio (burst_itl_p99_vs_colocated < 1 = win):
    # on a shared-core box the self-ratios are not comparable across
    # modes, because disaggregation also cleans up the NO-burst
    # baseline (the prefill pool idles when nobody bursts —
    # baseline_itl_p99_vs_colocated reports that win), which deflates
    # the colocated ratio's denominator asymmetrically.
    return {
        "metric": f"fleet_disagg_{args.model}_{tag}_itl_interference",
        "value": ratio("disagg"),
        "unit": "ratio",
        "vs_baseline": 1.0,
        "rc": 0,
        "extras": {
            "colocated_interference": ratio("colocated"),
            "burst_itl_p99_vs_colocated": vs_colocated(True),
            "baseline_itl_p99_vs_colocated": vs_colocated(False),
            "disagg_itl_p99_no_burst_s":
                results[("disagg", False)]["itl_p99_s"],
            "disagg_itl_p99_burst_s": d_burst["itl_p99_s"],
            "colocated_itl_p99_no_burst_s":
                results[("colocated", False)]["itl_p99_s"],
            "colocated_itl_p99_burst_s": c_burst["itl_p99_s"],
            "disagg_itl_p50_burst_s": d_burst["itl_p50_s"],
            "colocated_itl_p50_burst_s": c_burst["itl_p50_s"],
            "handoffs": d_burst["handoffs"],
            "handoff_transfers": d_burst["handoff_transfers"],
            "handoff_fallbacks": d_burst["handoff_fallbacks"],
            "finished": d_burst["finished"],
            "accepted": d_burst["accepted"],
            # structural isolation (deterministic, CI-gated): every
            # long prefill of the burst ran on the prefill pool; the
            # decode pool prefilled warm-hit tails only
            "disagg_pool_prefill_tokens":
                d_burst["pool_prefill_tokens"],
            "colocated_pool_prefill_tokens":
                c_burst["pool_prefill_tokens"],
            "kv_dtype": args.kv_dtype,
            "colocated_finished": c_burst["finished"],
            "colocated_accepted": c_burst["accepted"],
            "prefill_replicas": args.prefill_replicas,
            "decode_replicas": args.decode_replicas,
            "steady": args.steady,
            "burst_prompts": args.burst_prompts,
            "burst_prompt_len": args.burst_prompt_len,
            "max_new": args.max_new,
            "slots": args.slots,
            "model": args.model,
            "synthetic": bool(args.synthetic),
        },
    }


# ---------------------------------------------------------------------------
# --slo: the judgment layer replayed over the fleet_r16 interference trace
# ---------------------------------------------------------------------------


def _slo_capture(fleet) -> dict:
    """One mode's SLO story after an armed replay: which objectives
    breached / recovered (from the typed event stream — edges, not
    polling), the burn peaks, and the planner's recommendation ledger
    (disaggregated fleets only)."""
    status = fleet.slo.status()
    events = fleet.events.snapshot()

    def of_kind(kind):
        return [e for e in events if e["kind"] == kind]

    breaches = of_kind("slo_breach")
    out = {
        "breached": sorted({e["objective"] for e in breaches}),
        "breach_pools": {e["objective"]: e["pool"] for e in breaches},
        "recovered": sorted({e["objective"]
                             for e in of_kind("slo_recovered")}),
        "burn_fast_peak": {name: st["burn_fast_peak"]
                           for name, st in status["objectives"].items()},
        "breach_burns": [{"objective": e["objective"],
                          "burn_fast": e["burn_fast"],
                          "burn_slow": e["burn_slow"]}
                         for e in breaches],
        "still_breaching": status["breaching"],
    }
    if fleet.planner is not None:
        out["recommendations"] = [
            {k: r.get(k) for k in ("direction", "from_pool", "to_pool",
                                   "revert", "objective", "reason")}
            for r in fleet.planner.recommendations]
    return out


def run_slo(args) -> dict:
    """The SLO engine + signal plane over the SAME interference trace
    as --disagg (fleet_r16): each mode first replays WITHOUT the burst
    unarmed, then WITH the burst under the armed engine. The clean
    replays calibrate ONE shared objective set — each signal's BEST
    clean baseline across the two modes, x mult (absolute targets
    would bake in one machine's speed) — the tightest contract this
    box can promise at all; both modes are then judged against the
    SAME promise, which is the DistServe goodput framing.

    The acceptance story this records: on the DISAGGREGATED side the
    long-prefill burst trips the fast+slow TTFT burn windows, the
    breach names the prefill pool, the observe-only planner recommends
    converting a decode replica to prefill while the breach holds and
    recommends the REVERT after it recovers; ITL holds — the decode
    pool never runs a monolithic prefill. On the COLOCATED side the
    same burst ALSO burns the ITL budget — the monolithic prefills
    stall decode, a breach no rebalance can fix — which is the
    DistServe goodput argument as a typed event stream instead of a
    human reading fleet_r16.json."""
    import time

    from quintnet_tpu.fleet import ProcessFleet
    from quintnet_tpu.fleet.retry import RetryPolicy
    from quintnet_tpu.obs import SLOConfig

    if args.max_new < 4:
        # the ITL ledger excludes each request's first 2 gaps (handoff
        # transient) — shorter runs leave NO steady gaps, calibrate an
        # itl_p99 target of 0.0, and Objective rejects target <= 0
        raise SystemExit("--slo needs --max-new >= 4: shorter runs "
                         "record no steady ITL gaps to calibrate the "
                         "itl_p99 objective from")
    vocab = vocab_size(args)
    spec = {"file": os.path.abspath(__file__), "func": "build_engine",
            "kwargs": _disagg_engine_kwargs(args)}
    n_total = args.prefill_replicas + args.decode_replicas
    results = {}
    fleets = {}
    try:
        # phase 1 — both fleets up, warm, and replayed WITHOUT the
        # burst, unarmed: the clean baselines. The shared objective
        # set takes each signal's BEST clean baseline across the two
        # modes (x mult) — the tightest contract this box can promise
        # at all. That is what makes the verdict meaningful: TTFT
        # calibrates off the colocated side (no handoff in the first
        # token's path), ITL off the disaggregated side (a dedicated
        # decode pool nothing ever prefills on), and the burst replay
        # then shows which deployment can HOLD the combined promise.
        for mode in ("disagg", "colocated"):
            kw = (dict(pools={"prefill": args.prefill_replicas,
                              "decode": args.decode_replicas})
                  if mode == "disagg" else dict(n_replicas=n_total))
            fleet = fleets[mode] = ProcessFleet(
                spec, policy="least_work", max_pending=args.max_pending,
                max_dispatch=args.max_dispatch, heartbeat_s=0.05,
                handoff_retry=RetryPolicy(base_s=0.02, cap_s=0.5,
                                          max_attempts=3),
                name_prefix="r", obs=True, **kw)
            fleet.warmup()
            import argparse as _ap

            warm = _ap.Namespace(**{**vars(args), "steady": 2,
                                    "max_new": min(4, args.max_new)})
            _replay_itl(warm, fleet, vocab, burst=False,
                        seed=args.seed + 7919)
            base = _replay_itl(args, fleet, vocab, burst=False,
                               seed=args.seed)
            results[mode] = {"baseline": base}
        targets = {
            "ttft_p99_s": round(args.slo_ttft_mult * min(
                results[m]["baseline"]["ttft_p99_s"]
                for m in results), 5),
            "itl_p99_s": round(args.slo_itl_mult * min(
                results[m]["baseline"]["itl_p99_s"]
                for m in results), 5),
        }
        bad = {k: v for k, v in targets.items() if v <= 0}
        if bad:
            raise SystemExit(f"clean-replay calibration produced "
                             f"non-positive targets {bad} — the "
                             f"baseline recorded no samples for "
                             f"these signals; raise --steady/--max-new")
        # phase 2 — arm the SAME objectives on both fleets and replay
        # WITH the burst (the idle fleet just heartbeats while the
        # other replays; replays stay sequential so the two modes
        # never compete for cores mid-measurement)
        for mode in ("disagg", "colocated"):
            fleet = fleets[mode]
            fleet.arm_slo(
                SLOConfig.serving(
                    ttft_p99_s=targets["ttft_p99_s"],
                    itl_p99_s=targets["itl_p99_s"],
                    fast_window_s=args.slo_fast_window,
                    slow_window_s=args.slo_slow_window,
                    burn_threshold=args.slo_burn_threshold,
                    eval_interval_s=args.slo_eval_interval),
                cooldown_s=args.slo_cooldown,
                donor_occupancy_below=args.slo_donor_occ)
            burst = _replay_itl(args, fleet, vocab, burst=True,
                                seed=args.seed + 1)
            # post-burst: the dispatcher keeps evaluating on its own
            # tick — wait for the fast window to clear (recovery) and,
            # on the disaggregated side, for the planner's revert
            deadline = time.monotonic() + args.slo_recovery_wait
            while time.monotonic() < deadline:  # qtcheck: ok[QT106]
                recovered = not fleet.slo.status()["breaching"]
                reverted = (fleet.planner is None
                            or any(r["revert"] for r in
                                   fleet.planner.recommendations))
                if recovered and reverted:
                    break
                time.sleep(0.05)
            results[mode].update(burst=burst, slo=_slo_capture(fleet))
    finally:
        for fleet in fleets.values():
            fleet.drain(timeout=args.timeout_s)

    d, c = results["disagg"], results["colocated"]
    recs = d["slo"]["recommendations"]
    tag = "tiny" if args.synthetic else "full"
    # the headline value: how hard the burst burned the TTFT budget on
    # the disaggregated side's fast window (>= threshold = tripped)
    return {
        "metric": f"fleet_slo_{args.model}_{tag}_burst_burn_peak",
        "value": d["slo"]["burn_fast_peak"].get("ttft_p99", 0.0),
        "unit": "x",
        "vs_baseline": 1.0,
        "rc": 0,
        "extras": {
            "targets": targets,
            "burn_threshold": args.slo_burn_threshold,
            "fast_window_s": args.slo_fast_window,
            "slow_window_s": args.slo_slow_window,
            "disagg_baseline_ttft_p99_s": d["baseline"]["ttft_p99_s"],
            "disagg_baseline_itl_p99_s": d["baseline"]["itl_p99_s"],
            "colocated_baseline_ttft_p99_s":
                c["baseline"]["ttft_p99_s"],
            "colocated_baseline_itl_p99_s": c["baseline"]["itl_p99_s"],
            "disagg_breached": d["slo"]["breached"],
            "disagg_breach_pools": d["slo"]["breach_pools"],
            "disagg_recovered": d["slo"]["recovered"],
            "disagg_still_breaching": d["slo"]["still_breaching"],
            "disagg_breach_burns": d["slo"]["breach_burns"],
            "disagg_burn_fast_peak": d["slo"]["burn_fast_peak"],
            "recommendations": recs,
            "colocated_breached": c["slo"]["breached"],
            "colocated_breach_pools": c["slo"]["breach_pools"],
            "colocated_burn_fast_peak": c["slo"]["burn_fast_peak"],
            "disagg_itl_p99_burst_s": d["burst"]["itl_p99_s"],
            "colocated_itl_p99_burst_s": c["burst"]["itl_p99_s"],
            "disagg_ttft_p99_burst_s": d["burst"]["ttft_p99_s"],
            "colocated_ttft_p99_burst_s": c["burst"]["ttft_p99_s"],
            "handoffs": d["burst"]["handoffs"],
            "handoff_fallbacks": d["burst"]["handoff_fallbacks"],
            "finished": d["burst"]["finished"],
            "accepted": d["burst"]["accepted"],
            "colocated_finished": c["burst"]["finished"],
            "colocated_accepted": c["burst"]["accepted"],
            "ttft_mult": args.slo_ttft_mult,
            "itl_mult": args.slo_itl_mult,
            "donor_occupancy_below": args.slo_donor_occ,
            "cooldown_s": args.slo_cooldown,
            "kv_dtype": args.kv_dtype,
            "n_embd": args.disagg_n_embd,
            "prefill_replicas": args.prefill_replicas,
            "decode_replicas": args.decode_replicas,
            "steady": args.steady,
            "burst_prompts": args.burst_prompts,
            "burst_prompt_len": args.burst_prompt_len,
            "max_new": args.max_new,
            "slots": args.slots,
            "model": args.model,
            "synthetic": bool(args.synthetic),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2", choices=("gpt2", "llama"))
    ap.add_argument("--synthetic", action="store_true",
                    help="tiny random-init config (CPU-testable)")
    ap.add_argument("--policies", default="least_work,round_robin",
                    help="comma-separated routing policies to replay")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--burst", type=int, default=None,
                    help="arrivals submitted instantaneously at t=0 "
                         "(default: all of them)")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate for post-burst requests "
                         "(requests per second)")
    ap.add_argument("--max-pending", type=int, default=8)
    ap.add_argument("--max-dispatch", type=int, default=None,
                    help="per-replica dispatch window (default "
                         "2*slots). An instant burst sheds at least "
                         "requests - max_pending - replicas*window")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=64)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--eos", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--trip-after", type=int, default=3)
    ap.add_argument("--kill-at-step", type=int, default=None,
                    help="arm a mode='raise' ChaosMonkey: the target "
                         "replica dies after its K-th replay step")
    ap.add_argument("--kill-replica", default="r1")
    ap.add_argument("--process", action="store_true",
                    help="replicas as spawned OS processes "
                         "(fleet/proc.py) instead of threads; the "
                         "armed kill becomes an abrupt process exit "
                         "and migration runs off the dispatcher's "
                         "write-ahead journal. CPU-only for now (also "
                         "--disagg, --slo): this parent never touches "
                         "a JAX backend, but every child takes "
                         "whatever jax.devices() gives it, and a TPU "
                         "chip belongs to one process — two replicas "
                         "on TPU hardware collide (ROADMAP D6)")
    ap.add_argument("--disagg", action="store_true",
                    help="TTFT-vs-ITL interference A/B: a "
                         "disaggregated prefill/decode process fleet "
                         "vs a colocated one of the same size, each "
                         "replayed with and without a long-prefill "
                         "burst; reports the decode-ITL-p99 "
                         "interference ratio per mode")
    ap.add_argument("--prefill-replicas", type=int, default=1)
    ap.add_argument("--decode-replicas", type=int, default=2)
    ap.add_argument("--steady", type=int, default=6,
                    help="steady short-prompt decode requests per "
                         "--disagg replay (the ITL probe population)")
    ap.add_argument("--burst-prompts", type=int, default=3,
                    help="long-prefill requests injected mid-decode "
                         "on --disagg burst replays")
    ap.add_argument("--burst-prompt-len", type=int, default=96)
    ap.add_argument("--disagg-n-embd", type=int, default=None,
                    help="widen the synthetic gpt2 for --disagg so a "
                         "long prefill costs enough to measure")
    ap.add_argument("--kv-dtype", default="int8",
                    help="KV layout policy for the --disagg engines "
                         "(int8 makes each handed-off chain ~4x "
                         "smaller on the wire — PR 10's layout is "
                         "half of what makes disaggregation cheap)")
    ap.add_argument("--slo", action="store_true",
                    help="replay the --disagg interference trace with "
                         "the SLO engine + signal plane armed "
                         "(obs/slo.py, obs/signals.py): objectives "
                         "calibrated off the best clean no-burst "
                         "baseline, burn windows + breach events + "
                         "observe-only rebalance recommendations "
                         "recorded for BOTH modes")
    ap.add_argument("--slo-ttft-mult", type=float, default=3.0,
                    help="TTFT p99 objective = mult x the best "
                         "mode's no-burst baseline p99 (relative, so "
                         "the contract travels across machines)")
    ap.add_argument("--slo-itl-mult", type=float, default=1.5,
                    help="ITL p99 objective = mult x the best "
                         "mode's no-burst baseline p99")
    ap.add_argument("--slo-fast-window", type=float, default=1.5,
                    help="fast burn window (responsiveness + recovery)")
    ap.add_argument("--slo-slow-window", type=float, default=6.0,
                    help="slow burn window (the anti-flap gate)")
    ap.add_argument("--slo-burn-threshold", type=float, default=2.0)
    ap.add_argument("--slo-eval-interval", type=float, default=0.05)
    ap.add_argument("--slo-cooldown", type=float, default=1.0,
                    help="planner cooldown between recommendations")
    ap.add_argument("--slo-donor-occ", type=float, default=0.85,
                    help="planner donor-occupancy gate: only recommend "
                         "taking a replica from a pool whose EWMA "
                         "occupancy is below this")
    ap.add_argument("--slo-recovery-wait", type=float, default=15.0,
                    help="post-burst grace for the fast window to "
                         "clear and the planner to recommend the "
                         "revert")
    ap.add_argument("--steady-gap-s", type=float, default=0.1,
                    help="spacing between --disagg steady arrivals "
                         "(keeps the prefill pool periodically busy "
                         "in burst AND no-burst replays)")
    ap.add_argument("--burst-delay-s", type=float, default=0.1,
                    help="seconds into the steady decode at which the "
                         "--disagg burst lands (early enough that the "
                         "steady requests are still decoding)")
    ap.add_argument("--timeout-s", type=float, default=600.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="append the records to this artifacts JSON file")
    args = ap.parse_args()
    if args.burst is None:
        args.burst = args.requests

    from quintnet_tpu.core.runtime import enable_compilation_cache

    enable_compilation_cache()  # config only: touches no backend

    records = []
    if args.slo:
        records.append(run_slo(args))
        print(json.dumps(records[-1]))
    elif args.disagg:
        records.append(run_disagg(args))
        print(json.dumps(records[-1]))
    elif args.process:
        for policy in [p for p in args.policies.split(",") if p]:
            records.append(run_policy_process(args, policy))
            print(json.dumps(records[-1]))
    else:
        factory, vocab = build_factory(args)
        for policy in [p for p in args.policies.split(",") if p]:
            records.append(run_policy(args, policy, factory, vocab))
            print(json.dumps(records[-1]))

    if args.out:
        prev = []
        if os.path.exists(args.out):
            try:
                with open(args.out) as f:
                    loaded = json.load(f)
                prev = loaded if isinstance(loaded, list) else [loaded]
            except (OSError, json.JSONDecodeError):
                prev = []
        with open(args.out, "w") as f:
            json.dump(prev + records, f, indent=1)


if __name__ == "__main__":
    main()
