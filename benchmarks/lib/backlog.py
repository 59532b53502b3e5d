"""What the standing-backlog cells of a mixture-of-experts serving
family share, whatever the family: how the first ``max_slots`` requests
of a mix are cut (``initial_remaining``), and the run itself — build,
warm up, check, the window, the info line and the result — around the
three things a family's driver brings: its parameters, its engine and
its check. (drivers/serve_moe_mla.py and drivers/serve_window_moe.py
carry copies of :func:`run_family`'s body from before this file: PERF.md
section 7.)
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Dict, Iterator, Sequence


def staggered(stream: Iterator, traffic: Dict, slots: int,
              seed: int) -> Iterator:
    """``initial_remaining: "uniform"`` of a traffic file: the first
    ``slots`` requests keep a SEEDED uniform share of their drawn output
    length (at least one token), so the window opens on rows at every
    stage of an answer and completions do not come in waves; every later
    request runs its whole length. The shares are the ``slots`` equal
    strata of (0, 1), dealt over the requests by the seed — as
    lib/traffic.py deals a block's strata of lengths."""
    import numpy as np

    how = traffic.get("initial_remaining")
    if how is None:
        yield from stream
        return
    if how != "uniform":
        raise ValueError(f"unknown initial_remaining {how!r}")
    order = np.random.default_rng([int(seed), 7]).permutation(slots)
    for req, m in zip(itertools.islice(stream, slots), order):
        yield dataclasses.replace(req, max_new=max(1, int(round(
            (m + 0.5) / slots * req.max_new))))
    yield from stream


def run_family(ctx, cfg, *, make_params: Callable, build_engine: Callable,
               check_logits: Callable,
               decode_attrs: Sequence[str] = ()) -> Dict:
    """One run of a standing-backlog cell: ``make_params(cfg, dtype,
    seed)``, ``build_engine(spec, cfg, params)``, ``warmup()``,
    ``check_logits(engine, config, spec, seed)``, then the window of
    drivers/serve.py (``_Serving`` and ``_run_backlog`` are imported
    from it, not copied, so ``serve_tok_s``, the fill and
    ``token_counts`` mean what they mean in the GPT-2 XL cell).
    ``decode_attrs``: further per-step attrs of the family's decode
    steps to average into the info line's ``decode_means``."""
    import jax

    from benchmarks.drivers.serve import _Serving, _run_backlog
    from benchmarks.lib import traffic
    from benchmarks.lib.harness import DeviceTrace

    spec = ctx.cell.spec
    if ctx.cell.traffic["arrivals"]["kind"] != "backlog":
        raise NotImplementedError(
            "lib/backlog.py runs standing-backlog cells; an open loop "
            "needs drivers/serve.py's latency accounting")
    t_a = time.perf_counter()
    params = make_params(cfg, spec["engine"]["weights_dtype"], ctx.seed)
    jax.block_until_ready(params)
    t_b = time.perf_counter()
    engine = build_engine(spec, cfg, params)
    del params
    engine.warmup()
    jax.block_until_ready(engine.pool.caches())
    t_c = time.perf_counter()
    checks = {"logits_vs_reference": check_logits(
        engine, ctx.cell.config, spec, ctx.seed)}
    t_d = time.perf_counter()

    sv = _Serving(engine, staggered(
        traffic.requests(ctx.cell.traffic, cfg.vocab_size, ctx.seed),
        ctx.cell.traffic, engine.max_slots, ctx.seed))
    w = _run_backlog(sv, ctx, DeviceTrace(ctx) if ctx.trace else None)
    checks["no_compile_in_window"] = {"ok": w["compiles"] == 0,
                                      "compiles": w["compiles"]}
    checks["token_counts"] = sv.counts_add_up()
    m = engine.metrics
    # the dropless router: no routing is dropped in any step, ever
    checks["no_dropped_routing"] = {
        "ok": m.moe_dropped_tokens == 0,
        "dropped": m.moe_dropped_tokens, "routed": m.moe_routed_tokens}

    t0, t1 = w["t0"], w["t1"]
    window = t1 - t0
    in_window = [t for ts in sv.tokens.values() for t in ts if t0 <= t <= t1]
    finished = sum(1 for t in sv.done.values() if t0 <= t <= t1)
    steps = [s for s in sv.steps if t0 <= s[0] and s[1] <= t1]
    traced_steps = [s for s in sv.steps
                    if w["traced"][0] <= s[0] and s[1] <= w["traced"][1]]
    ring = [r for r in engine.recorder.snapshot()
            if t0 <= r["t0"] and r["t1"] <= t1]
    dec = [r for r in ring if r["decoding"]]

    def mean(get):
        return sum(get(r) for r in dec) / len(dec)

    ctx.info({"serve": {
        "window_s": window, "steps": len(steps), "tokens": len(in_window),
        "finished": finished, "finished_rps": finished / window,
        "submitted": len(sv.reqs), "refused": sv.refused,
        "preempted": m.preempted, "prefill_tokens": m.prefill_tokens,
        "decode_tokens": m.decode_tokens,
        "prefill_chunks": m.prefill_chunks,
        "compiled_programs": engine.compile_stats(),
        "kv_bytes_per_token": engine.pool.bytes_per_token,
        "state_bytes_per_slot": engine.pool.state_bytes_per_slot,
        "kv_blocks_used_max": max((r["kv_blocks_used"] for r in ring),
                                  default=0),
        "kv_blocks_used_at_end": ring[-1]["kv_blocks_used"] if ring else 0,
        "kv_blocks_total": engine.pool.usable_blocks,
        # means over the window's steps that decoded: rows a step,
        # positions they hold, held experts that received a row (of
        # MoE layers x experts held), the rows those received, and
        # what else the family counts a step
        "decode_means": {
            "rows": mean(lambda r: r["decoding"]),
            "context_tokens": mean(lambda r: r["context_tokens"]),
            "experts_touched": mean(
                lambda r: r["attrs"]["decode_experts_touched"]),
            "expert_rows": mean(lambda r: r["attrs"]["decode_expert_rows"]),
            **{k: mean(lambda r, k=k: r["attrs"][k]) for k in decode_attrs},
        } if dec else None,
        # where the host's time went, from the engine's own ring: mean
        # ms a step by phase over the window (wait = the device's time)
        "phase_ms_a_step": {
            k: 1e3 * sum(r["phases"].get(k, 0.0) for r in ring) / len(ring)
            for k in sorted({k for r in ring for k in r["phases"]})}
        if ring else None,
        "setup_parts_s": {"to_driver": t_a - ctx.t_process_start,
                          "weights": t_b - t_a,
                          "engine_warmup": t_c - t_b,
                          "logits_check": t_d - t_c,
                          "fill": w["t0"] - t_d},
        "checks": checks}})
    return {
        "checks": checks, "attempted": len(sv.reqs) + sv.refused,
        "failed": sv.refused + sv.errored(),
        "setup_s": t0 - ctx.t_process_start,
        "end_to_end": {"serve_tok_s": len(in_window) / window},
        "context": {
            "window_s": window, "engine_steps": steps,
            "max_slots": engine.max_slots,
            "latencies": {"ttft": [], "gaps": [], "late": []},
            "devices": ctx.devices,
            "device_kind": ctx.devices[0].device_kind,
            "trace": w["trace"], "traced_steps": len(traced_steps),
            "steps": len(steps), "model": ctx.cell.config,
            "counters": {"prefill_tokens": m.prefill_tokens,
                         "decode_tokens": m.decode_tokens,
                         "prefix_hit_tokens": m.prefix_hit_tokens,
                         "preempted": m.preempted}},
    }
