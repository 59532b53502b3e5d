"""Driver of the serving cells: one ``ServeEngine`` on one thread,
driven through ``submit`` and ``step``; every token is timed by the
benchmark itself through ``submit(..., on_token=...)``.

Two kinds of arrivals, from the cell's traffic file:

- ``backlog`` (a saturated cell): before every step the generator
  submits until ``max_slots`` requests wait, so the queue is never
  empty. The window opens once the first ``max_slots`` requests have
  their first token; what is judged is tokens delivered per second.
- ``gamma`` (an open loop at a fixed rate): requests fall due on a
  schedule drawn from the seed whether or not earlier ones finished.
  After ``lead_in_s`` of the same traffic the window opens; requests
  DUE inside it are the sample, every latency counts from the time a
  request was due, and after the window the run drains what is in
  flight for at most ``drain_s``: a request unfinished then has failed.

In a ``--trace 1`` run the profiler covers the last ``trace_seconds``
of the window and the host-clock per-layer numbers come from the
stretch before it.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from benchmarks.lib.harness import annotate


def make_params(gcfg, weights_dtype: str, seed: int):
    """GPT-2 weights on the device in ONE jitted call from the seed
    (lib/weights.py), the block matmuls already in the type they are
    served in: the engine's own ``quantize_params`` runs inside the
    call, and finds nothing left to cast at construction."""
    from benchmarks.lib.weights import seeded_params
    from quintnet_tpu.models.gpt2 import gpt2_init
    from quintnet_tpu.serve import gpt2_family
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    targets = gpt2_family(gcfg).weight_targets
    policy = make_weight_policy(weights_dtype)
    return seeded_params(
        lambda k: gpt2_init(k, gcfg), seed,
        finish=lambda p: quantize_params(
            p, present_targets(p, targets), policy))


def build_engine(cell_spec: Dict, gcfg, params):
    from quintnet_tpu.serve import ServeEngine, gpt2_family

    e = cell_spec["engine"]
    return ServeEngine(
        gpt2_family(gcfg), params, max_slots=int(e["max_slots"]),
        block_size=int(e["block_size"]), num_blocks=int(e["num_blocks"]),
        max_seq_len=int(e["max_seq_len"]), kv_dtype=e["kv_dtype"],
        weights_dtype=e["weights_dtype"], attn_kernel=e["attn_kernel"])


def engine_programs(engine):
    """(name, jitted program, arguments) of every program the engine
    serves with — each prefill bucket and the decode step — with the
    arguments ``ServeEngine.warmup`` gives them. For LOWERING (the
    compiler's byte plan, tools/aot_sizes.py); nothing is run."""
    import jax
    import jax.numpy as jnp

    key = jnp.asarray(jax.random.key_data(jax.random.key(0)))
    row = jnp.zeros((engine.table_width,), jnp.int32)
    zero = jnp.int32(0)
    pools = engine.pool.caches()
    for b, sentinel in engine._prefills.items():
        yield (f"prefill[{b}]", sentinel.fn,
               (engine.params, *pools, jnp.zeros((1, b), jnp.int32), zero,
                jnp.int32(1), row, zero, zero, key))
    yield ("decode", engine._decode.fn,
           (engine.params, *pools, jnp.asarray(engine._tok),
            jnp.asarray(engine._pos), jnp.asarray(engine._tables),
            jnp.asarray(engine._key_data)))


# ---------------------------------------------------------------------
# correctness: the paged programs' logits against the plain reference
# ---------------------------------------------------------------------
def verify_program(engine):
    """``Family.verify`` against the engine's own pool, jitted with the
    pool donated: the seam chip_smoke.paged_logits uses."""
    import jax

    pool, fam = engine.pool, engine.family

    def body(params, k_pool, v_pool, ids, starts, tail, tbl):
        return fam.verify(params, k_pool, v_pool, ids, starts, tail, tbl,
                          pool.block_size, tp_axis=None, kv_scales=None,
                          policy=pool.policy,
                          attn_kernel=engine.attn_kernel)

    return jax.jit(body, donate_argnums=(1, 2))


def paged_logits(engine, rows, lens, half: int):
    """Logits at every position of ``rows`` [S, 2*half] from the PAGED
    programs, in two calls of :func:`verify_program`: positions
    [0, half) first — a prefill into fresh blocks — then
    [half, 2*half), which attend to the first call's keys and values
    THROUGH the block table, as a decode step does. One compiled
    program serves both calls."""
    import jax.numpy as jnp
    import numpy as np

    pool = engine.pool
    S, width = rows.shape
    need = pool.blocks_for(width)
    tables = np.zeros((S, engine.table_width), np.int32)
    held = []
    for s in range(S):
        got = pool.acquire(need)
        if got is None:
            raise RuntimeError(f"pool cannot hold {S} rows of {width}")
        tables[s, :need] = got
        held.append(got)
    fn = verify_program(engine)
    out = []
    for lo in (0, half):
        starts = np.full((S,), lo, np.int32)
        tail = np.clip(np.asarray(lens) - lo, 0, half).astype(np.int32)
        logits, *pools = fn(engine.params, *pool.caches(),
                            jnp.asarray(rows[:, lo:lo + half]),
                            jnp.asarray(starts), jnp.asarray(tail),
                            jnp.asarray(tables))
        pool.update(*pools)
        out.append(logits.astype(jnp.float32))
    for blocks in held:
        pool.release(blocks)
    return jnp.concatenate(out, axis=1)


def check_logits(engine, gcfg, spec: Dict, seed: int) -> Dict:
    import numpy as np

    from benchmarks.lib import reference

    c = spec["correctness"]
    lens = [int(n) for n in c["prompt_lens"]]
    half = int(c["half_width"])
    if engine.pool.policy.scaled or engine.mesh is not None:
        raise NotImplementedError(
            "the logits check drives an unscaled KV pool on one device")
    if max(lens) > 2 * half or min(lens) <= half:
        raise ValueError(f"prompt_lens {lens} must lie in ({half}, "
                         f"{2 * half}]: both calls must score real "
                         f"positions of every row")
    rng = np.random.default_rng([seed, 5])
    rows = rng.integers(0, gcfg.vocab_size,
                        (len(lens), 2 * half)).astype(np.int32)
    got = np.asarray(paged_logits(engine, rows, lens, half))
    want = np.asarray(reference.forward(
        engine.params, rows, n_head=gcfg.n_head,
        vocab_size=gcfg.vocab_size))
    gap, spread = 0.0, 0.0
    for s, n in enumerate(lens):
        x, y = got[s, :n], want[s, :n]
        if not (np.isfinite(x).all() and np.isfinite(y).all()):
            return {"ok": False, "why": "non-finite logits"}
        gap = max(gap, float(np.abs(x - y).max()))
        spread = max(spread, float(y.std()))
    tol = float(c["logits_tolerance"])
    return {"ok": gap <= tol, "max_abs_diff": gap, "ref_std": spread,
            "tolerance": tol, "positions": lens}


# ---------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------
class _Serving:
    def __init__(self, engine, stream):
        self.engine, self.stream = engine, stream
        self.tokens: Dict[int, List[float]] = {}
        self.done: Dict[int, float] = {}
        self.reqs: Dict[int, object] = {}      # rid -> traffic.Req
        self.due: Dict[int, float] = {}        # rid -> due (open loop)
        self.submitted: Dict[int, float] = {}  # rid -> submit time
        self.refused = 0
        self.steps: List[tuple] = []           # (start, end, running)

    def on_token(self, rid: int, _token: int, last: bool) -> None:
        now = time.perf_counter()
        self.tokens.setdefault(rid, []).append(now)
        if last:
            self.done[rid] = now

    def submit(self, req, due: Optional[float] = None) -> None:
        with annotate("submit"):
            try:
                rid = self.engine.submit(req.prompt, req.max_new,
                                         on_token=self.on_token)
            except ValueError:
                self.refused += 1
                return
        self.reqs[rid] = req
        self.submitted[rid] = time.perf_counter()
        if due is not None:
            self.due[rid] = due

    def step(self) -> None:
        t0 = time.perf_counter()
        with annotate("engine_step"):
            self.engine.step()
        self.steps.append((t0, time.perf_counter(),
                           self.engine.metrics.running))

    def errored(self) -> int:
        return sum(1 for rid in self.reqs
                   if self.engine.request(rid).error is not None)

    def counts_add_up(self) -> Dict:
        """Every finished request got exactly the tokens it asked for
        (no EOS is set), none got more, and the engine's own count of
        generated tokens is the number the callbacks saw."""
        short = [rid for rid in self.done
                 if len(self.tokens[rid]) != self.reqs[rid].max_new]
        over = [rid for rid, ts in self.tokens.items()
                if len(ts) > self.reqs[rid].max_new]
        seen = sum(len(ts) for ts in self.tokens.values())
        counted = self.engine.metrics.summary()["gen_tokens"]
        return {"ok": not short and not over and seen == counted,
                "delivered": seen, "engine_counted": counted,
                "wrong_length": len(short) + len(over)}


def _run_backlog(sv: _Serving, ctx, tracer) -> Dict:
    slots = sv.engine.max_slots

    def top_up():
        while len(sv.engine.scheduler.waiting) < slots:
            sv.submit(next(sv.stream))

    while len(sv.tokens) < slots:                # fill: part of set-up
        top_up()
        sv.step()
    t0 = time.perf_counter()
    compiles0 = ctx.meter.compiles
    plain = ctx.seconds - (ctx.trace_seconds if ctx.trace else 0.0)
    while time.perf_counter() - t0 < plain:
        top_up()
        sv.step()
    t1 = time.perf_counter()
    reduced, traced = None, (t1, t1)
    if tracer is not None:
        tracer.start()
        ts = time.perf_counter()
        while time.perf_counter() - ts < ctx.trace_seconds:
            top_up()
            sv.step()
        traced = (ts, time.perf_counter())
        reduced = tracer.stop()
    return {"t0": t0, "t1": t1, "trace": reduced, "traced": traced,
            "sample": None, "compiles": ctx.meter.compiles - compiles0}


def _run_open_loop(sv: _Serving, ctx, tracer) -> Dict:
    arr = ctx.cell.traffic["arrivals"]
    lead = float(arr.get("lead_in_s", 0.0))
    drain_s = float(arr.get("drain_s", 15.0))
    start = time.perf_counter()
    t0 = start + lead
    t_end = t0 + ctx.seconds
    t1 = t_end - (ctx.trace_seconds if ctx.trace else 0.0)
    pending = next(sv.stream)
    reduced, traced = None, (t1, t1)
    tracing = False
    compiles0 = None
    while True:
        now = time.perf_counter()
        if compiles0 is None and now >= t0:
            compiles0 = ctx.meter.compiles     # the lead-in is set-up
        if tracer is not None and not tracing and now >= t1:
            tracer.start()
            tracing = True
            traced = (time.perf_counter(), t_end)
            now = time.perf_counter()
        while start + pending.due_s <= min(now, t_end):
            sv.submit(pending, due=start + pending.due_s)
            pending = next(sv.stream)
        if now >= t_end and tracing:
            reduced = tracer.stop()
            tracing = False
            tracer = None
        if sv.engine.has_work:
            sv.step()
        elif now >= t_end:
            break
        else:
            time.sleep(max(0.0, min(0.001, start + pending.due_s - now)))
        if now >= t_end + drain_s:
            break
    if tracing:
        reduced = tracer.stop()
    sample = [rid for rid, due in sv.due.items() if t0 <= due < t1]
    return {"t0": t0, "t1": t1, "trace": reduced, "traced": traced,
            "sample": sample, "compiles": ctx.meter.compiles - compiles0}


def run(ctx) -> Dict:
    from benchmarks.lib import stats, traffic
    from benchmarks.lib.harness import DeviceTrace
    from quintnet_tpu.models.gpt2 import GPT2Config

    import jax

    spec = ctx.cell.spec
    gcfg = GPT2Config.from_dict(ctx.cell.config)
    t_a = time.perf_counter()
    params = make_params(gcfg, spec["engine"]["weights_dtype"], ctx.seed)
    jax.block_until_ready(params)
    t_b = time.perf_counter()
    engine = build_engine(spec, gcfg, params)
    del params
    engine.warmup()
    jax.block_until_ready(engine.pool.caches())
    t_c = time.perf_counter()
    checks = {"logits_vs_reference": check_logits(engine, gcfg, spec,
                                                  ctx.seed)}
    t_d = time.perf_counter()

    sv = _Serving(engine, traffic.requests(ctx.cell.traffic,
                                           gcfg.vocab_size, ctx.seed))
    tracer = DeviceTrace(ctx) if ctx.trace else None
    kind = ctx.cell.traffic["arrivals"]["kind"]
    if kind == "backlog":
        w = _run_backlog(sv, ctx, tracer)
    else:
        w = _run_open_loop(sv, ctx, tracer)
    checks["no_compile_in_window"] = {"ok": w["compiles"] == 0,
                                      "compiles": w["compiles"]}
    checks["token_counts"] = sv.counts_add_up()

    t0, t1 = w["t0"], w["t1"]
    window = t1 - t0
    in_window = [t for ts in sv.tokens.values() for t in ts if t0 <= t <= t1]
    finished = sum(1 for t in sv.done.values() if t0 <= t <= t1)
    steps = [s for s in sv.steps if t0 <= s[0] and s[1] <= t1]
    traced_steps = [s for s in sv.steps
                    if w["traced"][0] <= s[0] and s[1] <= w["traced"][1]]
    m = engine.metrics
    end_to_end: Dict[str, float] = {}
    info = {"window_s": window, "steps": len(steps),
            "tokens": len(in_window), "finished": finished,
            "finished_rps": finished / window,
            "submitted": len(sv.reqs), "refused": sv.refused,
            "preempted": m.preempted, "prefill_tokens": m.prefill_tokens,
            "decode_tokens": m.decode_tokens,
            "prefix_hit_tokens": m.prefix_hit_tokens,
            "compiled_programs": engine.compile_stats(),
            "setup_parts_s": {"to_driver": t_a - ctx.t_process_start,
                              "weights": t_b - t_a,
                              "engine_warmup": t_c - t_b,
                              "logits_check": t_d - t_c,
                              "fill": w["t0"] - t_d}}
    lat = {"ttft": [], "gaps": [], "late": []}
    if w["sample"] is None:
        attempted = len(sv.reqs) + sv.refused
        failed = sv.refused + sv.errored()
        end_to_end["serve_tok_s"] = len(in_window) / window
    else:
        sample = w["sample"]
        got = stats.request_latencies(
            {rid: sv.due[rid] for rid in sample}, sv.tokens)
        lat = {"ttft": got["ttft"], "gaps": got["gaps"],
               "late": [sv.submitted[rid] - sv.due[rid] for rid in sample]}
        unfinished = sum(1 for rid in sample if rid not in sv.done)
        attempted = len(sample) + sv.refused
        failed = sv.refused + unfinished + sum(
            1 for rid in sample
            if rid in sv.done and engine.request(rid).error is not None)
        if lat["ttft"] and lat["gaps"]:
            end_to_end["ttft_p90_ms"] = 1e3 * stats.percentile(
                lat["ttft"], 90)
            end_to_end["gap_p95_ms"] = 1e3 * stats.percentile(
                lat["gaps"], 95)
            info.update({
                "sample": len(sample), "unfinished": unfinished,
                "ttft_p50_ms": 1e3 * stats.percentile(lat["ttft"], 50),
                "gap_p50_ms": 1e3 * stats.percentile(lat["gaps"], 50),
                "gaps": len(lat["gaps"]),
                "late_p95_ms": 1e3 * stats.percentile(lat["late"], 95)})
    ctx.info({"serve": {**info, "checks": checks}})
    return {
        "checks": checks, "attempted": attempted, "failed": failed,
        "setup_s": t0 - ctx.t_process_start,
        "end_to_end": end_to_end,
        "context": {
            "window_s": window, "engine_steps": steps,
            "max_slots": engine.max_slots, "latencies": lat,
            "devices": ctx.devices,
            "device_kind": ctx.devices[0].device_kind,
            "trace": w["trace"], "traced_steps": len(traced_steps),
            "steps": len(steps),
            "counters": {"prefill_tokens": m.prefill_tokens,
                         "decode_tokens": m.decode_tokens,
                         "prefix_hit_tokens": m.prefix_hit_tokens,
                         "preempted": m.preempted}},
    }
