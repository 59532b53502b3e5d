"""Driver of the serving cells of a RECURRENT family (Granite 4.0-H:
Mamba-2 layers with an attention layer among every few): the same
``ServeEngine`` on one thread, the same loop, window and counts as
``drivers/serve.py`` — ``_Serving`` and ``_run_backlog`` are imported
from it, not copied, so ``serve_tok_s``, the fill and ``token_counts``
mean what they mean in the GPT-2 XL cell. What differs is what is
built (the family, its seeded weights with the published state-space
initialisation, the engine with ``prefix_cache`` off) and the check
against the reference, which has to go through the recurrent state.

The model's modules are imported as this file is loaded: a checkout
that lacks them (the parent of the PR that added the configuration)
fails here, before the chip is touched.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmarks.drivers.serve import _Serving, _run_backlog
from quintnet_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                granite_hybrid_init)
from quintnet_tpu.serve import ServeEngine, granite_hybrid_family


def make_params(cfg, weights_dtype: str, seed: int):
    """The family's parameter tree on the device in ONE jitted call from
    the seed (lib/weights.py), then, in the same call: ``A_log``,
    ``dt_bias``, ``D`` and the depthwise conv's weight of every Mamba-2
    layer as published (nn/ssm.py: a step's decay in 0.2-0.999, so
    state carries over hundreds of positions and a stale or forgotten
    state shows in the logits), and the block matmuls packed into the
    type they are served in. The published draws are uniform; they are
    taken from the seeded normal(0, 0.02) leaves through the normal's
    own distribution function, so the seed stays an ARGUMENT of the
    compiled call: one compile serves every seed."""
    from jax.scipy.stats import norm

    from benchmarks.lib.weights import seeded_params
    from quintnet_tpu.nn.ssm import conv_published, ssm_published
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    targets = granite_hybrid_family(cfg).weight_targets
    policy = make_weight_policy(weights_dtype)

    def uniform(leaf):
        return norm.cdf(leaf / 0.02)

    def finish(p):
        mamba = p["blocks"]["mamba"]
        old = mamba["mixer"]
        mixer = {
            **old,
            **ssm_published(uniform(old["A_log"]), uniform(old["dt_bias"])),
            "conv": {**old["conv"],
                     "w": conv_published(uniform(old["conv"]["w"]),
                                         cfg.mamba_d_conv)}}
        p = {**p, "blocks": {**p["blocks"],
                             "mamba": {**mamba, "mixer": mixer}}}
        return quantize_params(p, present_targets(p, targets), policy)

    return seeded_params(lambda k: granite_hybrid_init(k, cfg), seed,
                         finish=finish)


def build_engine(cell_spec: Dict, cfg, params):
    e = cell_spec["engine"]
    return ServeEngine(
        granite_hybrid_family(cfg), params, max_slots=int(e["max_slots"]),
        block_size=int(e["block_size"]), num_blocks=int(e["num_blocks"]),
        max_seq_len=int(e["max_seq_len"]), kv_dtype=e["kv_dtype"],
        weights_dtype=e["weights_dtype"], attn_kernel=e["attn_kernel"],
        prefix_cache=bool(e["prefix_cache"]))


# ---------------------------------------------------------------------
# correctness: chunk program, then decode program, against the reference
# ---------------------------------------------------------------------
def check_programs(engine):
    """The family's own ``prefill_from`` and ``decode`` jitted against
    the engine's own pools and state buffers, all four donated (the
    seam drivers/serve.verify_program uses): (prefill(params, *bufs,
    ids, start, t0, row, slot) -> (logits [1, V], *bufs),
    decode(params, *bufs, tok, pos, tables, rows) -> (logits of
    ``rows``, *bufs))."""
    import jax

    pool, fam = engine.pool, engine.family

    def prefill(params, k, v, ssm, conv, ids, start, t0, row, slot):
        return fam.prefill_from(params, k, v, ids, start, t0, row,
                                pool.block_size, policy=pool.policy,
                                attn_kernel=engine.attn_kernel,
                                state=(ssm, conv), slot=slot)

    def decode(params, k, v, ssm, conv, tok, pos, tables, rows):
        logits, *bufs = fam.decode(params, k, v, tok, pos, tables,
                                   pool.block_size, policy=pool.policy,
                                   attn_kernel=engine.attn_kernel,
                                   state=(ssm, conv))
        return (logits[rows], *bufs)

    return (jax.jit(prefill, donate_argnums=(1, 2, 3, 4)),
            jax.jit(decode, donate_argnums=(1, 2, 3, 4)))


def paged_logits(engine, rows, lens, calls):
    """Logits of ``rows`` [n, T] from the PAGED programs at the engine's
    own shapes (``max_slots`` rows, the first ``n`` of them live): the
    first ``sum(calls)`` positions of each row through the chunk
    program in ``len(calls)`` calls of one bucket width (the state
    carried from call to call, the later calls right-padded), then
    EVERY remaining position through the decode program, one token a
    step, teacher-forced, through the state and the block table; a row
    that has reached its length rides on as an inactive one. Returns
    (logits [n, T - sum(calls) + 1, V] f32: the chunk program's at its
    last position, then each decode step's; the SSM state the pool
    holds for the ``n`` rows afterwards, [Mamba layers, n, heads, P, N]:
    each row's after its own last position)."""
    import jax.numpy as jnp
    import numpy as np

    pool = engine.pool
    n, width = rows.shape
    need = pool.blocks_for(width)
    tables = np.zeros((engine.max_slots, engine.table_width), np.int32)
    held = []
    for s in range(n):
        got = pool.acquire(need)
        if got is None:
            raise RuntimeError(f"pool cannot hold {n} rows of {width}")
        tables[s, :need] = got
        held.append(got)
    prefill, decode = check_programs(engine)
    bucket, done = max(calls), sum(calls)
    first = []
    for s in range(n):
        lo = 0
        for m in calls:
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :m] = rows[s, lo:lo + m]
            logits, *bufs = prefill(
                engine.params, *pool.caches(), jnp.asarray(ids),
                jnp.int32(lo), jnp.int32(lo + m), jnp.asarray(tables[s]),
                jnp.int32(s))
            pool.update(*bufs)
            lo += m
        first.append(logits[0].astype(jnp.float32))
    out = [jnp.stack(first)]
    live = jnp.arange(n)
    for pos in range(done, width):
        on = np.asarray(lens) > pos
        tok = np.zeros((engine.max_slots,), np.int32)
        at = np.zeros((engine.max_slots,), np.int32)
        tok[:n] = rows[:, pos] * on
        at[:n] = pos * on
        step_tables = tables.copy()
        step_tables[:n][~on] = 0
        logits, *bufs = decode(
            engine.params, *pool.caches(), jnp.asarray(tok),
            jnp.asarray(at), jnp.asarray(step_tables), live)
        pool.update(*bufs)
        out.append(logits.astype(jnp.float32))
    for blocks in held:
        pool.release(blocks)
    return jnp.stack(out, axis=1), pool.caches()[2][:, :n]


def check_logits(engine, config: Dict, spec: Dict, seed: int, *,
                 reference_params=None) -> Dict:
    """``reference_params``: the weights the reference computes with,
    where they are not the engine's own (tools/hybrid_probe.py holds an
    int8 engine to the reference on the stated bf16 weights)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import reference_granite_hybrid as reference

    c = spec["correctness"]
    lens = [int(x) for x in c["prompt_lens"]]
    calls = [int(x) for x in c["chunk_calls"]]
    done = sum(calls)
    if min(lens) <= done or len(lens) > engine.max_slots:
        raise ValueError(f"prompt_lens {lens} must all pass the "
                         f"{done} positions the chunk calls cover, on "
                         f"at most max_slots rows")
    rng = np.random.default_rng([seed, 5])
    rows = rng.integers(0, config["vocab_size"],
                        (len(lens), max(lens))).astype(np.int32)
    got, got_state = paged_logits(engine, rows, lens, calls)
    # got[:, 0] is the chunk program's at position done - 1, got[:, i]
    # the decode step's at position done - 1 + i: the reference's
    # logits at positions done - 1 .. T - 1 (one layer cast up at a
    # time), each row's up to its own last
    want, want_state = reference.forward(
        engine.params if reference_params is None else reference_params,
        rows, config, positions=list(range(done - 1, max(lens))),
        lengths=lens)
    real = jnp.asarray([[done - 1 + i <= n - 1
                         for i in range(want.shape[1])] for n in lens])
    if not bool(jnp.isfinite(got).all() & jnp.isfinite(want).all()
                & jnp.isfinite(got_state).all()):
        return {"ok": False, "why": "non-finite logits or state"}
    diff = jnp.where(real[:, :, None], jnp.abs(got - want), 0.0)
    per_step = np.asarray(diff.max(axis=(0, 2)))
    gap = float(per_step.max())
    tol = float(c["logits_tolerance"])
    # the state itself, in the FIRST state-space layer: each head's
    # relative distance from the reference's, the mean over heads and
    # rows. The logits average a state's rounding out (64 x 128 entries
    # a head behind one inner product), the state left in the pool does
    # not; the first layer's inputs are the token table's rows, exact
    # on both sides, so what the program's own arithmetic adds there is
    # small beside what a narrower state adds (deeper layers inherit
    # the matrix unit's rounding of every layer before them, which is
    # larger than either: by_layer, each layer's largest head, is kept
    # for the record); and a mean over 128 heads hardly moves with the
    # seed, where the largest head does
    by_head = np.asarray(
        jnp.sqrt(jnp.sum((got_state - want_state) ** 2, axis=(-1, -2))
                 / jnp.sum(want_state ** 2, axis=(-1, -2))))
    state_gap = float(by_head[0].mean())
    state_tol = float(c["state_tolerance"])
    return {"ok": gap <= tol and state_gap <= state_tol,
            "max_abs_diff": gap, "at_chunk_end": float(per_step[0]),
            "at_last_step": float(per_step[-1]),
            "ref_std": float(jnp.std(want[0])), "tolerance": tol,
            "state_rel_err": state_gap, "state_tolerance": state_tol,
            "state_rel_err_max_head": float(by_head[0].max()),
            "state_rel_err_by_layer": [
                round(float(x), 5) for x in by_head.max(axis=(1, 2))],
            "positions": lens, "chunk_calls": calls,
            "decode_steps": int(want.shape[1]) - 1}


# ---------------------------------------------------------------------
def run(ctx) -> Dict:
    import jax

    from benchmarks.lib import traffic
    from benchmarks.lib.harness import DeviceTrace

    spec = ctx.cell.spec
    if ctx.cell.traffic["arrivals"]["kind"] != "backlog":
        raise NotImplementedError(
            "drivers/serve_hybrid.py runs standing-backlog cells; an "
            "open loop needs drivers/serve.py's latency accounting")
    cfg = GraniteHybridConfig.from_dict(ctx.cell.config)
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

    sv = _Serving(engine, traffic.requests(ctx.cell.traffic,
                                           cfg.vocab_size, ctx.seed))
    w = _run_backlog(sv, ctx, DeviceTrace(ctx) if ctx.trace else None)
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
    ring = [r for r in engine.recorder.snapshot()
            if t0 <= r["t0"] and r["t1"] <= t1]
    ctx.info({"serve": {
        "window_s": window, "steps": len(steps), "tokens": len(in_window),
        "finished": finished, "finished_rps": finished / window,
        "submitted": len(sv.reqs), "refused": sv.refused,
        "preempted": m.preempted, "prefill_tokens": m.prefill_tokens,
        "decode_tokens": m.decode_tokens,
        "compiled_programs": engine.compile_stats(),
        "state_bytes_per_slot": engine.pool.state_bytes_per_slot,
        "kv_bytes_per_token": engine.pool.bytes_per_token,
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
