"""Driver of the serving cells of a LATENT-attention mixture-of-experts
family (openPangu-Ultra-MoE, one chip's share of it): the same
``ServeEngine`` on one thread, the same loop, window and counts as
``drivers/serve.py`` — ``_Serving`` and ``_run_backlog`` are imported
from it, not copied, so ``serve_tok_s``, the fill and ``token_counts``
mean what they mean in the GPT-2 XL cell. What differs is what is
built (the family, its seeded weights packed as they are served, the
engine with chunked prefill on) and the check against the reference,
which goes through the paged latent cache in both of its forms.

The model's modules are imported as this file is loaded: a checkout
that lacks them (the parent of the PR that added the configuration)
fails here, before the chip is touched.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmarks.drivers.serve import _Serving, _run_backlog
from quintnet_tpu.models.pangu_moe import PanguMoEConfig, pangu_moe_init
from quintnet_tpu.serve import ServeEngine, pangu_moe_family


def make_params(cfg, weights_dtype: str, seed: int, finish=None):
    """The family's parameter tree on the device in ONE jitted call from
    the seed (lib/weights.py), the block matmuls — the held experts
    among them — packed into the type they are served in inside the
    same call. ``finish`` (tools/moe_mla_probe.py's controls) runs on
    the tree before the packing."""
    from benchmarks.lib.weights import seeded_params
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    targets = pangu_moe_family(cfg).weight_targets
    policy = make_weight_policy(weights_dtype)

    def pack(p):
        if finish is not None:
            p = finish(p)
        return quantize_params(p, present_targets(p, targets), policy)

    return seeded_params(lambda k: pangu_moe_init(k, cfg), seed,
                         finish=pack)


def build_engine(cell_spec: Dict, cfg, params):
    e = cell_spec["engine"]
    return ServeEngine(
        pangu_moe_family(cfg), params, max_slots=int(e["max_slots"]),
        block_size=int(e["block_size"]), num_blocks=int(e["num_blocks"]),
        max_seq_len=int(e["max_seq_len"]),
        prefill_len=int(e["prefill_len"]),
        chunked_prefill=bool(e["chunked_prefill"]),
        kv_dtype=e["kv_dtype"], weights_dtype=e["weights_dtype"],
        attn_kernel=e["attn_kernel"], prefix_cache=bool(e["prefix_cache"]))


# ---------------------------------------------------------------------
# correctness: prefill bucket, a second chunk call, then the decode
# program, against the reference
# ---------------------------------------------------------------------
def check_programs(engine):
    """The family's own ``prefill_from`` (materialized) and ``decode``
    (absorbed) jitted against the engine's own latent pool, donated
    (the seam drivers/serve.verify_program uses): (prefill(params, k,
    ids, start, t0, row) -> (logits [1, V], k, stats), decode(params,
    k, tok, pos, tables, rows) -> (logits of ``rows``, k, stats))."""
    import jax

    pool, fam = engine.pool, engine.family

    def prefill(params, k, ids, start, t0, row):
        return fam.prefill_from(params, k, None, ids, start, t0, row,
                                pool.block_size, policy=pool.policy,
                                attn_kernel=engine.attn_kernel)

    def decode(params, k, tok, pos, tables, rows):
        logits, k, stats = fam.decode(
            params, k, None, tok, pos, tables, pool.block_size,
            policy=pool.policy, attn_kernel=engine.attn_kernel)
        return logits[rows], k, stats

    return (jax.jit(prefill, donate_argnums=(1,)),
            jax.jit(decode, donate_argnums=(1,)))


def paged_logits(engine, rows, lens, calls):
    """Logits of ``rows`` [n, T] from the PAGED programs at the engine's
    own shapes (``max_slots`` rows, the first ``n`` of them live): the
    first ``sum(calls)`` positions of each row through the prefill
    program in ``len(calls)`` calls of one bucket width — the later
    calls are CHUNK calls: they start past 0 and rebuild keys and values
    from the latent rows the earlier ones left in the pool — then EVERY
    remaining position through the decode program, one token a step,
    teacher-forced through the block table; a row that has reached its
    length rides on as an inactive one. Returns (logits [n, T -
    sum(calls) + 1, V] f32: the last prefill call's at its last
    position, then each decode step's; per decode step the routing
    counts over all the router's experts [steps, E] and whether any
    program reported a dropped routing)."""
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
    first, dropped = [], 0.0
    for s in range(n):
        lo = 0
        for m in calls:
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :m] = rows[s, lo:lo + m]
            logits, k, stats = prefill(
                engine.params, *pool.caches(), jnp.asarray(ids),
                jnp.int32(lo), jnp.int32(lo + m), jnp.asarray(tables[s]))
            pool.update(k)
            dropped += float(stats["dropped"])
            lo += m
        first.append(logits[0].astype(jnp.float32))
    out, routed = [jnp.stack(first)], []
    live = jnp.arange(n)
    for pos in range(done, width):
        on = np.asarray(lens) > pos
        tok = np.zeros((engine.max_slots,), np.int32)
        at = np.zeros((engine.max_slots,), np.int32)
        tok[:n] = rows[:, pos] * on
        at[:n] = pos * on
        step_tables = tables.copy()
        step_tables[:n][~on] = 0
        logits, k, stats = decode(
            engine.params, *pool.caches(), jnp.asarray(tok),
            jnp.asarray(at), jnp.asarray(step_tables), live)
        pool.update(k)
        out.append(logits.astype(jnp.float32))
        routed.append(stats["expert_tokens"])
        dropped += float(stats["dropped"])
    for blocks in held:
        pool.release(blocks)
    return jnp.stack(out, axis=1), np.asarray(jnp.stack(routed)), dropped


def check_rows(config: Dict, spec: Dict, seed: int):
    """The check's token rows [n, T] for ``seed``, the rows' lengths and
    the positions its prefill calls cover."""
    import numpy as np

    c = spec["correctness"]
    lens = [int(x) for x in c["prompt_lens"]]
    calls = [int(x) for x in c["chunk_calls"]]
    rng = np.random.default_rng([seed, 5])
    rows = rng.integers(0, config["vocab_size"],
                        (len(lens), max(lens))).astype(np.int32)
    return rows, lens, calls


EXPERT_LEG_TOKENS = 256


def expert_leg_input(config: Dict, seed: int):
    """The expert leg's tokens [1, n, d]: unit normal features, what a
    normed residual stream hands the mixture."""
    import numpy as np

    return np.random.default_rng([seed, 6]).standard_normal(
        (1, EXPERT_LEG_TOKENS, config["hidden_size"])).astype(np.float32)


def reference_side(params, config: Dict, spec: Dict, seed: int, *,
                   routed: bool = True) -> Dict:
    """What the check holds the engine to, from the plain reference on
    ``params``: ``logits`` and ``chosen`` experts for the check's rows
    at the positions the check reads (the last prefill call's last
    position and every one after it), and ``expert_part``, the ROUTED
    experts' part alone (no shared expert) of the last MoE layer for
    the expert leg's tokens."""
    from benchmarks.lib import reference_pangu_moe as reference

    rows, lens, calls = check_rows(config, spec, seed)
    logits, chosen = reference.forward(
        params, rows, config,
        positions=list(range(sum(calls) - 1, max(lens))), routed=routed)
    stack = params["blocks"]["moe"]["moe"]
    layer = config["num_hidden_layers"] - config["first_k_dense_replace"] - 1
    u = expert_leg_input(config, seed)
    whole, _ = reference.moe(stack, u, config, layer=layer)
    part = whole - reference._swiglu_of(stack["shared"], u, layer)
    return {"logits": logits, "chosen": chosen, "expert_part": part}


def expert_leg(engine, config: Dict, seed: int, want) -> Dict:
    """The program's own mixture layer (nn/moe.moe_apply: router, sort,
    grouped matmul over the experts held, on the engine's own packed
    weights, the last MoE layer's of the stack) on the leg's tokens,
    without the shared expert, against the reference's routed part
    ``want``: each token's distance over the reference's norm, over the
    tokens that met a held expert there; the MEDIAN, which a token or
    two routed the other way at a near-tie do not move. The logits see
    the routed experts as a whole (leave them out and they fail) but
    not HOW WELL they are computed: a token meets half a held expert,
    a fifth of one layer's output. This leg does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quintnet_tpu.nn.moe import moe_apply

    args = engine.family.cfg.moe_args
    stack = engine.params["blocks"]["moe"]["moe"]
    layer = stack["router"]["w"].shape[0] - 1

    def routed(router_w, experts, u):
        return moe_apply({"router": {"w": router_w[layer]},
                          "experts": experts}, u, args,
                         expert_layer=layer)[0]

    got = np.asarray(jax.jit(routed)(
        stack["router"]["w"], stack["experts"],
        jnp.asarray(expert_leg_input(config, seed))))[0]
    want = np.asarray(want)[0]
    norm = np.linalg.norm(want, axis=-1)
    met = norm > 0
    err = np.linalg.norm(got - want, axis=-1)[met] / norm[met]
    return {"median": float(np.median(err)), "p90": float(
        np.quantile(err, 0.9)), "tokens": int(met.sum()),
        "finite": bool(np.isfinite(got).all())}


def check_logits(engine, config: Dict, spec: Dict, seed: int, *,
                 reference_out=None, detail: bool = False) -> Dict:
    """The cell's check. THREE limits (the cell file's ``correctness``
    says where each reading lay):

    - ``logits_tolerance`` on ``token_rms_median``: each compared
      token's root-mean-square logit distance from the reference over
      the vocabulary, the MEDIAN over the tokens. A near-tie at the
      eighth score that bf16 rounding upstream decides the other way
      (``routings_agreeing_share`` says how many) moves ONE token's
      logits by most of their spread — the largest and the plain rms
      distance are those tokens' — and the median not at all;
    - ``expert_tolerance`` on the expert leg (:func:`expert_leg`);
    - ``routing_floor`` under ``routings_agreeing_share``.

    ``reference_out``: :func:`reference_side` computed beforehand,
    where the reference's weights are not the engine's own or no longer
    fit beside it (tools/moe_mla_probe.py holds an engine on rounded
    weights to the reference on the stated ones, and a stated engine to
    a reference without the routed experts). ``detail`` adds every
    token's distance."""
    import jax.numpy as jnp
    import numpy as np

    c = spec["correctness"]
    rows, lens, calls = check_rows(config, spec, seed)
    done = sum(calls)
    if min(lens) <= done or len(lens) > engine.max_slots:
        raise ValueError(f"prompt_lens {lens} must all pass the "
                         f"{done} positions the prefill calls cover, "
                         f"on at most max_slots rows")
    got, got_routed, dropped = paged_logits(engine, rows, lens, calls)
    # got[:, 0] is the last prefill call's at position done - 1,
    # got[:, i] the decode step's at position done - 1 + i: the
    # reference's logits at positions done - 1 .. T - 1, each row's up
    # to its own last
    ref = (reference_out if reference_out is not None
           else reference_side(engine.params, config, spec, seed))
    want, chosen = ref["logits"], ref["chosen"]
    leg = expert_leg(engine, config, seed, ref["expert_part"])
    real = np.asarray([[done - 1 + i <= n - 1
                        for i in range(want.shape[1])] for n in lens])
    if not (bool(jnp.isfinite(got).all() & jnp.isfinite(want).all())
            and leg["finite"]):
        return {"ok": False, "why": "non-finite logits or expert part"}
    diff = np.asarray(jnp.abs(got - want))
    token_rms = np.sqrt((diff ** 2).mean(axis=-1))[real]
    typical = float(np.median(token_rms))
    per_step = (diff * real[:, :, None]).max(axis=(0, 2))
    ref_std = float(np.sqrt((np.asarray(want) ** 2).mean(axis=-1)[real]
                            .mean()))
    # the routings: every decode step's counts over the router's
    # experts (the live rows' tokens x MoE layers x top-k) against the
    # reference's for the same tokens. Half the L1 distance is the
    # number of routings that chose another expert
    chosen = np.asarray(chosen)                     # [L_moe, n, T, k]
    n_experts = got_routed.shape[1]
    moved = total = 0.0
    for i, pos in enumerate(range(done, max(lens))):
        on = np.asarray(lens) > pos
        there = np.bincount(chosen[:, on, pos].reshape(-1),
                            minlength=n_experts)
        moved += np.abs(got_routed[i] - there).sum() / 2.0
        total += there.sum()
    agreeing = 1.0 - moved / max(total, 1.0)
    tol, leg_tol = float(c["logits_tolerance"]), float(c["expert_tolerance"])
    floor = float(c["routing_floor"])
    out = {"ok": (typical <= tol and leg["median"] <= leg_tol
                  and agreeing >= floor and dropped == 0),
           "token_rms_median": typical, "tolerance": tol,
           "expert_rel_err_median": leg["median"],
           "expert_tolerance": leg_tol,
           "routings_agreeing_share": agreeing, "routing_floor": floor,
           "token_rms_p90": float(np.quantile(token_rms, 0.9)),
           "token_rms_max": float(token_rms.max()),
           "max_abs_diff": float(per_step.max()),
           "at_chunk_end": float(per_step[0]),
           "at_last_step": float(per_step[-1]), "ref_std": ref_std,
           "expert_rel_err_p90": leg["p90"], "expert_tokens": leg["tokens"],
           "routings_compared": int(total), "dropped": dropped,
           "positions": lens, "chunk_calls": calls,
           "decode_steps": int(want.shape[1]) - 1}
    if detail:
        out["token_rms"] = [round(float(x), 5) for x in token_rms]
    return out


# ---------------------------------------------------------------------
def run(ctx) -> Dict:
    import jax

    from benchmarks.lib import traffic
    from benchmarks.lib.harness import DeviceTrace

    spec = ctx.cell.spec
    if ctx.cell.traffic["arrivals"]["kind"] != "backlog":
        raise NotImplementedError(
            "drivers/serve_moe_mla.py runs standing-backlog cells; an "
            "open loop needs drivers/serve.py's latency accounting")
    cfg = PanguMoEConfig.from_dict(ctx.cell.config)
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
    ctx.info({"serve": {
        "window_s": window, "steps": len(steps), "tokens": len(in_window),
        "finished": finished, "finished_rps": finished / window,
        "submitted": len(sv.reqs), "refused": sv.refused,
        "preempted": m.preempted, "prefill_tokens": m.prefill_tokens,
        "decode_tokens": m.decode_tokens,
        "prefill_chunks": m.prefill_chunks,
        "compiled_programs": engine.compile_stats(),
        "kv_bytes_per_token": engine.pool.bytes_per_token,
        "kv_blocks_used_max": max((r["kv_blocks_used"] for r in ring),
                                  default=0),
        "kv_blocks_total": engine.pool.usable_blocks,
        # means over the window's steps that decoded: rows a step,
        # positions they hold, held experts that received a row (of
        # MoE layers x experts held) and the rows those received
        "decode_means": {
            "rows": sum(r["decoding"] for r in dec) / len(dec),
            "context_tokens": sum(r["context_tokens"] for r in dec)
            / len(dec),
            "experts_touched": sum(r["attrs"]["decode_experts_touched"]
                                   for r in dec) / len(dec),
            "expert_rows": sum(r["attrs"]["decode_expert_rows"]
                               for r in dec) / len(dec)} if dec else None,
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
