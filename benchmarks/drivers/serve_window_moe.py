"""Driver of the serving cells of a family of SLIDING-WINDOW and global
attention layers with a mixture of experts (Laguna, the first pipeline
stage of it): the same ``ServeEngine`` on one thread, the same loop,
window and counts as ``drivers/serve.py`` — ``_Serving`` and
``_run_backlog`` are imported from it, not copied, so ``serve_tok_s``,
the fill and ``token_counts`` mean what they mean in the GPT-2 XL cell.
What differs is what is built (the family, its seeded weights packed as
they are served, the engine with chunked prefill on) and the check
against the reference, which goes through the block pool AND the window
store, past two windows, in both of the store's orders.

The model's modules are imported as this file is loaded: a checkout
that lacks them (the parent of the PR that added the configuration)
fails here, before the chip is touched.
"""

from __future__ import annotations

import time
from typing import Dict

from benchmarks.drivers.serve import _Serving, _run_backlog
# the check's token rows and the expert leg's tokens are the latent
# cell's, seed for seed
from benchmarks.drivers.serve_moe_mla import check_rows, expert_leg_input
from quintnet_tpu.models.laguna import LagunaConfig, laguna_init
from quintnet_tpu.serve import ServeEngine, laguna_family


def family_of(cell_spec: Dict, cfg):
    return laguna_family(cfg, block_size=int(
        cell_spec["engine"]["block_size"]))


def make_params(cfg, weights_dtype: str, seed: int, finish=None):
    """The family's parameter tree on the device in ONE jitted call from
    the seed (lib/weights.py), the block matmuls — the experts among
    them — packed into the type they are served in inside the same
    call. ``finish`` (tools/window_moe_probe.py's controls) runs on the
    tree before the packing."""
    from benchmarks.lib.weights import seeded_params
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    targets = laguna_family(cfg).weight_targets
    policy = make_weight_policy(weights_dtype)

    def pack(p):
        if finish is not None:
            p = finish(p)
        return quantize_params(p, present_targets(p, targets), policy)

    return seeded_params(lambda k: laguna_init(k, cfg), seed, finish=pack)


def build_engine(cell_spec: Dict, cfg, params):
    e = cell_spec["engine"]
    return ServeEngine(
        family_of(cell_spec, cfg), params, max_slots=int(e["max_slots"]),
        block_size=int(e["block_size"]), num_blocks=int(e["num_blocks"]),
        max_seq_len=int(e["max_seq_len"]),
        prefill_len=int(e["prefill_len"]),
        chunked_prefill=bool(e["chunked_prefill"]),
        kv_dtype=e["kv_dtype"], weights_dtype=e["weights_dtype"],
        attn_kernel=e["attn_kernel"], prefix_cache=bool(e["prefix_cache"]))


# ---------------------------------------------------------------------
# correctness: the widest prefill bucket, a second chunk call of another
# bucket that starts past 0, then the decode program, against the
# reference
# ---------------------------------------------------------------------
def check_programs(engine):
    """The family's own ``prefill_from`` and ``decode`` jitted against
    the engine's own pool and window store, donated (the seam
    drivers/serve.verify_program uses): (prefill(params, k, v, wk, wv,
    ids, start, t0, row, slot) -> (logits [1, V], k, v, wk, wv, stats),
    decode(params, k, v, wk, wv, tok, pos, tables, rows) -> (logits of
    ``rows``, k, v, wk, wv, stats))."""
    import jax

    pool, fam = engine.pool, engine.family

    def prefill(params, k, v, wk, wv, ids, start, t0, row, slot):
        return fam.prefill_from(params, k, v, ids, start, t0, row,
                                pool.block_size, policy=pool.policy,
                                attn_kernel=engine.attn_kernel,
                                window=(wk, wv), slot=slot)

    def decode(params, k, v, wk, wv, tok, pos, tables, rows):
        logits, *bufs = fam.decode(
            params, k, v, tok, pos, tables, pool.block_size,
            policy=pool.policy, attn_kernel=engine.attn_kernel,
            window=(wk, wv))
        return (logits[rows], *bufs)

    donate = (1, 2, 3, 4)
    return (jax.jit(prefill, donate_argnums=donate),
            jax.jit(decode, donate_argnums=donate))


def paged_logits(engine, rows, lens, calls):
    """Logits of ``rows`` [n, T] from the PAGED programs at the engine's
    own shapes (``max_slots`` rows, the first ``n`` of them live): the
    first ``sum(calls)`` positions of each row through the prefill
    programs, call ``i`` of ``calls[i]`` tokens in the bucket of that
    width — the first is wider than the window (a query sees keys of
    its own chunk and the ring's), the later ones are CHUNK calls: they
    start past 0 and find the block pool and the ring as the earlier
    ones left them — then EVERY remaining position through the decode
    program, one token a step, teacher-forced through the block table
    and the ring (write, then read; the ring wraps); a row that has
    reached its length rides on as an inactive one. Returns (logits
    [n, T - sum(calls) + 1, V] f32: the last prefill call's at its last
    position, then each decode step's; per decode step the routing
    counts over all the experts [steps, E] and whether any program
    reported a dropped routing)."""
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
    done = sum(calls)
    first, dropped = [], 0.0
    for s in range(n):
        lo = 0
        for m in calls:
            ids = np.zeros((1, m), np.int32)
            ids[0] = rows[s, lo:lo + m]
            logits, *bufs, stats = prefill(
                engine.params, *pool.caches(), jnp.asarray(ids),
                jnp.int32(lo), jnp.int32(lo + m), jnp.asarray(tables[s]),
                jnp.int32(s))
            pool.update(*bufs)
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
        logits, *bufs, stats = decode(
            engine.params, *pool.caches(), jnp.asarray(tok),
            jnp.asarray(at), jnp.asarray(step_tables), live)
        pool.update(*bufs)
        out.append(logits.astype(jnp.float32))
        routed.append(stats["expert_tokens"])
        dropped += float(stats["dropped"])
    for blocks in held:
        pool.release(blocks)
    return jnp.stack(out, axis=1), np.asarray(jnp.stack(routed)), dropped


def _last_sparse(params):
    """(the stack that holds the last sparse layer's router, its index
    in that stack, its index among ALL sparse layers)."""
    experts = params["blocks"]["experts"]["gate"]["w"].shape[0]
    blocks = params["blocks"]
    for kind in ("full_sparse", "sliding_sparse"):
        if kind in blocks:
            return (blocks[kind]["moe"],
                    blocks[kind]["moe"]["router"]["w"].shape[0] - 1,
                    experts - 1)
    raise KeyError("no sparse stack")


def reference_side(params, config: Dict, spec: Dict, seed: int, *,
                   controls=()) -> Dict:
    """What the check holds the engine to, from the plain reference on
    ``params``: ``logits`` and ``chosen`` experts for the check's rows
    at the positions the check reads (the last prefill call's last
    position and every one after it), and ``expert_part``, the ROUTED
    experts' part alone (no shared expert) of the last sparse layer for
    the expert leg's tokens. ``controls``: the reference's
    (lib/reference_laguna.py), for tools/window_moe_probe.py. The model
    ends on a full sparse layer here (the published pattern's layer 4);
    the last sparse layer of whichever kind is taken."""
    import jax.numpy as jnp

    from benchmarks.lib import reference_laguna as reference

    rows, lens, calls = check_rows(config, spec, seed)
    logits, chosen = reference.forward(
        params, jnp.asarray(rows), config,
        positions=list(range(sum(calls) - 1, max(lens))), controls=controls)
    stack, layer, sparse = _last_sparse(params)
    part, _ = reference.routed_part(
        params["blocks"]["experts"], stack["router"]["w"][layer],
        jnp.asarray(expert_leg_input(config, seed)), config, layer=sparse)
    return {"logits": logits, "chosen": chosen, "expert_part": part}


def expert_leg(engine, config: Dict, seed: int, want) -> Dict:
    """The program's own mixture layer (nn/moe.moe_apply: router, sort,
    grouped matmul over all 256 experts, on the engine's own packed
    weights, the last sparse layer's of the whole stack) on the leg's
    tokens, without the shared expert, against the reference's routed
    part ``want``: each token's distance over the reference's norm, the
    MEDIAN, which a token or two routed the other way at a near-tie do
    not move. The logits see the routed experts as a whole but not HOW
    WELL they are computed: a token meets 8 of 256 experts a layer, a
    part of one layer's output. This leg does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quintnet_tpu.nn.moe import moe_apply

    args = engine.family.cfg.moe_args
    stack, layer, sparse = _last_sparse(engine.params)

    def routed(router_w, experts, u):
        return moe_apply({"router": {"w": router_w[layer]},
                          "experts": experts}, u, args,
                         expert_layer=sparse)[0]

    got = np.asarray(jax.jit(routed)(
        stack["router"]["w"], engine.params["blocks"]["experts"],
        jnp.asarray(expert_leg_input(config, seed))))[0]
    want = np.asarray(want)[0]
    norm = np.linalg.norm(want, axis=-1)
    err = np.linalg.norm(got - want, axis=-1) / norm
    return {"median": float(np.median(err)), "p90": float(
        np.quantile(err, 0.9)), "tokens": int(len(err)),
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
      logits — the largest and the plain rms distance are those
      tokens' — and the median not at all;
    - ``expert_tolerance`` on the expert leg (:func:`expert_leg`);
    - ``routing_floor`` under ``routings_agreeing_share``.

    ``reference_out``: :func:`reference_side` computed beforehand,
    where the reference's weights are not the engine's own or the
    reference runs a control (tools/window_moe_probe.py). ``detail``
    adds every token's distance."""
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
    # a position at a time over the vocabulary: [n, steps, V] f32 twice
    # over beside the engine is memory the check need not hold
    token_sq = np.stack([
        np.asarray(((got[:, i] - want[:, i]) ** 2).mean(axis=-1))
        for i in range(want.shape[1])], axis=1)              # [n, steps]
    token_rms_all = np.sqrt(token_sq)
    token_rms = token_rms_all[real]
    typical = float(np.median(token_rms))
    ref_std = float(np.sqrt(np.asarray(
        (want ** 2).mean(axis=-1))[real].mean()))
    # the routings: every decode step's counts over the experts (the
    # live rows' tokens x sparse layers x top-k) against the
    # reference's for the same tokens. Half the L1 distance is the
    # number of routings that chose another expert
    chosen = np.asarray(chosen)                     # [L_sparse, n, T, k]
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
           "at_chunk_end": float(token_rms_all[:, 0].max()),
           "at_last_step": float(token_rms_all[-1, -1]), "ref_std": ref_std,
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
            "drivers/serve_window_moe.py runs standing-backlog cells; an "
            "open loop needs drivers/serve.py's latency accounting")
    cfg = LagunaConfig.from_dict(ctx.cell.config)
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

    def mean(rows, get):
        return sum(get(r) for r in rows) / len(rows)

    ctx.info({"serve": {
        "window_s": window, "steps": len(steps), "tokens": len(in_window),
        "finished": finished, "finished_rps": finished / window,
        "submitted": len(sv.reqs), "refused": sv.refused,
        "preempted": m.preempted, "prefill_tokens": m.prefill_tokens,
        "decode_tokens": m.decode_tokens,
        "prefill_chunks": m.prefill_chunks,
        "compiled_programs": engine.compile_stats(),
        "kv_bytes_per_token": engine.pool.bytes_per_token,
        "window_bytes_per_slot": engine.pool.window_bytes_per_slot,
        "kv_blocks_used_max": max((r["kv_blocks_used"] for r in ring),
                                  default=0),
        "kv_blocks_total": engine.pool.usable_blocks,
        # means over the window's steps that decoded: rows a step, the
        # positions they hold, the rows x layers they attend by layer
        # kind, experts that received a row (of sparse layers x
        # experts) and the rows those received
        "decode_means": {
            "rows": mean(dec, lambda r: r["decoding"]),
            "context_tokens": mean(dec, lambda r: r["context_tokens"]),
            "global_rows": mean(dec, lambda r: r["attrs"]["global_rows"]),
            "window_rows": mean(dec, lambda r: r["attrs"]["window_rows"]),
            "experts_touched": mean(
                dec, lambda r: r["attrs"]["decode_experts_touched"]),
            "expert_rows": mean(
                dec, lambda r: r["attrs"]["decode_expert_rows"])}
        if dec else None,
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
