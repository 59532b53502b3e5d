"""Driver of the serving cells of a LINEAR-attention + LATENT-attention
mixture-of-experts family (Ling 3.0, one chip's share of its first
pipeline stage). The run itself — build, warm up, check, the window of
``drivers/serve.py``, the info line — is ``lib/backlog.run_family``, as
is the cut of the first ``max_slots`` requests; this file brings what
the family decides: what is built (its seeded weights with decays that
span slow and fast channels, packed as they are served, the engine with
chunked prefill on and the prefix cache off) and the check against the
reference, which goes through the ENGINE'S OWN compiled programs, the
per-slot state AND the paged latent cache, on rows spread over the
slots.

The model's modules are imported as this file is loaded: a checkout
that lacks them (the parent of the PR that added the configuration)
fails here, before the chip is touched.
"""

from __future__ import annotations

from typing import Dict

# the check's rows and the expert leg's tokens are the latent cell's,
# draw for draw
from benchmarks.drivers.serve_moe_mla import check_rows, expert_leg_input
from quintnet_tpu.models.ling_hybrid import (LingHybridConfig,
                                             ling_hybrid_init)
from quintnet_tpu.serve import ServeEngine, ling_hybrid_family


def make_params(cfg, weights_dtype: str, seed: int, finish=None):
    """The family's parameter tree on the device in ONE jitted call from
    the seed (lib/weights.py), then, in the same call: ``A_log`` and
    ``dt_bias`` of every KDA layer drawn so that a token's per-channel
    decay spans slow and fast channels in every head (nn/kda.
    kda_published), the depthwise conv's weight uniform in +-1/2
    (fan-in 4), the router's selection bias normal(0, 0.01), and the
    block matmuls — the held experts among them — packed into the type
    they are served in. The uniform draws are taken from the seeded
    normal(0, 0.02) leaves through the normal's own distribution
    function, so the seed stays an ARGUMENT of the compiled call.
    ``finish`` (tools/kda_moe_probe.py's controls) runs on the tree
    before the packing."""
    from jax.scipy.stats import norm

    from benchmarks.lib.weights import seeded_params
    from quintnet_tpu.nn.kda import kda_published
    from quintnet_tpu.nn.ssm import conv_published
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    targets = ling_hybrid_family(cfg).weight_targets
    policy = make_weight_policy(weights_dtype)

    def uniform(leaf):
        return norm.cdf(leaf / 0.02)

    def pack(p):
        kda, moe = p["blocks"]["kda"], p["blocks"]["moe"]
        old = kda["mixer"]
        mixer = {**old,
                 **kda_published(uniform(old["A_log"]),
                                 uniform(old["dt_bias"])),
                 "conv": {"w": conv_published(
                     uniform(old["conv"]["w"]),
                     cfg.short_conv_kernel_size)}}
        router = {**moe["moe"]["router"], "e_score_correction_bias":
                  0.5 * moe["moe"]["router"]["e_score_correction_bias"]}
        p = {**p, "blocks": {
            **p["blocks"], "kda": {**kda, "mixer": mixer},
            "moe": {**moe, "moe": {**moe["moe"], "router": router}}}}
        if finish is not None:
            p = finish(p)
        return quantize_params(p, present_targets(p, targets), policy)

    return seeded_params(lambda k: ling_hybrid_init(k, cfg), seed,
                         finish=pack)


def build_engine(cell_spec: Dict, cfg, params):
    e = cell_spec["engine"]
    return ServeEngine(
        ling_hybrid_family(cfg), params, max_slots=int(e["max_slots"]),
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
    """The family's own ``prefill_from`` (chunked delta rule,
    materialized latent layer) and ``decode`` (the recurrence, absorbed)
    jitted against the engine's own latent pool and state buffers, all
    three donated (the seam drivers/serve.verify_program uses) — the
    bodies of the engine's programs without their sampling tail, for
    the LOGITS the engine's programs do not hand back:
    (prefill(params, k, ssm, conv, ids, start, t0, row, slot) ->
    (logits [1, V], k, ssm, conv, stats), decode(params, k, ssm, conv,
    tok, pos, tables, rows) -> (logits of ``rows``, k, ssm, conv,
    stats))."""
    import jax

    pool, fam = engine.pool, engine.family

    def prefill(params, k, ssm, conv, ids, start, t0, row, slot):
        return fam.prefill_from(params, k, None, ids, start, t0, row,
                                pool.block_size, policy=pool.policy,
                                attn_kernel=engine.attn_kernel,
                                state=(ssm, conv), slot=slot)

    def decode(params, k, ssm, conv, tok, pos, tables, rows):
        logits, *bufs = fam.decode(
            params, k, None, tok, pos, tables, pool.block_size,
            policy=pool.policy, attn_kernel=engine.attn_kernel,
            state=(ssm, conv))
        return (logits[rows], *bufs)

    return (jax.jit(prefill, donate_argnums=(1, 2, 3)),
            jax.jit(decode, donate_argnums=(1, 2, 3)))


def engine_programs(engine, bucket: int):
    """The engine's OWN compiled objects, the ones its steps call in
    the window (``jit_serve_prefill_b<bucket>`` and ``jit_serve_decode``
    behind their recompile sentinels: a call whose abstract signature
    differed from warm-up's would raise, so what runs here IS the
    program the window runs), under :func:`check_programs`' signatures.
    They sample inside (the cell is greedy: the argmax) and hand back a
    TOKEN where those hand back logits: (prefill(...) -> (token [1], k,
    ssm, conv, stats), decode(...) -> (tokens of ``rows``, k, ssm,
    conv, stats))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # the programs take each call's key data for their own (donated)
    key = np.asarray(jax.random.key_data(jax.random.key(0)))
    none = jnp.int32(0)                 # no copy-on-write: cache off

    def prefill(params, k, ssm, conv, ids, start, t0, row, slot):
        *bufs, tok, _ = engine._prefills[bucket](
            params, k, ssm, conv, ids, start, t0, row, none, none,
            jnp.asarray(key), slot)
        return (tok[None], *bufs)

    def decode(params, k, ssm, conv, tok, pos, tables, rows):
        *bufs, nxt, _ = engine._decode(params, k, ssm, conv, tok, pos,
                                       tables, jnp.asarray(engine._key_data))
        return (nxt[rows], *bufs)

    return prefill, decode


def check_slots(engine, spec: Dict):
    """The slots the check's rows live on: ``live_rows`` of them (tens:
    the sort and the grouped matmul see many rows a step, as in the
    window), spread evenly from slot 0 to the LAST, so that a wrong
    slot index into the ``[layers, slot, 32, 128, 128]`` state shows
    wherever it starts. Slot ``slots[j]`` holds a copy of check row
    ``j % n``."""
    import numpy as np

    live = min(int(spec["correctness"]["live_rows"]), engine.max_slots)
    return np.unique(np.linspace(0, engine.max_slots - 1, live)
                     .round().astype(int))


def paged_pass(engine, programs, rows, lens, calls, slots, keep, *,
               after_call=None):
    """``rows`` [n, T] through a pair of PAGED ``programs``
    (:func:`check_programs` or :func:`engine_programs`) at the engine's
    own shapes (``max_slots`` rows; slot ``slots[j]`` live with a copy
    of row ``j % n``): the first ``sum(calls)`` positions of each live
    row through the prefill program in ``len(calls)`` calls of one
    bucket width — the later calls are CHUNK calls: they start past 0,
    from the state and conv tail the earlier ones left in the slot, and
    rebuild keys and values from the latent rows those left in the pool
    — then EVERY remaining position through the decode program, one
    token a step, teacher-forced through the state and the block table;
    a row that has reached its length rides on as an inactive one.
    ``after_call(pool, slot, start)`` (tools/kda_moe_probe.py's
    controls) is called after every program call, between programs as
    a faulty host would act: ``slot`` the one a prefill call served and
    ``start`` its first position, both None after a decode step.
    Returns (what the programs hand back for the live rows ``keep``
    (indices into ``slots``) [len(keep), T - sum(calls) + 1, ...]: the
    last prefill call's at its last position, then each decode step's;
    the FIRST KDA layer's state of every live row after the last step
    [m, H, dk, dv]; the latent rows the pool holds for the live rows'
    positions, first latent layer [m, T, rank + rope]; per decode step
    the routing counts over all the router's experts [steps, E];
    whether any program reported a dropped routing)."""
    import jax.numpy as jnp
    import numpy as np

    pool = engine.pool
    n, width = rows.shape
    m = len(slots)
    of = np.arange(m) % n
    need = pool.blocks_for(width)
    tables = np.zeros((engine.max_slots, engine.table_width), np.int32)
    held = []
    for s in slots:
        got = pool.acquire(need)
        if got is None:
            raise RuntimeError(f"pool cannot hold {m} rows of {width}")
        tables[s, :need] = got
        held.append(got)
    prefill, decode = programs
    bucket, done = max(calls), sum(calls)
    first, dropped = {}, 0.0
    for j, s in enumerate(slots):
        lo = 0
        for c in calls:
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :c] = rows[of[j], lo:lo + c]
            out, *bufs, stats = prefill(
                engine.params, *pool.caches(), jnp.asarray(ids),
                jnp.int32(lo), jnp.int32(lo + c), jnp.asarray(tables[s]),
                jnp.int32(s))
            pool.update(*bufs)
            dropped += float(stats["dropped"])
            if after_call is not None:
                after_call(pool, int(s), lo)
            lo += c
        if j in keep:
            first[j] = out[0]
    outs, routed = [jnp.stack([first[j] for j in keep])], []
    kept = jnp.asarray(slots[np.asarray(keep)])
    live_lens = np.asarray(lens)[of]
    for pos in range(done, width):
        on = live_lens > pos
        tok = np.zeros((engine.max_slots,), np.int32)
        at = np.zeros((engine.max_slots,), np.int32)
        tok[slots] = rows[of, pos] * on
        at[slots] = pos * on
        step_tables = tables.copy()
        step_tables[slots[~on]] = 0
        out, *bufs, stats = decode(
            engine.params, *pool.caches(), jnp.asarray(tok),
            jnp.asarray(at), jnp.asarray(step_tables), kept)
        pool.update(*bufs)
        outs.append(out)
        routed.append(stats["expert_tokens"])
        dropped += float(stats["dropped"])
        if after_call is not None:
            after_call(pool, None, None)
    state = pool.ssm[0, jnp.asarray(slots)]
    at = np.arange(width)
    slots_of = tables[slots][:, at // pool.block_size] * pool.block_size + (
        at % pool.block_size)[None, :]
    latent = pool.k[0, jnp.asarray(slots_of)][..., :pool.latent]
    for blocks in held:
        pool.release(blocks)
    return (jnp.stack(outs, axis=1), state, latent.astype(jnp.float32),
            np.asarray(jnp.stack(routed)), dropped)


def _last_moe_layer(config: Dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"] - 1


def reference_side(params, config: Dict, spec: Dict, seed: int,
                   **control) -> Dict:
    """What the check holds the engine to, from the plain reference on
    ``params``: ``logits`` and ``chosen`` experts for the check's rows
    at the positions the check reads (the last prefill call's last
    position and every one after it), ``state``, the first KDA layer's
    state after each row's last position, ``latent``, the latent
    layer's rows ``[c | k_rope]`` at every position, ``expert_part``,
    the ROUTED experts' part alone (no shared expert) of the last MoE
    layer for the expert leg's tokens, and ``latent_leg``, the rows the
    first latent layer would cache for those same tokens. ``control``: the
    reference's own (``routed=False``, ``bias_in_weights=True``), for
    tools/kda_moe_probe.py; a changed router is ``config``'s."""
    from benchmarks.lib import reference_ling_hybrid as reference

    rows, lens, calls = check_rows(config, spec, seed)
    logits, chosen, state, latent = reference.forward(
        params, rows, config,
        positions=list(range(sum(calls) - 1, max(lens))),
        state_at=[n - 1 for n in lens], latent_rows=True, **control)
    part, _ = reference.moe(
        params["blocks"]["moe"]["moe"], expert_leg_input(config, seed),
        config, layer=_last_moe_layer(config), shared=False,
        bias_in_weights=control.get("bias_in_weights", False))
    return {"logits": logits, "chosen": chosen, "state": state,
            "latent": latent, "expert_part": part,
            "latent_leg": reference.latent_rows(
                params["blocks"]["mla"], expert_leg_input(config, seed),
                config)}


def expert_leg(engine, config: Dict, seed: int, want) -> Dict:
    """The program's own mixture layer (nn/moe.moe_apply: the
    group-limited router with its bias, sort, grouped matmul over the
    experts held, on the engine's own packed weights, the last MoE
    layer's of the stack) on the leg's tokens, without the shared
    expert, against the reference's routed part ``want``: each token's
    distance over the reference's norm, over the tokens that met a held
    expert there; the MEDIAN, which a token or two routed the other way
    at a near-tie do not move. The logits see the routed experts as a
    whole but not HOW WELL they are computed: a token meets two held
    experts of eight. This leg does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quintnet_tpu.nn.moe import moe_apply

    args = engine.family.cfg.moe_args
    stack = engine.params["blocks"]["moe"]["moe"]
    layer = _last_moe_layer(config)

    def routed(router, experts, u):
        return moe_apply({"router": jax.tree.map(lambda a: a[layer],
                                                 router),
                          "experts": experts}, u, args,
                         expert_layer=layer)[0]

    got = np.asarray(jax.jit(routed)(
        stack["router"], stack["experts"],
        jnp.asarray(expert_leg_input(config, seed))))[0]
    want = np.asarray(want)[0]
    norm = np.linalg.norm(want, axis=-1)
    met = norm > 0
    err = np.linalg.norm(got - want, axis=-1)[met] / norm[met]
    return {"median": float(np.median(err)), "p90": float(
        np.quantile(err, 0.9)), "tokens": int(met.sum()),
        "finite": bool(np.isfinite(got).all())}


def latent_leg(engine, config: Dict, seed: int, want) -> Dict:
    """The program's own latent layer (models/ling_hybrid.mla_mixer,
    the first of the stack, on the engine's own packed weights) writing
    the expert leg's 256 unit-normal tokens into blocks of the engine's
    own pool, and the rows ``[c | k_rope]`` the pool then holds against
    the reference's ``want``: each position's distance over the
    reference row's norm, the MEDIAN. In the check's own run the rows
    inherit the rounding of five layers of bf16 matmuls before them
    (1.6-1.9% of a row: ``latent_rows_in_run_rel_err``), which hides
    HOW the rows are stored; here the inputs are exact on both sides."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quintnet_tpu.models.ling_hybrid import MATERIALIZED, mla_mixer
    from quintnet_tpu.nn.attention import rope_cos_sin

    pool, cfg = engine.pool, engine.family.cfg
    # as many of the leg's tokens as one table row addresses (a row's
    # cache entry depends on its own token and position alone)
    n = min(expert_leg_input(config, seed).shape[1],
            engine.table_width * pool.block_size)
    u = expert_leg_input(config, seed)[:, :n]
    blocks = pool.acquire(pool.blocks_for(n))
    if blocks is None:
        raise RuntimeError(f"pool cannot hold a row of {n}")
    row = np.zeros((engine.table_width,), np.int32)
    row[:len(blocks)] = blocks

    def write(stack, k, u, row):
        positions = jnp.arange(n, dtype=jnp.int32)[None]
        cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim,
                                theta=cfg.rope_theta)
        return mla_mixer(
            jax.tree.map(lambda a: a[0], stack), u, k, 0, positions,
            jnp.full((1,), n, jnp.int32), row[None], pool.block_size, cfg,
            cos, sin, form=MATERIALIZED)[1]

    k = jax.jit(write, donate_argnums=(1,))(
        engine.params["blocks"]["mla"], pool.k, jnp.asarray(u),
        jnp.asarray(row))
    at = np.arange(n)
    got = np.asarray(k[0, jnp.asarray(
        row[at // pool.block_size] * pool.block_size
        + at % pool.block_size)][:, :pool.latent], np.float64)
    pool.update(k, *pool.caches()[1:])
    pool.release(blocks)
    want = np.asarray(want, np.float64)[0, :n]
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    return {"median": float(np.median(err)),
            "p90": float(np.quantile(err, 0.9)),
            "finite": bool(np.isfinite(got).all())}


def check_logits(engine, config: Dict, spec: Dict, seed: int, *,
                 reference_out=None, after_call=None) -> Dict:
    """The cell's check: the check's two rows, copied onto ``live_rows``
    slots from the first to the last (:func:`check_slots`), TWICE
    through the paged path (:func:`paged_pass`) — once through the
    engine's OWN compiled programs (:func:`engine_programs`), which
    leave the state, the latent rows, the routing counts and a greedy
    token a step, and once through the same bodies without their
    sampling tail (:func:`check_programs`) for the LOGITS. SEVEN limits
    (the cell file's ``correctness`` says where each reading lay):

    - ``logits_tolerance`` on ``token_rms_median``: each compared
      token's root-mean-square logit distance from the reference over
      the vocabulary, the MEDIAN over the tokens of the rows on the two
      HIGHEST live slots. A near-tie at the eighth selection score that
      bf16 rounding upstream decides the other way moves ONE token's
      logits by much of their spread — the largest and the plain rms
      distance are those tokens' — and the median not at all;
    - ``engine_tokens_floor`` under ``engine_tokens_agreeing_share``:
      the share of the engine's own programs' tokens, every live row's
      at every step, that are the argmax of those logits — what ties
      the programs the window runs to the ones the logits are read
      from;
    - ``state_tolerance`` on ``state_rel_err``: the FIRST KDA layer's
      state the ENGINE'S decode program left for every live row after
      the last step, the distance from the reference's over the
      reference's norm, the largest of the rows'. The logits cannot see
      a rounded state (PR 27); this can;
    - ``latent_in_run_tolerance`` on ``latent_rows_in_run_rel_err``: the
      latent rows the engine's programs left in the pool for every live
      row, each position's distance over the reference row's norm, the
      median — the one limit ON THE RUN that sees how a latent row is
      stored;
    - ``latent_tolerance`` on the latent leg (:func:`latent_leg`): the
      same, with exact inputs on both sides and so with more room;
    - ``expert_tolerance`` on the expert leg (:func:`expert_leg`);
    - ``routing_floor`` under ``routings_agreeing_share`` (the engine's
      programs' counts).

    ``reference_out``: :func:`reference_side` computed beforehand,
    where the reference's weights are not the engine's own
    (tools/kda_moe_probe.py holds an engine on altered weights to the
    reference on the stated ones). ``after_call``: :func:`paged_pass`."""
    import jax.numpy as jnp
    import numpy as np

    c = spec["correctness"]
    rows, lens, calls = check_rows(config, spec, seed)
    done, n = sum(calls), len(lens)
    slots = check_slots(engine, spec)
    m = len(slots)
    if min(lens) <= done or m < n:
        raise ValueError(f"prompt_lens {lens} must all pass the "
                         f"{done} positions the prefill calls cover, "
                         f"on at least {n} live rows")
    of = np.arange(m) % n
    top = list(range(m - n, m))         # the rows on the highest slots
    tokens, got_state, got_latent, got_routed, dropped = paged_pass(
        engine, engine_programs(engine, max(calls)), rows, lens, calls,
        slots, list(range(m)), after_call=after_call)
    got, _, _, _, dropped_too = paged_pass(
        engine, check_programs(engine), rows, lens, calls, slots, top,
        after_call=after_call)
    got = got.astype(jnp.float32)
    dropped += dropped_too
    # got[:, 0] is the last prefill call's at position done - 1,
    # got[:, i] the decode step's at position done - 1 + i: the
    # reference's logits at positions done - 1 .. T - 1, each row's up
    # to its own last
    ref = (reference_out if reference_out is not None
           else reference_side(engine.params, config, spec, seed))
    want, chosen = ref["logits"][of[top]], ref["chosen"]
    leg = expert_leg(engine, config, seed, ref["expert_part"])
    rows_leg = latent_leg(engine, config, seed, ref["latent_leg"])
    live_lens = np.asarray(lens)[of]
    real_all = (done - 1 + np.arange(want.shape[1])[None, :]
                <= live_lens[:, None] - 1)
    real = real_all[top]
    if not (bool(jnp.isfinite(got).all() & jnp.isfinite(want).all()
                 & jnp.isfinite(got_state).all()
                 & jnp.isfinite(got_latent).all()) and leg["finite"]
            and rows_leg["finite"]):
        return {"ok": False, "why": "non-finite logits, state, latent "
                "rows or expert part"}
    diff = np.asarray(jnp.abs(got - want))
    token_rms = np.sqrt((diff ** 2).mean(axis=-1))[real]
    typical = float(np.median(token_rms))
    per_step = (diff * real[:, :, None]).max(axis=(0, 2))
    ref_std = float(np.sqrt((np.asarray(want) ** 2).mean(axis=-1)[real]
                            .mean()))
    # the engine's own programs' greedy tokens, every live row's,
    # against the argmax of the logits of that row's copy up top
    greedy = np.asarray(jnp.argmax(got, axis=-1))    # of rows of[top]
    greedy = greedy[np.argsort(of[top])][of]         # of every live row
    same_token = float((np.asarray(tokens) == greedy)[real_all].mean())
    want_state = np.asarray(ref["state"], np.float64)
    state_err = float(max(
        np.linalg.norm(np.asarray(got_state[j], np.float64)
                       - want_state[of[j]])
        / np.linalg.norm(want_state[of[j]]) for j in range(m)))
    want_latent = np.asarray(ref["latent"], np.float64)[of]
    held = np.arange(max(lens))[None, :] < live_lens[:, None]
    latent_in_run = float(np.median((
        np.linalg.norm(np.asarray(got_latent, np.float64) - want_latent,
                       axis=-1)
        / np.linalg.norm(want_latent, axis=-1))[held]))
    # the routings: every decode step's counts over the router's
    # experts (the live rows' tokens x MoE layers x top-k) against the
    # reference's for the same tokens. Half the L1 distance is the
    # number of routings that chose another expert
    chosen = np.asarray(chosen)[:, of]              # [L_moe, m, T, k]
    n_experts = got_routed.shape[1]
    moved = total = 0.0
    for i, pos in enumerate(range(done, max(lens))):
        on = live_lens > pos
        there = np.bincount(chosen[:, on, pos].reshape(-1),
                            minlength=n_experts)
        moved += np.abs(got_routed[i] - there).sum() / 2.0
        total += there.sum()
    agreeing = 1.0 - moved / max(total, 1.0)
    tol, leg_tol = float(c["logits_tolerance"]), float(c["expert_tolerance"])
    state_tol, floor = float(c["state_tolerance"]), float(c["routing_floor"])
    latent_tol = float(c["latent_tolerance"])
    in_run_tol = float(c["latent_in_run_tolerance"])
    token_floor = float(c["engine_tokens_floor"])
    return {"ok": (typical <= tol and same_token >= token_floor
                   and state_err <= state_tol
                   and latent_in_run <= in_run_tol
                   and rows_leg["median"] <= latent_tol
                   and leg["median"] <= leg_tol and agreeing >= floor
                   and dropped == 0),
            "token_rms_median": typical, "tolerance": tol,
            "engine_tokens_agreeing_share": same_token,
            "engine_tokens_floor": token_floor,
            "state_rel_err": state_err, "state_tolerance": state_tol,
            "latent_rows_in_run_rel_err": latent_in_run,
            "latent_in_run_tolerance": in_run_tol,
            "latent_rel_err_median": rows_leg["median"],
            "latent_tolerance": latent_tol,
            "latent_rel_err_p90": rows_leg["p90"],
            "expert_rel_err_median": leg["median"],
            "expert_tolerance": leg_tol,
            "routings_agreeing_share": agreeing, "routing_floor": floor,
            "token_rms_p90": float(np.quantile(token_rms, 0.9)),
            "token_rms_max": float(token_rms.max()),
            "max_abs_diff": float(per_step.max()),
            "at_chunk_end": float(per_step[0]),
            "at_last_step": float(per_step[-1]), "ref_std": ref_std,
            "state_norm": float(np.linalg.norm(want_state[0])),
            "expert_rel_err_p90": leg["p90"], "expert_tokens": leg["tokens"],
            "routings_compared": int(total), "dropped": dropped,
            "positions": lens, "chunk_calls": calls,
            "live_slots": [int(s) for s in slots],
            "decode_steps": int(want.shape[1]) - 1}


# ---------------------------------------------------------------------
def run(ctx) -> Dict:
    from benchmarks.lib.backlog import run_family

    return run_family(ctx, LingHybridConfig.from_dict(ctx.cell.config),
                      make_params=make_params, build_engine=build_engine,
                      check_logits=check_logits,
                      decode_attrs=("tokens_without_held_group",))
