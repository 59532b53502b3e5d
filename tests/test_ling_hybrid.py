"""Ling 3.0 (``bailing_hybrid``) at tiny widths on the CPU (hidden 64, 4
heads of 16, two groups of ``[kda, kda, mla]`` with one leading dense
layer, 16 experts in 4 groups of which the first two groups' 8 are
held, 2 groups kept, top-4): the group-limited router, the share of one
chip, and ``ServeEngine`` itself — a per-slot state AND a latent row a
position in one sequence — against the plain reference
(benchmarks/lib/reference_ling_hybrid.py: f32, ``precision="highest"``,
a scan over time, no cache, no absorption, a loop over experts with a
mask; nothing imported from ``quintnet_tpu``).

Tolerances. Everything here is f32 on the CPU, where a matmul is exact
f32: the forms differ in the ORDER of their sums (the chunked delta rule
and its solve, the absorbed contraction, the grouped matmul over sorted
rows), a few ulp of values of size about 1. ``ATOL`` 2e-4 is far under
what any missing piece does (one expert's part is 0.1 of a layer's
output; a zeroed conv tail moves the logits by 0.01).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.ling_hybrid import (LingHybridConfig,
                                             ling_hybrid_init,
                                             ling_hybrid_partition_specs)
from quintnet_tpu.nn.moe import MoEArgs, moe_apply, moe_held_init
from quintnet_tpu.serve import ServeEngine, ling_hybrid_family
from quintnet_tpu.serve.kv_pool import KVPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_ling_hybrid", os.path.join(
            ROOT, "benchmarks", "lib", "reference_ling_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()
CFG = LingHybridConfig.tiny()
CFG_DICT = dataclasses.asdict(CFG)
FAMILY = ling_hybrid_family(CFG)
BS = 4


@pytest.fixture(scope="module")
def params():
    return ling_hybrid_init(jax.random.key(11), CFG)


def _engine(params, **kw):
    opts = dict(max_slots=3, block_size=BS, num_blocks=96, max_seq_len=96,
                prefix_cache=False)
    opts.update(kw)
    return ServeEngine(FAMILY, params, **opts)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _pool(max_slots=2, num_blocks=24):
    return KVPool(n_layers=FAMILY.n_layers, n_kv_heads=1,
                  head_dim=CFG.latent_width, block_size=BS,
                  num_blocks=num_blocks, latent=CFG.latent_width,
                  state=FAMILY.state, max_slots=max_slots,
                  prefix_cache=False)


def _table(pool, n_tokens, width):
    blocks = pool.acquire(pool.blocks_for(n_tokens))
    row = np.zeros((width,), np.int32)
    row[:len(blocks)] = blocks
    return row


# ---------------------------------------------------------------------
# the family's contracts against the reference's full forward
# ---------------------------------------------------------------------
def test_prefill_a_chunk_call_past_0_then_decode_equal_the_full_forward(
        params):
    """What the cell's check does, small: 16 positions through the
    prefill bucket, 7 more through a second CHUNK call that starts past
    0 (entry state and conv tail non-zero, keys and values rebuilt from
    the latent rows the first call left), then every remaining position
    through the decode program, teacher-forced, beside an empty slot.
    LOGITS at every position read, and the first KDA layer's state after
    the last step."""
    pool = _pool()
    (ids,) = _prompts(2, [31])
    tables = np.zeros((2, 8), np.int32)
    tables[0] = _table(pool, 32, 8)
    prefill = jax.jit(lambda k, s, c, ids, start, t0: FAMILY.prefill_from(
        params, k, None, ids, start, t0, jnp.asarray(tables[0]), BS,
        state=(s, c), slot=jnp.int32(0)))
    bufs = pool.caches()
    got = []
    for lo, n in ((0, 16), (16, 7)):
        bucket = np.zeros((1, 16), np.int32)
        bucket[0, :n] = ids[lo:lo + n]
        logits, *bufs, stats = prefill(*bufs, jnp.asarray(bucket),
                                       jnp.int32(lo), jnp.int32(lo + n))
        assert float(stats["dropped"]) == 0.0
        # the bucket's pad columns are routed nowhere
        assert float(stats["assigned"]) == (
            n * CFG.num_experts_per_tok * CFG.n_moe_layers)
    got.append(logits[0])
    decode = jax.jit(lambda k, s, c, tok, pos: FAMILY.decode(
        params, k, None, tok, pos, jnp.asarray(tables), BS, state=(s, c)))
    for t in range(23, 31):
        logits, *bufs, stats = decode(
            *bufs, jnp.asarray([ids[t], 0], jnp.int32),
            jnp.asarray([t, 0], jnp.int32))
        got.append(logits[0])
        # the empty slot's token is padding: one live token a layer
        assert float(stats["assigned"]) == (
            CFG.num_experts_per_tok * CFG.n_moe_layers)
    want, _, state = reference.forward(
        params, jnp.asarray(ids[None]), CFG_DICT,
        positions=list(range(22, 31)))
    np.testing.assert_allclose(jnp.stack(got), want[0], atol=ATOL)
    k, ssm, conv = bufs
    np.testing.assert_allclose(ssm[0, 0], state[0], atol=ATOL)
    # the empty slot's state and the null row were never written
    assert not np.asarray(ssm[:, 1:]).any()
    assert not np.asarray(conv[:, 1:]).any()


def test_verify_in_chunks_equals_the_reference(params):
    """The verify contract (the chunk program for P tokens a row, every
    row from its CURRENT state, the latent layer absorbed): two rows of
    different lengths, three calls."""
    pool = _pool()
    ids = np.stack(_prompts(3, [24, 24]))
    lens = np.asarray([24, 17])
    tables = np.stack([_table(pool, 24, 6) for _ in range(2)])
    verify = jax.jit(lambda k, s, c, ids, starts, tails: FAMILY.verify(
        params, k, None, ids, starts, tails, jnp.asarray(tables), BS,
        state=(s, c)))
    bufs = pool.caches()
    got = []
    for lo in (0, 8, 16):
        tails = np.clip(lens - lo, 0, 8).astype(np.int32)
        logits, *bufs, _ = verify(
            *bufs, jnp.asarray(ids[:, lo:lo + 8]),
            jnp.full((2,), lo, jnp.int32), jnp.asarray(tails))
        got.append(logits)
    got = jnp.concatenate(got, axis=1)
    want, _, state = reference.forward(params, jnp.asarray(ids), CFG_DICT,
                                       state_at=list(lens - 1))
    for row, n in enumerate(lens):
        np.testing.assert_allclose(got[row, :n], want[row, :n], atol=ATOL)
    np.testing.assert_allclose(bufs[1][0, :2], state, atol=ATOL)


# ---------------------------------------------------------------------
# the group-limited router
# ---------------------------------------------------------------------
ARGS = MoEArgs(n_experts=16, top_k=4, dropless=True, scoring="sigmoid",
               routed_scale=2.5, n_group=4, topk_group=2)


def _moe_case(seed, held=16, shared=32):
    p = moe_held_init(jax.random.key(seed), 64, 32, 16, held=held,
                      shared_hidden=shared, selection_bias=True)
    x = jax.random.normal(jax.random.key(seed + 1), (2, 9, 64))
    return p, x


def _route_by_hand(s, b, *, n_group, topk_group, k, scale):
    """A NumPy transcription of the published router: selection on
    ``s + b``, a group's score the sum of its two largest, the best
    groups, the ``k`` largest inside them, weights from the unbiased
    ``s``. The lower index wins a tie."""
    sel = s + b
    n, e = s.shape
    size = e // n_group
    group = np.sort(sel.reshape(n, n_group, size), axis=-1)[..., -2:].sum(-1)
    best = np.argsort(-group, axis=-1, kind="stable")[:, :topk_group]
    kept = np.zeros((n, n_group), bool)
    np.put_along_axis(kept, best, True, axis=1)
    masked = np.where(np.repeat(kept, size, axis=1), sel, -np.inf)
    idx = np.argsort(-masked, axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(s, idx, axis=1)
    return idx, scale * w / w.sum(axis=-1, keepdims=True), kept


def _swiglu(g, u, d, v):
    a = v @ g
    return (a / (1 + np.exp(-a)) * (v @ u)) @ d


@pytest.mark.parametrize("case", ["seeded", "bias_decides", "ties"])
def test_the_router_equals_a_numpy_transcription(case):
    """``seeded``: the router as initialised. ``bias_decides``: a bias
    of 3 on two experts of one group puts that group and those experts
    in every token's choice, yet their WEIGHTS are their unbiased
    scores. ``ties``: a router of zeros scores every expert 0.5; the
    lower index wins among groups and among experts."""
    p, x = _moe_case(20)
    if case == "bias_decides":
        b = jnp.zeros((16,)).at[jnp.asarray([13, 14])].set(3.0)
        p = {**p, "router": {**p["router"], "e_score_correction_bias": b}}
    if case == "ties":
        p = {**p, "router": {"w": jnp.zeros((64, 16)),
                             "e_score_correction_bias": jnp.zeros((16,))}}
    y, _, stats = moe_apply(p, x, ARGS, return_stats=True)
    xt = np.asarray(x, np.float64).reshape(18, 64)
    s = 1.0 / (1.0 + np.exp(-(xt @ np.asarray(p["router"]["w"],
                                               np.float64))))
    b = np.asarray(p["router"]["e_score_correction_bias"], np.float64)
    idx, w, kept = _route_by_hand(s, b, n_group=4, topk_group=2, k=4,
                                  scale=2.5)
    if case == "bias_decides":
        assert kept[:, 3].all() and all({13, 14} <= set(r) for r in idx)
        # a weight is the unbiased score's share: well under the 3.5
        # and more a biased one would give
        assert w.max() < 2.5
    if case == "ties":
        np.testing.assert_array_equal(idx, np.tile([0, 1, 2, 3], (18, 1)))
    e = jax.tree.map(lambda a: np.asarray(a, np.float64), p["experts"])
    sh = jax.tree.map(lambda a: np.asarray(a, np.float64), p["shared"])
    want = np.zeros((18, 64))
    for t in range(18):
        want[t] = _swiglu(sh["gate"]["w"], sh["up"]["w"], sh["down"]["w"],
                          xt[t])
        for j, wj in zip(idx[t], w[t]):
            want[t] += wj * _swiglu(e["gate"]["w"][j], e["up"]["w"][j],
                                    e["down"]["w"][j], xt[t])
    np.testing.assert_allclose(y.reshape(18, 64), want, atol=ATOL)
    np.testing.assert_array_equal(
        stats["expert_tokens"], np.bincount(idx.reshape(-1), minlength=16))
    assert float(stats["dropped"]) == 0 and float(stats["assigned"]) == 72
    assert float(stats["no_held_group"]) == 0        # every expert held
    # and the reference's own router is the same transcription
    ref_idx, ref_w, ref_kept = reference.route(
        p, x, {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
               "norm_topk_prob": True, "n_group": 4, "topk_group": 2})
    np.testing.assert_array_equal(ref_idx.reshape(18, 4), idx)
    np.testing.assert_allclose(ref_w.reshape(18, 4), w, atol=1e-5)
    np.testing.assert_array_equal(ref_kept.reshape(18, 4), kept)


def test_dropping_the_group_limit_or_biasing_the_weights_changes_routing():
    """The two controls of the cell's check are visible at this size:
    plain top-4 of 16 chooses other experts for some token, and the
    bias in the weights moves them."""
    p, x = _moe_case(21)
    y, _, st = moe_apply(p, x, ARGS, return_stats=True)
    plain, _, st_plain = moe_apply(
        p, x, ARGS._replace(n_group=0, topk_group=0), return_stats=True)
    assert np.abs(np.asarray(st["expert_tokens"])
                  - np.asarray(st_plain["expert_tokens"])).sum() > 0
    assert float(jnp.abs(y - plain).max()) > 100 * ATOL
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True, "n_group": 4, "topk_group": 2}
    _, w, _ = reference.route(p, x, cfg)
    _, w_biased, _ = reference.route(p, x, cfg, bias_in_weights=True)
    assert float(jnp.abs(w - w_biased).max()) > 1e-3


def test_the_shares_of_all_chips_sum_to_the_uncut_layer():
    """THE share test: the routed parts that all four shares give (each
    told which group of experts it holds, each routing over the full
    router with the groups and the bias), with the shared expert
    counted once, add up to the uncut reference layer — and so do the
    reference's own shares. A token whose kept groups miss a share's
    experts gets nothing routed from it, and is counted."""
    p, x = _moe_case(30)
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True, "num_experts": 16, "experts_first": 0,
           "n_group": 4, "topk_group": 2}
    whole, _ = reference.moe(p, x, cfg)
    _, _, kept = reference.route(p, x, cfg)
    total = total_ref = reference._swiglu_jit(p["shared"], x)
    rows = missed = 0
    for first in range(0, 16, 4):
        part = {"router": p["router"],
                "experts": jax.tree.map(lambda a: a[first:first + 4],
                                        p["experts"])}
        y, _, st = moe_apply(part, x, ARGS._replace(experts_held=(first, 4)),
                             return_stats=True)
        total = total + y
        rows += float(st["held_rows"])
        assert float(st["held_rows"]) + float(st["elsewhere"]) == 72
        # a share of ONE group: the tokens that did not keep it
        assert float(st["no_held_group"]) == float(
            (~kept[..., first // 4]).sum())
        missed += float(st["no_held_group"])
        y_ref, _ = reference.moe({**part, "shared": p["shared"]}, x, cfg,
                                 experts_held=(first, 4), shared=False)
        total_ref = total_ref + y_ref
    np.testing.assert_allclose(total, whole, atol=ATOL)
    np.testing.assert_allclose(total_ref, whole, atol=ATOL)
    # every token keeps 2 of the 4 groups
    assert rows == 72 and missed == 18 * 2


def test_masked_tokens_are_not_counted_without_a_held_group():
    p, x = _moe_case(50, held=4)
    args = ARGS._replace(experts_held=(4, 4))
    mask = jnp.arange(9)[None, :] < jnp.asarray([9, 4])[:, None]
    _, _, st = moe_apply(p, x, args, return_stats=True, token_mask=mask)
    _, _, kept = reference.route(
        p, x, {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
               "norm_topk_prob": True, "n_group": 4, "topk_group": 2})
    assert float(st["no_held_group"]) == float(
        (~kept[..., 1] & mask).sum())
    assert float(st["assigned"]) == 13 * 4


def test_groups_are_refused_where_they_cannot_hold():
    p, x = _moe_case(1)
    with pytest.raises(ValueError, match="n_group"):
        moe_apply(p, x, ARGS._replace(n_group=3, topk_group=2))
    with pytest.raises(ValueError, match="topk_group"):
        moe_apply(p, x, ARGS._replace(topk_group=5))
    with pytest.raises(ValueError, match="top_k"):
        moe_apply(p, x, ARGS._replace(top_k=5, topk_group=1))
    from quintnet_tpu.nn.moe import moe_init

    with pytest.raises(NotImplementedError, match="dropless"):
        moe_apply(moe_init(jax.random.key(0), 64, 32, 4),
                  jnp.zeros((1, 3, 64)),
                  MoEArgs(n_experts=4, n_group=2, topk_group=1))


# ---------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------
def _assert_greedy_by_reference(params, prompt, out):
    """``out`` (prompt + generated) is the reference's greedy decode:
    one full forward over it, the argmax at every position from the
    prompt's last on."""
    out = np.asarray(out)
    n = len(prompt)
    np.testing.assert_array_equal(out[:n], prompt)
    logits, _, _ = reference.forward(
        params, jnp.asarray(out[None, :-1]), CFG_DICT,
        positions=list(range(n - 1, len(out) - 1)))
    np.testing.assert_array_equal(np.argmax(logits[0], axis=-1), out[n:])


def test_engine_tokens_equal_the_references_greedy_decode(params):
    """submit + step through the scheduler, the prefill ladder and the
    one decode program, more requests than slots: every request's
    tokens are the reference's greedy continuation, nothing compiles
    twice, and the ring carries the family's facts."""
    eng = _engine(params)
    eng.warmup()
    prompts = _prompts(11, (5, 17, 9, 30, 12))
    rids = [eng.submit(p, 6) for p in prompts]
    eng.run()
    for rid, prompt in zip(rids, prompts):
        _assert_greedy_by_reference(params, prompt, eng.result(rid))
    eng.assert_compile_count(prefill=len(eng.prefill_buckets), decode=1)
    st = eng.recorder.static
    assert st["state_bytes_per_slot"] == eng.pool.state_bytes_per_slot > 0
    assert st["layer_pattern"] == list(CFG.layer_types)
    assert st["paged_layers"] == CFG.periods == 2
    assert st["kv_bytes_per_token"] == 2 * CFG.latent_width * 4
    assert st["expert_param_bytes"] == sum(
        int(x.nbytes) for x in jax.tree.leaves(
            params["blocks"]["moe"]["moe"]["experts"]))
    decoded = [r for r in eng.recorder.snapshot()
               if r["decoding"] and not r["prefill_tokens"]]
    assert decoded
    for r in decoded:
        a = r["attrs"]
        k = CFG.num_experts_per_tok * CFG.n_moe_layers * r["decoding"]
        assert r["state_bytes"] == (2 * r["decoding"]
                                    * eng.pool.state_bytes_per_slot)
        assert a["expert_rows"] + a["routed_elsewhere"] == k
        assert a["decode_expert_rows"] == a["expert_rows"]
        assert 0 <= a["tokens_without_held_group"] <= (
            CFG.n_moe_layers * r["decoding"])
        assert a["moe_dropped_tokens"] == 0 and a["attended_rows"] > 0


def test_chunked_prefill_through_the_engine_equals_one_shot(params):
    prompts = _prompts(13, (40, 7, 33))
    plain = _engine(params)
    chunked = _engine(params, chunked_prefill=True, prefill_len=16,
                      prefill_chunk_budget=16)
    for eng in (plain, chunked):
        rids = [eng.submit(p, 5) for p in prompts]
        eng.run()
        eng.outs = [eng.result(r) for r in rids]
    assert chunked.metrics.summary()["prefill_chunks"] >= 3 + 1 + 3
    for a, b in zip(plain.outs, chunked.outs):
        np.testing.assert_array_equal(a, b)


def test_a_preempted_request_re_prefills_to_the_same_tokens(params):
    """A preempted slot's state is dropped and its blocks freed; the
    resume prefills prompt + generated from position 0 through the
    chunk program. On the CPU's exact f32 that is token-equal to the
    run nobody interrupted, and to the reference."""
    prompts = _prompts(17, (20, 22, 18))
    roomy = _engine(params)
    tight = _engine(params, num_blocks=20)        # 19 blocks of 4
    for eng in (roomy, tight):
        rids = [eng.submit(p, 16) for p in prompts]
        eng.run()
        eng.outs = [eng.result(r) for r in rids]
    assert roomy.metrics.preempted == 0 and tight.metrics.preempted > 0
    for a, b, prompt in zip(roomy.outs, tight.outs, prompts):
        np.testing.assert_array_equal(a, b)
        _assert_greedy_by_reference(params, prompt, b)
    # everything a sequence held came back: blocks and nothing else
    assert tight.pool.num_free == tight.pool.usable_blocks


def test_a_reused_slot_starts_from_a_zeroed_state(params):
    """One slot, two requests one after the other: the second finds the
    first's state and conv tail in its row and must not see them."""
    first, second = _prompts(19, (23, 14))
    eng = _engine(params, max_slots=1)
    eng.submit(first, 8)
    eng.run()
    assert np.asarray(eng.pool.ssm[:, 0]).any()       # slot 0 is stale
    assert np.asarray(eng.pool.conv[:, 0]).any()
    rid = eng.submit(second, 8)
    eng.run()
    _assert_greedy_by_reference(params, second, eng.result(rid))


def test_the_programs_are_named_and_their_census_is_pinned(params):
    """Every program is ``jit_serve_*``; on a bf16 pool none has a
    collective or a pool- or state-shaped scan operand; decode walks
    the one latent pool in place, once a written-out group and once in
    the scan over the rest, and gathers nothing; a prefill bucket
    gathers the one row kind as often and walks nothing
    (analysis/specs.expected_serve_kda_moe)."""
    from quintnet_tpu import analysis
    from quintnet_tpu.analysis.specs import expected_serve_kda_moe

    eng = _engine(params, kv_dtype="bf16", weights_dtype="bf16",
                  max_seq_len=88)
    calls = list(eng._warmup_calls())
    names = sorted(s.fn.__name__ for s, _ in calls)
    assert names[0] == "serve_decode" and all(
        n.startswith(("serve_prefill_b", "serve_decode")) for n in names)
    text = calls[0][0].fn.lower(*calls[0][1]).as_text()
    assert "module @jit_serve_" in text
    geometry = dict(table_width=eng.table_width,
                    block_size=eng.pool.block_size)
    for sentinel, args in calls:
        fn = sentinel.fn
        want = expected_serve_kda_moe(
            periods=CFG.periods,
            chunk=fn.__name__.startswith("serve_prefill_b"))
        assert analysis.collective_census(fn, *args).as_dict() == \
            want["census"], fn.__name__
        for buf in eng.pool.caches():
            assert analysis.pool_scan_operands(
                fn, *args, pool_shape=buf.shape) == \
                want["pool_scan_operands"], fn.__name__
        assert analysis.view_head_splits(fn, *args, **geometry) == \
            want["view_head_splits"]
        assert analysis.gathered_view_gathers(
            fn, *args, num_blocks=eng.pool.num_blocks,
            table_width=eng.table_width) == \
            want["gathered_view_gathers"], fn.__name__
        assert analysis.row_walk_calls(
            fn, *args, pool_shape=eng.pool.k.shape, pools=1) == \
            want["row_walk_calls"], fn.__name__
        # the latent pool and both state buffers are donated
        assert not analysis.donation_report(
            fn, *args).undonated_aliasable, fn.__name__


# ---------------------------------------------------------------------
# refusals: both kinds apply
# ---------------------------------------------------------------------
@pytest.mark.parametrize("kw,match", [
    (dict(prefix_cache=True), "snapshots"),
    (dict(kv_tier_bytes=1 << 20), "host tier"),
    (dict(spec=True), "rolls the recurrent state back"),
    (dict(adapters=True), "LoRA"),
    (dict(attn_kernel="pallas"), "attn_kernel='pallas'"),
    (dict(kv_dtype="int8"), "conv tail|scaled or float8 KV"),
    (dict(kv_dtype="fp8"), "conv tail|scaled or float8 KV"),
    (dict(weights_dtype="int8"), "scaled weight layout"),
])
def test_what_a_latent_and_recurrent_family_cannot_serve_is_refused(
        params, kw, match):
    with pytest.raises(NotImplementedError, match=match):
        _engine(params, **kw)


@pytest.mark.parametrize("axis,match", [("tp", "head-sharded"),
                                        ("sp", "ring form"),
                                        ("ep", "mesh")])
def test_a_mesh_is_refused(params, axis, match):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), (axis,))
    kw = {"tp": {"tp_axis": "tp"}, "ep": {"ep_axis": "ep"},
          "sp": {"sp_axis": "sp"}}[axis]
    with pytest.raises(NotImplementedError, match=match):
        _engine(params, mesh=mesh, **kw)
    with pytest.raises(NotImplementedError, match="tp.*ep|ep.*tp"):
        ling_hybrid_partition_specs("tp", "ep")


@pytest.mark.parametrize("call,match", [
    (lambda e, p: e.export_kv_chain(p), "handoff payload"),
    (lambda e, p: e.import_kv_chain({}), "handoff payload"),
    (lambda e, p: e.submit(p, 4, prefill_only=True), "prefill-phase"),
])
def test_kv_only_methods_are_refused_when_called(params, call, match):
    eng = _engine(params)
    with pytest.raises(NotImplementedError, match=match):
        call(eng, _prompts(23, (9,))[0])


def test_the_contracts_refuse_what_the_engine_would(params):
    pool = _pool()
    k, ssm, conv = pool.caches()
    tok = jnp.zeros((2,), jnp.int32)
    tables = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="state buffers"):
        FAMILY.decode(params, k, None, tok, tok, tables, BS)
    with pytest.raises(NotImplementedError, match="v_pool=None"):
        FAMILY.decode(params, k, k, tok, tok, tables, BS, state=(ssm, conv))


# ---------------------------------------------------------------------
# the config
# ---------------------------------------------------------------------
def test_the_config_reads_the_hugging_face_keys_and_the_share():
    cfg = LingHybridConfig.from_dict({
        "model_type": "bailing_hybrid", "num_hidden_layers": 6,
        "layer_group_size": 6, "first_k_dense_replace": 2,
        "num_experts": 128, "num_experts_published": 512,
        "experts_first": 0, "vocab_size": 39296, "n_group": 8,
        "topk_group": 4, "kda_lower_bound": -5, "q_lora_rank": None,
        "expert_swiglu_limit_list": [0] * 42, "rope_theta": 6000000})
    assert cfg.layer_types == ("kda_dense", "kda_dense", "kda_moe",
                               "kda_moe", "kda_moe", "mla_moe")
    assert (cfg.periods, cfg.n_kda_layers, cfg.n_moe_layers) == (1, 5, 4)
    assert cfg.latent_width == 576 and cfg.kda.d_qkv == 12288
    args = cfg.moe_args
    assert (args.n_experts, args.experts_held, args.n_group,
            args.topk_group, args.top_k) == (512, (0, 128), 8, 4, 8)
    fam = ling_hybrid_family(cfg)
    assert fam.latent == 576 and fam.n_layers == 1
    assert fam.state.n_layers == 5 and fam.state.ssm == (32, 128, 128)
    assert fam.state.conv == (3 * 12288,)
    full = LingHybridConfig()
    assert full.layer_types.count("mla_moe") == 7
    assert full.layer_types[:6] == cfg.layer_types
    for bad, match in [(dict(num_nextn_predict_layers=1),
                        "num_nextn_predict_layers"),
                       (dict(q_lora_rank=1536), "q_lora_rank"),
                       (dict(num_hidden_layers=8), "whole groups"),
                       (dict(first_k_dense_replace=6), "dense layers"),
                       (dict(topk_method="greedy"), "topk_method")]:
        with pytest.raises(NotImplementedError, match=match):
            LingHybridConfig(**bad)
    with pytest.raises(ValueError, match="not among the router's"):
        LingHybridConfig(num_experts=128, num_experts_published=512,
                         experts_first=448)
