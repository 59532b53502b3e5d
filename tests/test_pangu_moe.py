"""openPangu-Ultra-MoE at tiny widths on the CPU (hidden 64, 4 heads,
ranks 24/16, 16 experts top-4 of which 8 are held, 2 dense + 3 MoE
layers): latent attention's two forms, the dropless router, the share
of one chip, and ``ServeEngine`` itself against the plain reference
(benchmarks/lib/reference_pangu_moe.py: f32, ``precision="highest"``,
no cache, no absorption, a loop over experts with a mask; nothing
imported from ``quintnet_tpu``).

Tolerances. Everything here is f32 on the CPU, where a matmul is exact
f32: the forms differ in the ORDER of their sums (the absorbed
contraction goes through the latent space, the grouped matmul through
sorted rows), a few ulp of values of size about 1. ``ATOL`` 2e-4 is a
hundred times that and far under what any missing piece does (one
expert's part is 0.1 of a layer's output).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.pangu_moe import (ABSORBED, MATERIALIZED,
                                           PanguMoEConfig, mla_paged,
                                           pangu_moe_init)
from quintnet_tpu.nn.attention import rope_cos_sin
from quintnet_tpu.nn.moe import MoEArgs, moe_apply, moe_held_init
from quintnet_tpu.serve import ServeEngine, SpecConfig, pangu_moe_family
from quintnet_tpu.serve.kv_pool import KVPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_pangu_moe", os.path.join(
            ROOT, "benchmarks", "lib", "reference_pangu_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()
CFG = PanguMoEConfig.tiny()
CFG_DICT = dataclasses.asdict(CFG)
FAMILY = pangu_moe_family(CFG)


@pytest.fixture(scope="module")
def params():
    return pangu_moe_init(jax.random.key(11), CFG)


def _engine(params, **kw):
    opts = dict(max_slots=3, block_size=4, num_blocks=96, max_seq_len=96,
                prefix_cache=False)
    opts.update(kw)
    return ServeEngine(FAMILY, params, **opts)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _pool(num_blocks=24, block_size=4, dtype=jnp.float32):
    return KVPool(n_layers=CFG.num_hidden_layers, n_kv_heads=1,
                  head_dim=CFG.latent_width, block_size=block_size,
                  num_blocks=num_blocks, dtype=dtype, latent=CFG.latent_width,
                  prefix_cache=True)


# ---------------------------------------------------------------------
# the family's contracts against the reference's full forward
# ---------------------------------------------------------------------
def _table(pool, n_tokens, width):
    blocks = pool.acquire(pool.blocks_for(n_tokens))
    row = np.zeros((width,), np.int32)
    row[:len(blocks)] = blocks
    return row


def test_prefill_logits_equal_the_reference(params):
    pool = _pool()
    (ids,) = _prompts(1, [11])
    bucket = np.zeros((1, 16), np.int32)
    bucket[0, :11] = ids
    row = _table(pool, 16, 6)
    logits, k, stats = FAMILY.prefill_from(
        params, pool.k, None, jnp.asarray(bucket), jnp.int32(0),
        jnp.int32(11), jnp.asarray(row), 4)
    want, _ = reference.forward(params, jnp.asarray(ids[None]), CFG_DICT,
                                positions=[10])
    np.testing.assert_allclose(logits, want[:, 0], atol=ATOL)
    assert float(stats["dropped"]) == 0.0
    # the bucket's five pad columns are routed nowhere
    assert float(stats["assigned"]) == (
        11 * CFG.num_experts_per_tok * CFG.n_moe_layers)


def test_prefill_then_paged_decode_equals_the_full_forward(params):
    """Position by position: a prompt through the materialized prefill,
    then every further token through the absorbed decode step and the
    paged latent cache, teacher-forced, beside an empty slot."""
    pool = _pool()
    (ids,) = _prompts(2, [19])
    n0 = 7
    tables = np.zeros((2, 6), np.int32)
    tables[0] = _table(pool, 20, 6)
    bucket = np.zeros((1, 8), np.int32)
    bucket[0, :n0] = ids[:n0]
    logits, k, _ = FAMILY.prefill_from(
        params, pool.k, None, jnp.asarray(bucket), jnp.int32(0),
        jnp.int32(n0), jnp.asarray(tables[0]), 4)
    got = [logits[0]]
    decode = jax.jit(lambda k, tok, pos: FAMILY.decode(
        params, k, None, tok, pos, jnp.asarray(tables), 4))
    for t in range(n0, 19):
        logits, k, stats = decode(k, jnp.asarray([ids[t], 0], jnp.int32),
                                  jnp.asarray([t, 0], jnp.int32))
        got.append(logits[0])
        assert float(stats["dropped"]) == 0.0
        # the empty slot's token is padding: one live token a layer
        assert float(stats["assigned"]) == (
            CFG.num_experts_per_tok * CFG.n_moe_layers)
    want, _ = reference.forward(params, jnp.asarray(ids[None]), CFG_DICT,
                                positions=list(range(n0 - 1, 19)))
    np.testing.assert_allclose(jnp.stack(got), want[0], atol=ATOL)


def test_a_prompt_fed_in_chunks_equals_the_same_prompt_whole(params):
    """The second and third calls read the first's latent rows out of
    the pool and rebuild keys and values from them."""
    (ids,) = _prompts(3, [21])

    def feed(cuts):
        pool = _pool()
        row = jnp.asarray(_table(pool, 24, 6))
        k, lo = pool.k, 0
        for hi in cuts:
            bucket = np.zeros((1, 24), np.int32)
            bucket[0, :hi - lo] = ids[lo:hi]
            logits, k, _ = FAMILY.prefill_from(
                params, k, None, jnp.asarray(bucket), jnp.int32(lo),
                jnp.int32(hi), row, 4)
            lo = hi
        return logits, k

    whole, k_whole = feed([21])
    parts, k_parts = feed([8, 13, 21])
    np.testing.assert_allclose(parts, whole, atol=ATOL)
    want, _ = reference.forward(params, jnp.asarray(ids[None]), CFG_DICT,
                                positions=[20])
    np.testing.assert_allclose(parts, want[:, 0], atol=ATOL)


def test_verify_equals_decode_equals_reference(params):
    """Four tokens a row through the absorbed form at once (verify's
    width) against the reference: the forms agree at every width."""
    pool = _pool()
    (ids,) = _prompts(4, [12])
    tables = np.zeros((1, 6), np.int32)
    tables[0] = _table(pool, 12, 6)
    bucket = np.zeros((1, 8), np.int32)
    bucket[0, :8] = ids[:8]
    _, k, _ = FAMILY.prefill_from(
        params, pool.k, None, jnp.asarray(bucket), jnp.int32(0),
        jnp.int32(8), jnp.asarray(tables[0]), 4)
    logits, k, _ = FAMILY.verify(
        params, k, None, jnp.asarray(ids[None, 8:12]),
        jnp.asarray([8], jnp.int32), jnp.asarray([4], jnp.int32),
        jnp.asarray(tables), 4)
    want, _ = reference.forward(params, jnp.asarray(ids[None]), CFG_DICT,
                                positions=[8, 9, 10, 11])
    np.testing.assert_allclose(logits, want, atol=ATOL)


# ---------------------------------------------------------------------
# latent attention: absorbed = materialized, on the same rows
# ---------------------------------------------------------------------
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, ATOL),
                                        (jnp.bfloat16, 0.06)])
def test_absorbed_equals_materialized_on_the_same_rows(params, dtype, atol):
    """Both forms on the same pool, the same run and the same earlier
    rows (written by a first call). In f32 they differ by the order of
    sums; on a bf16 pool each rounds what IT contracts against the rows
    (the absorbed query, the rebuilt keys and values), 2^-9 relative a
    product, on outputs of size about 1."""
    p = jax.tree.map(lambda a: a[0], params["blocks"]["dense"])["attn"]
    pool = _pool(dtype=dtype)
    tables = np.zeros((2, 6), np.int32)
    tables[0], tables[1] = _table(pool, 20, 6), _table(pool, 20, 6)
    tables = jnp.asarray(tables)
    x = jax.random.normal(jax.random.key(5), (2, 20, CFG.hidden_size))

    def run(k, lo, hi, form):
        pos = jnp.broadcast_to(jnp.arange(lo, hi)[None], (2, hi - lo))
        cos, sin = rope_cos_sin(pos, CFG.qk_rope_head_dim,
                                theta=CFG.rope_theta)
        return mla_paged(p, x[:, lo:hi], k, 1, pos,
                         jnp.full((2,), hi - lo), tables, 4, CFG, cos, sin,
                         form=form)

    _, k = run(pool.k, 0, 13, MATERIALIZED)
    y_abs, k_abs = run(k, 13, 20, ABSORBED)
    y_mat, k_mat = run(k, 13, 20, MATERIALIZED)
    np.testing.assert_allclose(y_abs, y_mat, atol=atol)
    np.testing.assert_array_equal(np.asarray(k_abs, np.float32),
                                  np.asarray(k_mat, np.float32))
    assert float(jnp.abs(y_abs).max()) > 0.1


def test_pad_lanes_of_the_latent_row_stay_zero(params):
    """24 features in rows of 128 lanes: every writer leaves lanes 24
    on at zero (the absorbed form multiplies them by the query's zero
    lanes instead of cutting them off), whatever the programs wrote."""
    eng = _engine(params, prefix_cache=True)
    assert eng.pool.k.shape[-1] == 128 and eng.pool.v is None
    for ids in _prompts(6, [5, 9, 13, 17]):
        eng.submit(ids, 6)
    eng.run()
    k = np.asarray(eng.pool.k)
    assert np.abs(k[..., :CFG.latent_width]).max() > 0
    assert not k[..., CFG.latent_width:].any()
    assert eng.pool.bytes_per_token == (
        CFG.num_hidden_layers * CFG.latent_width * 4)


# ---------------------------------------------------------------------
# the dropless router
# ---------------------------------------------------------------------
ARGS = MoEArgs(n_experts=16, top_k=4, dropless=True, scoring="sigmoid",
               routed_scale=2.5)


def _moe_case(seed, held=16, shared=32):
    p = moe_held_init(jax.random.key(seed), 64, 32, 16, held=held,
                      shared_hidden=shared)
    x = jax.random.normal(jax.random.key(seed + 1), (2, 9, 64))
    return p, x


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_router_equals_a_hand_written_top_k(scoring):
    """On tie-free inputs: the scores (the family's sigmoid, or the
    softmax the capacity router has), the four largest by a sort, their
    weights normalised over the four and scaled by 2.5; the layer's
    output is the shared expert plus exactly those experts' parts."""
    p, x = _moe_case(20)
    y, _, stats = moe_apply(p, x, ARGS._replace(scoring=scoring),
                            return_stats=True)
    z = (np.asarray(x, np.float64).reshape(18, 64)
         @ np.asarray(p["router"]["w"], np.float64))
    s = (1.0 / (1.0 + np.exp(-z)) if scoring == "sigmoid"
         else np.exp(z) / np.exp(z).sum(axis=-1, keepdims=True))
    order = np.argsort(-s, axis=-1)[:, :4]
    assert np.all(np.diff(np.sort(s, axis=-1), axis=-1) > 1e-10)  # no ties
    want = np.zeros((18, 64))
    xt = np.asarray(x, np.float64).reshape(18, 64)
    e = jax.tree.map(lambda a: np.asarray(a, np.float64), p["experts"])
    sh = jax.tree.map(lambda a: np.asarray(a, np.float64), p["shared"])

    def swiglu(g, u, d, v):
        a = v @ g
        return (a / (1 + np.exp(-a)) * (v @ u)) @ d

    for t in range(18):
        w = 2.5 * s[t, order[t]] / s[t, order[t]].sum()
        want[t] = swiglu(sh["gate"]["w"], sh["up"]["w"], sh["down"]["w"],
                         xt[t])
        for j, wj in zip(order[t], w):
            want[t] += wj * swiglu(e["gate"]["w"][j], e["up"]["w"][j],
                                   e["down"]["w"][j], xt[t])
    np.testing.assert_allclose(y.reshape(18, 64), want, atol=ATOL)
    counts = np.bincount(order.reshape(-1), minlength=16)
    np.testing.assert_array_equal(stats["expert_tokens"], counts)
    assert float(stats["dropped"]) == 0 and float(stats["assigned"]) == 72


def test_the_shares_of_all_chips_sum_to_the_uncut_layer():
    """THE share test: the routed parts that all ``E / held`` shares
    give (each told which experts it holds, each routing over the full
    router), with the shared expert counted once, add up to the uncut
    reference layer — and so do the reference's own shares."""
    p, x = _moe_case(30)
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True, "n_routed_experts": 16,
           "experts_first": 0}
    whole, _ = reference.moe(p, x, cfg)
    shared_only = p["shared"]
    total = reference._swiglu_jit(shared_only, x)
    total_ref = total
    rows = touched = 0
    for first in range(0, 16, 4):
        part = {"router": p["router"],
                "experts": jax.tree.map(lambda a: a[first:first + 4],
                                        p["experts"])}
        y, _, st = moe_apply(part, x, ARGS._replace(experts_held=(first, 4)),
                             return_stats=True)
        total = total + y
        rows += float(st["held_rows"])
        touched += float(st["touched"])
        assert float(st["held_rows"]) + float(st["elsewhere"]) == 72
        y_ref, _ = reference.moe({**part, "shared": jax.tree.map(
            jnp.zeros_like, shared_only)}, x, cfg, experts_held=(first, 4))
        total_ref = total_ref + y_ref
    np.testing.assert_allclose(total, whole, atol=ATOL)
    np.testing.assert_allclose(total_ref, whole, atol=ATOL)
    assert rows == 72 and 4 <= touched <= 16


@pytest.mark.parametrize("hot", [0, 9, 15])
def test_no_routing_is_dropped_at_any_skew(hot):
    """Every token's first choice is ONE expert (its router column made
    huge): the capacity router would drop nearly all of them; here the
    expert runs all 18 rows and the output is the reference's."""
    p, x = _moe_case(40 + hot)
    w = p["router"]["w"]
    p = {**p, "router": {"w": w.at[:, hot].set(
        50.0 * jnp.sign(jnp.mean(x.reshape(-1, 64), axis=0)) + w[:, hot])}}
    x = jnp.abs(x)                      # every token scores the column high
    p["router"]["w"] = p["router"]["w"].at[:, hot].set(1.0)
    y, _, st = moe_apply(p, x, ARGS, return_stats=True)
    assert float(st["expert_tokens"][hot]) == 18
    assert float(st["dropped"]) == 0
    assert float(st["held_rows"]) == 72
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True, "n_routed_experts": 16,
           "experts_first": 0}
    want, idx = reference.moe(p, x, cfg)
    assert bool((idx == hot).any(axis=-1).all())
    np.testing.assert_allclose(y, want, atol=ATOL)


def test_masked_tokens_are_routed_nowhere():
    p, x = _moe_case(50)
    mask = jnp.arange(9)[None, :] < jnp.asarray([9, 4])[:, None]
    y, _, st = moe_apply(p, x, ARGS, return_stats=True, token_mask=mask)
    full, _, _ = moe_apply(p, x, ARGS, return_stats=True)
    assert float(st["assigned"]) == 13 * 4
    np.testing.assert_allclose(y[1, :4], full[1, :4], atol=ATOL)
    # a masked token keeps the shared expert's part only
    np.testing.assert_allclose(
        y[1, 4:], reference._swiglu_jit(p["shared"], x)[1, 4:], atol=ATOL)


def test_the_capacity_router_refuses_the_dropless_arguments():
    from quintnet_tpu.nn.moe import moe_init

    p = moe_init(jax.random.key(0), 64, 32, 4)
    x = jnp.zeros((1, 3, 64))
    with pytest.raises(NotImplementedError, match="dropless"):
        moe_apply(p, x, MoEArgs(n_experts=4, scoring="sigmoid"))
    with pytest.raises(NotImplementedError, match="dropless"):
        moe_apply(p, x, MoEArgs(n_experts=4, experts_held=(0, 2)))
    q, xq = _moe_case(1)
    with pytest.raises(ValueError, match="experts_held"):
        moe_apply(q, xq, ARGS._replace(experts_held=(4, 8)))


# ---------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------
def _greedy_reference(params, prompt, n_new):
    ids = list(prompt)
    for _ in range(n_new):
        logits, _ = reference.forward(params, jnp.asarray([ids]), CFG_DICT,
                                      positions=[len(ids) - 1])
        ids.append(int(jnp.argmax(logits[0, 0])))
    return ids


def test_engine_tokens_equal_the_references_greedy_decode(params):
    eng = _engine(params)
    prompts = _prompts(7, [5, 9, 14, 6])
    rids = [eng.submit(p, 5) for p in prompts]
    eng.run()
    for rid, prompt in zip(rids, prompts):
        assert list(eng.result(rid)) == _greedy_reference(params, prompt, 5)
    assert eng.metrics.moe_dropped_tokens == 0
    assert all(r["attrs"]["moe_dropped_tokens"] == 0
               for r in eng.recorder.snapshot() if r["attrs"])


def _tokens(params, prompts, n_new=6, **kw):
    eng = _engine(params, **kw)
    rids = [eng.submit(p, n_new) for p in prompts]
    eng.run()
    return eng, [list(eng.result(r)) for r in rids]


def test_chunked_prefill_through_the_engine_equals_one_shot(params):
    prompts = _prompts(8, [45, 7, 30])
    _, want = _tokens(params, prompts)
    eng, got = _tokens(params, prompts, prefill_len=16,
                       chunked_prefill=True, prefill_chunk_budget=16)
    assert got == want
    assert eng.metrics.prefill_chunks >= 5


def test_the_prefix_cache_serves_latent_blocks(params):
    """A second request with the same 13-token prefix re-reads the first's
    published latent blocks (three whole ones and a copy-on-write of the
    fourth) and samples the same tokens as a cold engine."""
    rng = np.random.default_rng(9)
    shared = rng.integers(0, CFG.vocab_size, 13)
    a = np.concatenate([shared, rng.integers(0, CFG.vocab_size, 4)])
    b = np.concatenate([shared, rng.integers(0, CFG.vocab_size, 6)])
    _, (want_b,) = _tokens(params, [b])
    eng = _engine(params, prefix_cache=True)
    ra = eng.submit(a, 6)
    eng.run()
    rb = eng.submit(b, 6)
    eng.run()
    assert list(eng.result(rb)) == want_b
    assert eng.metrics.prefix_hit_tokens >= 12


def test_a_preempted_request_resumes_to_the_same_tokens(params):
    prompts = _prompts(10, [14, 15, 13])
    _, want = _tokens(params, prompts, n_new=12)
    eng, got = _tokens(params, prompts, n_new=12, num_blocks=15)
    assert eng.metrics.preempted >= 1
    assert got == want


def test_speculative_verify_commits_the_plain_tokens(params):
    rng = np.random.default_rng(12)
    motif = rng.integers(0, CFG.vocab_size, 5)
    prompts = [np.tile(motif, 4)[:n] for n in (17, 20)]
    _, want = _tokens(params, prompts, n_new=10)
    eng, got = _tokens(params, prompts, n_new=10, spec=SpecConfig())
    assert got == want


def test_a_latent_chain_exports_and_imports(params):
    """The handoff payload of a latent pool: records of ``k`` alone,
    byte-exact into another engine's pool, through the fleet's wire."""
    from quintnet_tpu.fleet import wire

    (prompt,) = _prompts(13, [17])
    src = _engine(params, prefix_cache=True)
    rid = src.submit(prompt, 3)
    src.run()
    chain = src.export_kv_chain(prompt)
    assert chain is not None and chain["n_tokens"] >= 16
    assert all("v" not in r and r["k"].shape == (
        CFG.num_hidden_layers, 4, 1, CFG.latent_width)
        for r in chain["blocks"])
    chain = wire.kv_chain_from_wire(wire.kv_chain_to_wire(chain))[0]
    dst = _engine(params, prefix_cache=True)
    assert dst.import_kv_chain(chain) == chain["n_tokens"]
    r2 = dst.submit(prompt, 3)
    dst.run()
    assert list(dst.result(r2)) == list(src.result(rid))
    assert dst.metrics.prefix_hit_tokens >= 16


def test_the_ring_carries_the_latent_and_expert_facts(params):
    eng = _engine(params)
    for p in _prompts(14, [6, 9]):
        eng.submit(p, 4)
    eng.run()
    st = eng.recorder.static
    assert st["kv_bytes_per_token"] == 5 * CFG.latent_width * 4
    e = params["blocks"]["moe"]["moe"]["experts"]
    assert st["expert_param_bytes"] == sum(
        int(x.nbytes) for x in jax.tree.leaves(e))
    assert sorted(st["programs"])[0] == "serve_decode"
    decoded = [r for r in eng.recorder.snapshot()
               if r["decoding"] and not r["prefill_tokens"]]
    assert decoded
    for r in decoded:
        a = r["attrs"]
        k = CFG.num_experts_per_tok * CFG.n_moe_layers * r["decoding"]
        assert a["expert_rows"] + a["routed_elsewhere"] == k
        assert a["decode_expert_rows"] == a["expert_rows"]
        assert sum(a["decode_held_expert_tokens"]) == a["expert_rows"]
        assert 0 < a["experts_touched"] <= min(
            k, CFG.n_moe_layers * CFG.n_routed_experts)
        assert a["moe_dropped_tokens"] == 0
        # the tiny preset is off the 128-lane grid: its programs keep
        # ragged_dot and no kernel visits a tile (PR 38)
        assert a["expert_tile_visits"] == 0
        assert a["decode_expert_tile_visits"] == 0


@pytest.mark.parametrize("traffic", ("steady", "chunked", "speculative"))
def test_bf16_pool_tokens_equal_walked_and_gathered(params, traffic,
                                                    monkeypatch):
    """Greedy tokens of a bf16-pool engine are the same whether its
    decode and verify programs walk each row's live latent rows in
    place (the kernel, under the interpreter) or gather the table's
    width, the form they took before and still take off the TPU —
    through a steady batch, a prompt prefilled in chunks beside
    decoding rows, and speculation."""
    import importlib

    from quintnet_tpu import analysis

    pa = importlib.import_module("quintnet_tpu.ops.paged_attention")
    kw = dict(kv_dtype="bf16", weights_dtype="bf16")
    prompts = _prompts(15, [5, 12, 3])
    if traffic == "chunked":
        prompts = _prompts(15, [4, 37])
        kw.update(chunked_prefill=True, prefill_len=16)
    elif traffic == "speculative":
        motif = np.random.default_rng(16).integers(0, CFG.vocab_size, 5)
        prompts = [np.tile(motif, 4)[:n] for n in (17, 20)]
        kw.update(spec=SpecConfig())
    served = {}
    for form, interpret in (("walk", True), ("gathered", False)):
        monkeypatch.setattr(pa, "INTERPRET", interpret)
        eng, served[form] = _tokens(params, prompts, n_new=8, **kw)
        for sentinel, args in eng._warmup_calls():
            name = sentinel.fn.__name__
            if name.startswith("serve_prefill_b"):
                continue
            assert analysis.row_walk_calls(
                sentinel.fn, *args, pool_shape=eng.pool.k.shape,
                pools=1) == 2 * (form == "walk"), name
    assert served["walk"] == served["gathered"]


@pytest.mark.parametrize("spec", (False, True))
def test_a_step_counts_the_live_key_blocks_it_read(params, spec,
                                                   monkeypatch):
    """On a bf16 pool the decode program and every verify bucket note
    the walk's key block as what they read of a row
    (``_read_granule``), so a step's ``attended_rows`` is each row of
    the program — the one that sat out too, at position 0 — rounded up
    to that block, x the 5 layers, not rows x the table's width."""
    import quintnet_tpu.nn.attention as attention

    kb, slots = 8, 3
    monkeypatch.setattr(attention, "WALK_KEY_BLOCK", kb)
    eng = _engine(params, kv_dtype="bf16", weights_dtype="bf16",
                  spec=SpecConfig(max_draft=4) if spec else None)
    layers = eng.recorder.static["paged_layers"]
    assert layers == CFG.num_hidden_layers == 5
    width = eng.table_width * eng.pool.block_size
    rng = np.random.default_rng(17)
    motif = rng.integers(0, CFG.vocab_size, 4)
    lens = (9, 14)                               # the third slot sits out
    for n in lens:
        eng.submit(np.tile(motif, 4)[:n], 12)
    run = 1
    for step in range(5):
        before = np.array(eng._pos)
        eng.step()
        rec = eng.recorder.last()
        if rec["spec_step"]:
            # a verify run reads up to its bucket's last column
            run = 1 + max(b for b in eng.spec.buckets
                          if f"serve_verify_b{b}" in eng._read_granule)
        if step == 0:
            before = np.array(lens + (0,))       # admitted this step
        last = before + (run - 1 if rec["spec_step"] else 0)
        want = int(((last // kb + 1) * kb).sum()) * layers
        assert rec["attrs"]["attended_rows"] == want, (step, rec)
        assert want < slots * width * layers
    assert any(r["spec_step"] for r in eng.recorder.snapshot()) == spec
    eng.warmup()
    names = ["serve_decode"] + [f"serve_verify_b{b}"
                                for b in (eng.spec.buckets if spec else ())]
    assert {n: eng._read_granule[n] for n in names} == dict.fromkeys(names,
                                                                     kb)


def test_the_programs_are_named_and_their_census_is_pinned(params):
    """Every program is ``jit_serve_*`` like the other families'; on a
    bf16 pool none has a collective, a pool-shaped scan operand, a head
    split of the cached rows or a widened view dot; decode and every
    verify bucket gather nothing and walk the one pool in place, once a
    layer scan, where a prefill bucket gathers the one row kind once a
    scan and walks nothing
    (analysis/specs.expected_serve_latent_moe)."""
    from quintnet_tpu import analysis
    from quintnet_tpu.analysis.specs import expected_serve_latent_moe

    eng = _engine(params, kv_dtype="bf16", weights_dtype="bf16",
                  spec=SpecConfig(), max_seq_len=88)
    calls = list(eng._warmup_calls())
    names = sorted(s.fn.__name__ for s, _ in calls)
    assert names[0] == "serve_decode" and all(
        n.startswith(("serve_prefill_b", "serve_verify_b", "serve_decode"))
        for n in names)
    assert any(n.startswith("serve_verify_b") for n in names)
    text = calls[0][0].fn.lower(*calls[0][1]).as_text()
    assert "module @jit_serve_" in text
    geometry = dict(table_width=eng.table_width,
                    block_size=eng.pool.block_size)
    for sentinel, args in calls:
        fn = sentinel.fn
        want = expected_serve_latent_moe(
            chunk=fn.__name__.startswith("serve_prefill_b"))
        assert analysis.collective_census(fn, *args).as_dict() == \
            want["census"], fn.__name__
        assert analysis.pool_scan_operands(
            fn, *args, pool_shape=eng.pool.k.shape) == \
            want["pool_scan_operands"]
        assert analysis.view_head_splits(fn, *args, **geometry) == \
            want["view_head_splits"]
        assert analysis.widened_view_dots(fn, *args, **geometry) == \
            want["widened_view_dots"]
        assert analysis.gathered_view_gathers(
            fn, *args, num_blocks=eng.pool.num_blocks,
            table_width=eng.table_width) == \
            want["gathered_view_gathers"], fn.__name__
        assert analysis.row_walk_calls(
            fn, *args, pool_shape=eng.pool.k.shape, pools=1) == \
            want["row_walk_calls"], fn.__name__
        # the one pool buffer is donated and aliasable
        assert not analysis.donation_report(
            fn, *args).undonated_aliasable, fn.__name__


# ---------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------
@pytest.mark.parametrize("kw,match", [
    (dict(kv_dtype="int8"), "scaled or float8 KV"),
    (dict(kv_dtype="fp8"), "scaled or float8 KV"),
    (dict(kv_dtype="fake_quant"), "scaled or float8 KV"),
    (dict(weights_dtype="int8"), "scaled weight layout"),
    (dict(weights_dtype="fp8"), "scaled weight layout"),
    (dict(attn_kernel="pallas"), "attn_kernel='pallas'"),
    (dict(adapters=True), "adapters"),
])
def test_what_a_latent_family_cannot_serve_is_refused(params, kw, match):
    with pytest.raises(NotImplementedError, match=match):
        _engine(params, **kw)


@pytest.mark.parametrize("axis", ["tp", "ep", "sp"])
def test_a_mesh_is_refused_for_a_latent_family(params, axis):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), (axis,))
    kw = {"tp": {}, "ep": {"ep_axis": "ep"}, "sp": {"sp_axis": "sp"}}[axis]
    with pytest.raises(NotImplementedError, match="a mesh"):
        _engine(params, mesh=mesh, **kw)


def test_a_latent_pool_has_one_row_kind():
    pool = _pool()
    assert pool.caches() == (pool.k,) and pool.v is None
    assert pool.bytes_per_block == 5 * 4 * CFG.latent_width * 4
    with pytest.raises(ValueError, match="ONE row"):
        KVPool(n_layers=2, n_kv_heads=2, head_dim=12, block_size=4,
               num_blocks=8, latent=24)
    with pytest.raises(NotImplementedError, match="scaled policy"):
        KVPool(n_layers=2, n_kv_heads=1, head_dim=24, block_size=4,
               num_blocks=8, latent=24, policy="int8")
    with pytest.raises(ValueError, match="needs all 1"):
        pool.update(pool.k, pool.k)


def test_the_config_reads_the_hugging_face_keys_and_the_share():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "openpangu-ultra-moe-718b.json")) as f:
        d = json.load(f)
    cfg = PanguMoEConfig.from_dict(d)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.latent_width) == (7680, 128, 1536, 512,
                                                    576)
    assert (cfg.n_dense_layers, cfg.n_moe_layers) == (1, 4)
    args = cfg.moe_args
    assert (args.n_experts, args.top_k, args.experts_held,
            args.routed_scale, args.scoring, args.dropless) == (
                256, 8, (0, 16), 2.5, "sigmoid", True)
    with pytest.raises(NotImplementedError, match="sandwich_norm"):
        PanguMoEConfig.tiny(sandwich_norm=False)
    with pytest.raises(ValueError, match="are not among"):
        PanguMoEConfig.tiny(experts_first=12)
