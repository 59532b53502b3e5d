"""Laguna at tiny widths on the CPU (hidden 64, 6 / 8 query heads over 2
kv heads of 16 features, a window of 8, 16 experts top-4, the published
pattern's first five layers: full+dense, sliding+sparse x3,
full+sparse): the window store, the two attention shapes, the two
rotary settings, the gate, the router, and ``ServeEngine`` itself
against the plain reference (benchmarks/lib/reference_laguna.py: f32,
``precision="highest"``, no cache, no ring, full ``[T, T]`` masks, a
loop over experts with a mask; nothing imported from ``quintnet_tpu``).

Tolerances. Everything here is f32 on the CPU, where a matmul is exact
f32: the paged programs differ from the reference in the ORDER of
their sums (keys read in ring order, the grouped matmul through sorted
rows), a few ulp of values of size about 1. ``ATOL`` 2e-4 is a hundred
times that and far under what any missing piece does: every control of
the reference (the window ignored, the rotary settings swapped, the
gate or the attention factor left out) moves the logits by more than 1.

The guide's share test (the parts all chips compute add up to the
whole layer) does not apply: every expert, every head and the whole
vocabulary are held here; the configuration is cut in depth alone.
"""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models import laguna
from quintnet_tpu.models.laguna import (FULL, SLIDING, LagunaConfig,
                                        laguna_init, laguna_rope_tables)
from quintnet_tpu.nn import attention
from quintnet_tpu.nn.moe import moe_apply
from quintnet_tpu.serve import ServeEngine, SpecConfig, laguna_family
from quintnet_tpu.serve.kv_pool import KVPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-4
BS = 4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_laguna", os.path.join(
            ROOT, "benchmarks", "lib", "reference_laguna.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()
CFG = LagunaConfig.tiny()
CFG_DICT = CFG.to_dict()
FAMILY = laguna_family(CFG, block_size=BS)
WINDOW = CFG.sliding_window
RING = WINDOW + BS
WIDTH = 24                     # the tests' block tables: 96 positions


@pytest.fixture(scope="module")
def params():
    return laguna_init(jax.random.key(7), CFG)


@pytest.fixture(autouse=True)
def key_blocks_of_two_table_entries(monkeypatch):
    """A prefill chunk reads a global layer's cache 8 positions (two
    table entries) at a time here, so every prefill below walks several
    key blocks and stops at the last one it can see (the published
    1,024 would read these 96-position tables in one trip). Read at
    trace time: every test traces under the same value."""
    monkeypatch.setattr(laguna, "PREFILL_KEY_BLOCK", 8)


def _engine(params, **kw):
    opts = dict(max_slots=3, block_size=BS, num_blocks=96, max_seq_len=96,
                prefill_len=16, chunked_prefill=True, prefix_cache=False)
    opts.update(kw)
    return ServeEngine(FAMILY, params, **opts)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _pool(dtype=jnp.float32, max_slots=3, num_blocks=64):
    return KVPool(n_layers=FAMILY.n_layers,
                  n_kv_heads=CFG.num_key_value_heads, head_dim=CFG.head_dim,
                  block_size=BS, num_blocks=num_blocks, dtype=dtype,
                  prefix_cache=False, window=FAMILY.window,
                  max_slots=max_slots)


def _table(pool, n_tokens):
    blocks = pool.acquire(pool.blocks_for(n_tokens))
    row = np.zeros((WIDTH,), np.int32)
    row[:len(blocks)] = blocks
    return row


@jax.jit
def _prefill_program(params, k, v, wk, wv, bucket, lo, hi, row, slot):
    return FAMILY.prefill_from(params, k, v, bucket, lo, hi, row, BS,
                               window=(wk, wv), slot=slot)


@jax.jit
def _decode_program(params, k, v, wk, wv, tok, at, tables):
    return FAMILY.decode(params, k, v, tok, at, tables, BS,
                         window=(wk, wv))


def _prefill(params, pool, ids, lo, hi, width, row, slot):
    """Positions [lo, hi) of ``ids`` through ``prefill_from`` in a
    bucket of ``width``, into ring ``slot``: the logits at hi - 1."""
    bucket = np.zeros((1, width), np.int32)
    bucket[0, :hi - lo] = ids[lo:hi]
    logits, *bufs, stats = _prefill_program(
        params, *pool.caches(), jnp.asarray(bucket), jnp.int32(lo),
        jnp.int32(hi), jnp.asarray(row), jnp.int32(slot))
    pool.update(*bufs)
    return logits[0], stats


def _decode(params, pool, tok, at, tables):
    logits, *bufs, stats = _decode_program(
        params, *pool.caches(), jnp.asarray(tok), jnp.asarray(at),
        jnp.asarray(tables))
    pool.update(*bufs)
    return logits, stats


def _full_forward(params, ids, **kw):
    want, chosen = reference.forward(params, jnp.asarray(ids[None]),
                                     CFG_DICT, **kw)
    return want[0], chosen


# ---------------------------------------------------------------------
# the family's contracts against the reference's full forward
# ---------------------------------------------------------------------
def test_prefill_logits_equal_the_reference(params):
    pool = _pool()
    (ids,) = _prompts(1, [27])                      # three windows
    logits, stats = _prefill(params, pool, ids, 0, 27, 32,
                             _table(pool, 32), 1)
    want, _ = _full_forward(params, ids, positions=[26])
    np.testing.assert_allclose(logits, want[0], atol=ATOL)
    assert float(stats["dropped"]) == 0.0
    # the bucket's five pad columns are routed nowhere
    assert float(stats["assigned"]) == (
        27 * CFG.num_experts_per_tok * CFG.n_sparse_layers)
    assert float(stats["elsewhere"]) == 0.0


@pytest.mark.parametrize("calls", [
    [(0, 24, 32), (24, 36, 32), (36, 40, 32)],   # wide, wide, wide
    [(0, 24, 32), (24, 37, 16), (37, 40, 4)],    # the last WRITES, then reads
    [(0, 3, 4), (3, 40, 48)],                    # a narrow bucket first
])
def test_chunked_prefill_equals_one_shot_equals_the_reference(params, calls):
    """Chunks wider than the window (24 and 37 against 8), chunk calls
    that start past 0 and find the ring as the earlier ones left it, and
    buckets narrow enough to take the decode's order (write, then
    read): each call's logits at its last position are the reference's
    full forward's."""
    (ids,) = _prompts(2, [40])
    want, _ = _full_forward(params, ids)
    one = _pool()
    logits, _ = _prefill(params, one, ids, 0, 40, 48, _table(one, 48), 0)
    np.testing.assert_allclose(logits, want[39], atol=ATOL)
    pool = _pool()
    row = _table(pool, 48)
    for lo, hi, width in calls:
        logits, _ = _prefill(params, pool, ids, lo, hi, width, row, 2)
        np.testing.assert_allclose(logits, want[hi - 1], atol=ATOL)


def test_decode_and_verify_through_the_store_past_three_windows(params):
    """Position by position: a prompt through the chunk program, then
    every further token through the decode step — the ring wraps twice
    (28 positions over a ring of 12) — teacher-forced, beside an empty
    slot and a row that is mid-prefill; then a verify run of 5 tokens a
    row from where the decode stopped. All equal the reference's full
    forward (a window of 8: the sequence ends past five of them)."""
    pool = _pool()
    ids, other = _prompts(3, [45, 20])
    want, _ = _full_forward(params, ids)
    rows = {0: _table(pool, 48), 2: _table(pool, 24)}
    _prefill(params, pool, ids, 0, 12, 16, rows[0], 0)
    # slot 2 is in the middle of a chunked prefill: its ring is half
    # built and the decode steps below must not touch it
    _prefill(params, pool, other, 0, 14, 16, rows[2], 2)
    ring2 = np.asarray(pool.wk[:, 2 * RING:3 * RING])
    tables = np.zeros((3, WIDTH), np.int32)
    tables[0] = rows[0]
    for pos in range(12, 40):
        tok = np.zeros((3,), np.int32)
        at = np.zeros((3,), np.int32)
        tok[0], at[0] = ids[pos], pos
        logits, stats = _decode(params, pool, tok, at, tables)
        np.testing.assert_allclose(logits[0], want[pos], atol=ATOL,
                                   err_msg=f"position {pos}")
        # one live row: its token alone is routed
        assert float(stats["assigned"]) == (
            CFG.num_experts_per_tok * CFG.n_sparse_layers)
    np.testing.assert_array_equal(
        np.asarray(pool.wk[:, 2 * RING:3 * RING]), ring2)
    # verify: 5 tokens a row (block_size + 1, the most a ring allows)
    run = np.zeros((3, 5), np.int32)
    run[0] = ids[40:45]
    k, v, wk, wv = pool.caches()
    logits, *bufs, _ = FAMILY.verify(
        params, k, v, jnp.asarray(run), jnp.asarray([40, 0, 0], jnp.int32),
        jnp.asarray([5, 0, 0], jnp.int32), jnp.asarray(tables), BS,
        window=(wk, wv))
    np.testing.assert_allclose(logits[0], want[40:45], atol=ATOL)
    with pytest.raises(ValueError, match="block_size \\+ 1"):
        FAMILY.verify(params, k, v, jnp.zeros((3, 6), jnp.int32),
                      jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32),
                      jnp.asarray(tables), BS, window=(wk, wv))


def test_two_rows_of_very_different_lengths_in_one_batch(params):
    """A row 5 tokens long (under a window: most of its ring is an
    earlier owner's and must stay masked) beside one of 44 (past five
    windows), decoding together."""
    pool = _pool()
    # an earlier owner fills every ring with its own keys
    (junk,) = _prompts(4, [30])
    for slot in range(3):
        _prefill(params, pool, junk, 0, 30, 32, _table(pool, 32), slot)
    short, long_ = _prompts(5, [8, 47])
    rows = [_table(pool, 12), _table(pool, 48)]
    _prefill(params, pool, short, 0, 5, 16, rows[0], 0)
    _prefill(params, pool, long_, 0, 44, 48, rows[1], 1)
    tables = np.zeros((3, WIDTH), np.int32)
    tables[0], tables[1] = rows
    want = [_full_forward(params, short)[0], _full_forward(params, long_)[0]]
    for step in range(3):
        tok = np.asarray([short[5 + step], long_[44 + step], 0], np.int32)
        at = np.asarray([5 + step, 44 + step, 0], np.int32)
        logits, _ = _decode(params, pool, tok, at, tables)
        np.testing.assert_allclose(logits[0], want[0][5 + step], atol=ATOL)
        np.testing.assert_allclose(logits[1], want[1][44 + step], atol=ATOL)


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_a_control_that_leaves_a_piece_out_fails(params, control):
    """The reference with the window ignored, the two rotary settings
    swapped, the gate or the attention factor left out is NOT what the
    programs compute: the comparison above would refuse each."""
    (ids,) = _prompts(6, [40])
    pool = _pool()
    logits, _ = _prefill(params, pool, ids, 0, 40, 48, _table(pool, 48), 0)
    wrong, _ = _full_forward(params, ids, positions=[39],
                             controls=(control,))
    assert float(jnp.abs(logits - wrong[0]).max()) > 100 * ATOL


# ---------------------------------------------------------------------
# the engine, through its normal path
# ---------------------------------------------------------------------
def _assert_greedy(params, prompt, out, n):
    """``out`` = prompt + n generated tokens is what the reference
    generates greedily: ONE full forward over all of it, whose argmax
    at every position from the prompt's last on is the next token."""
    assert list(out[:len(prompt)]) == list(prompt) and len(out) == (
        len(prompt) + n)
    logits, _ = _full_forward(params, np.asarray(out[:-1], np.int32))
    np.testing.assert_array_equal(
        np.argmax(logits[len(prompt) - 1:], axis=-1), out[len(prompt):])


def test_the_engine_serves_what_the_reference_generates(params):
    """Four requests over three slots, prompts of 5 to 40 tokens through
    16-wide chunks, 12 tokens each: greedy tokens equal the reference's
    full forward, token by token; the ring's ledger is empty at the end
    and the step ring carries the window's counters."""
    eng = _engine(params)
    prompts = _prompts(8, [30, 5, 21, 40])
    rids = [eng.submit(p, 12) for p in prompts]
    eng.run()
    for p, rid in zip(prompts, rids):
        _assert_greedy(params, p, eng.result(rid), 12)
    assert eng.pool.window_owner == [None] * 3
    assert eng.pool.num_used == 0 and eng.metrics.moe_dropped_tokens == 0
    static = eng.recorder.static
    assert static["window_bytes_per_slot"] == eng.pool.window_bytes_per_slot \
        == 2 * 3 * RING * 2 * 16 * 4
    assert static["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4   # 2 layers
    assert static["layer_pattern"] == [
        "full_dense", "sliding_sparse", "sliding_sparse", "sliding_sparse",
        "full_sparse"]
    assert static["expert_param_bytes"] == sum(
        int(x.nbytes) for x in jax.tree.leaves(
            eng.params["blocks"]["experts"]))
    for rec in eng.recorder.snapshot():
        if not rec["decoding"]:
            continue
        a = rec["attrs"]
        assert a["global_rows"] == rec["context_tokens"] * 2
        # a row contributes at most the window, a layer
        assert 0 < a["window_rows"] <= rec["decoding"] * WINDOW * 3
        assert a["routed_elsewhere"] == 0
        assert a["decode_expert_rows"] == (
            rec["decoding"] * CFG.num_experts_per_tok * 4)


def test_a_sequence_preempted_and_prefilled_again(params):
    """A pool too small for three sequences to finish together: the
    youngest is preempted (its blocks AND its ring given back) and
    re-prefilled from position 0 into whatever slot is free; every
    request still generates the reference's tokens."""
    eng = _engine(params, num_blocks=19)
    prompts = _prompts(9, [20, 18, 22])
    rids = [eng.submit(p, 14) for p in prompts]
    eng.run()
    assert eng.metrics.preempted >= 1
    for p, rid in zip(prompts, rids):
        _assert_greedy(params, p, eng.result(rid), 14)
    assert eng.pool.window_owner == [None] * 3 and eng.pool.num_used == 0


def test_both_head_counts_keep_the_lane_diagonal_form(params):
    """On a bf16 pool the decode program and a verify run of 5 contract
    the cached rows as stored for 6 and 8 query heads alike (the global
    layers walk each row's live blocks of the pool in place: no view of
    the table's width at all; no head split of a ring), the census of every
    program is the pinned one, and the bf16 programs track the f32
    reference to bf16's rounding."""
    from quintnet_tpu import analysis
    from quintnet_tpu.analysis.specs import expected_serve_window_moe

    eng = _engine(params, kv_dtype="bf16", weights_dtype="bf16")
    fam, pool = eng.family, eng.pool
    runs = CFG.runs
    geometry = dict(table_width=eng.table_width, block_size=BS)
    S = eng.max_slots

    def verify(p, k, v, wk, wv, ids, starts, lens, tables):
        return fam.verify(p, k, v, ids, starts, lens, tables, BS,
                          window=(wk, wv))

    calls = [(s.fn, a, s.fn.__name__) for s, a in eng._warmup_calls()]
    calls.append((verify, (
        eng.params, *pool.caches(), jnp.zeros((S, 5), jnp.int32),
        jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.zeros((S, eng.table_width), jnp.int32)), "verify"))
    assert sorted(n for *_, n in calls)[0] == "serve_decode"
    for fn, args, name in calls:
        chunk = name.startswith("serve_prefill")
        want = expected_serve_window_moe(
            full_runs=sum(r.attn == FULL for r in runs),
            sliding_runs=sum(r.attn == SLIDING for r in runs),
            rows=1 if chunk else S, ring=RING, width=pool.wk.shape[-1],
            chunk=chunk)
        assert analysis.collective_census(fn, *args).as_dict() == \
            want["census"], name
        for shape in (pool.k.shape, pool.wk.shape):
            assert analysis.pool_scan_operands(
                fn, *args, pool_shape=shape) == want["pool_scan_operands"]
        assert analysis.gathered_view_gathers(
            fn, *args, num_blocks=pool.num_blocks,
            table_width=eng.table_width) == want["gathered_view_gathers"]
        assert analysis.row_walk_calls(
            fn, *args, pool_shape=pool.k.shape) == want["row_walk_calls"], \
            name
        assert analysis.store_reads(
            fn, *args, store_shape=pool.wk.shape) == want["store_reads"], name
        assert analysis.view_head_splits(fn, *args, **geometry) == \
            want["view_head_splits"], name
        assert analysis.widened_view_dots(fn, *args, **geometry) == \
            want["widened_view_dots"]
        if name != "verify":
            assert not analysis.donation_report(
                fn, *args).undonated_aliasable, name
    # the decode program's ring views are never cut into heads either:
    # no reshape [.., RING, F] -> [.., RING, H, Dh] anywhere in it
    decode = next(c for c in calls if c[2] == "serve_decode")
    text = str(jax.make_jaxpr(decode[0])(*decode[1]))
    assert f"{RING},2,16]" not in text.replace(" ", "")
    # and the arithmetic: bf16 weights and pool against the f32 reference
    (ids,) = _prompts(10, [30])
    rid = eng.submit(ids, 6)
    eng.run()
    got = eng.result(rid)
    f32 = _engine(params)
    rid = f32.submit(ids, 6)
    f32.run()
    assert list(got[:30]) == list(ids)
    # greedy tokens under bf16 may part ways at a near-tie; the first
    # generated token's logits margin is not one here
    assert got[30] == f32.result(rid)[30]


# ---------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------
def _yarn_by_hand(rot, theta, factor, original, fast, slow):
    """YaRN's inverse frequencies, written out with plain loops."""
    def c(b):
        return rot * math.log(original / (2 * math.pi * b)) / (
            2 * math.log(theta))

    low, high = max(math.floor(c(fast)), 0), min(math.ceil(c(slow)), rot - 1)
    out = []
    for i in range(rot // 2):
        extrap = theta ** (-2.0 * i / rot)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extrap / factor * ramp + extrap * (1.0 - ramp))
    return low, high, out


def test_yarn_and_partial_rotation_against_a_hand_written_table():
    # the published full-attention setting: 64 of 128 features rotate;
    # the correction dimensions are 5 and 16 of 32 frequencies
    low, high, inv = _yarn_by_hand(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert (low, high) == (5, 16)
    got = attention.yarn_inv_freq(64, theta=500000.0, factor=64.0,
                                  original_max=4096, beta_fast=64.0,
                                  beta_slow=1.0)
    np.testing.assert_allclose(got, inv, rtol=1e-6)
    # below the low dimension plain, above the high one divided by 64
    assert got[0] == 1.0 and got[5] == pytest.approx(500000.0 ** (-10 / 64))
    assert got[16] == pytest.approx(500000.0 ** (-32 / 64) / 64, rel=1e-6)
    assert got[31] == pytest.approx(500000.0 ** (-62 / 64) / 64, rel=1e-6)
    # the tables of the published config, by layer kind
    cfg = LagunaConfig(num_hidden_layers=5)
    pos = jnp.asarray([0, 1, 777, 16000])
    cos, sin = laguna_rope_tables(pos, cfg, FULL)
    assert cos.shape == (4, 64)
    factor = 1.4158883083359672
    assert factor == pytest.approx(0.1 * math.log(64.0) + 1.0)
    ang = np.asarray(pos, np.float64)[:, None] * np.asarray(inv)[None, :]
    np.testing.assert_allclose(cos[:, :32], np.cos(ang) * factor, atol=2e-3)
    np.testing.assert_allclose(cos[:, 32:], cos[:, :32])
    np.testing.assert_allclose(sin[1, :32], np.sin(ang[1]) * factor,
                               rtol=1e-5)
    cos_s, sin_s = laguna_rope_tables(pos, cfg, SLIDING)
    assert cos_s.shape == (4, 128)
    np.testing.assert_allclose(
        cos_s[1, :64], np.cos(10000.0 ** (-np.arange(64) / 64.0)), rtol=1e-5)
    # partial rotation: features 64.. pass through, lane i pairs i + 32
    x = jax.random.normal(jax.random.key(0), (4, 128))
    y = attention.apply_rope(x, cos, sin)
    np.testing.assert_array_equal(y[:, 64:], x[:, 64:])
    np.testing.assert_allclose(
        y[:, :32], x[:, :32] * cos[:, :32] - x[:, 32:64] * sin[:, :32],
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y[:, 32:64], x[:, 32:64] * cos[:, 32:] + x[:, :32] * sin[:, 32:],
        rtol=1e-5, atol=1e-6)
    # the reference's own tables are the same numbers
    rc, rs, turn = reference.rope_tables(
        CFG_DICT["rope_parameters"]["full_attention"], CFG.head_dim, 20)
    tc, ts = laguna_rope_tables(jnp.arange(20), CFG, FULL)
    rot = tc.shape[-1]
    np.testing.assert_allclose(tc, rc[:, :rot], atol=1e-6)
    np.testing.assert_allclose(ts, rs[:, :rot], atol=1e-6)
    np.testing.assert_array_equal(rc[:, rot:], 1.0)
    np.testing.assert_array_equal(rs[:, rot:], 0.0)
    z = jax.random.normal(jax.random.key(3), (20, CFG.head_dim))
    np.testing.assert_allclose(reference._rope(z, rc, rs, turn),
                               attention.apply_rope(z, tc, ts), atol=1e-6)


def test_the_gate_is_one_sigmoid_a_head_on_the_normed_input(params):
    """With ``W_g`` zero every head is scaled by sigmoid(0) = 1/2, so
    the attention's output is half the ungated one's; with ``W_g``
    large and negative on ONE head, that head alone is shut."""
    blk = jax.tree.map(lambda a: a[0], params["blocks"]["sliding_sparse"])
    u = jax.random.normal(jax.random.key(1), (1, 6, CFG.hidden_size))
    pos = jnp.arange(6)[None]
    cos, sin = laguna_rope_tables(pos, CFG, SLIDING)

    def attend(attn):
        pool = _pool()
        y, _ = laguna.laguna_attention(
            attn, u, pool.caches(), 0, pos, jnp.asarray([6]),
            jnp.zeros((1, WIDTH), jnp.int32), 0, BS, CFG, cos, sin,
            attn=SLIDING, chunk=True)
        return y

    zero = {**blk["attn"], "gate": {"w": jnp.zeros_like(
        blk["attn"]["gate"]["w"])}}
    half = attend(zero)
    # the reference's attention with the gate left out
    want = reference._attention(
        blk["attn"], u, reference.rope_tables(
            CFG_DICT["rope_parameters"]["sliding_attention"], 16, 6),
        kv_heads=2, head_dim=16, window=WINDOW, gated=False)
    np.testing.assert_allclose(half, 0.5 * want, atol=ATOL)
    # shut head 3: the output loses exactly that head's columns of W_o
    shut = {**blk["attn"], "gate": {"w": zero["gate"]["w"].at[:, 3].set(
        -1e4 * jnp.sign(u[0, 0, 0]) * jnp.eye(CFG.hidden_size)[0])}}
    w_o = blk["attn"]["o"]["w"]
    only3 = {**zero, "o": {"w": w_o.at[:48].set(0).at[64:].set(0)}}
    np.testing.assert_allclose(attend(shut)[:, 0],
                               (half - attend(only3))[:, 0], atol=ATOL)


def test_the_routers_weights_sum_to_the_scaling_factor(params):
    """Sigmoid scores over all 16 experts, the 4 largest, normalised
    over the 4 and scaled by 2.5; applied to the experts' OUTPUT."""
    u = jax.random.normal(jax.random.key(2), (1, 9, CFG.hidden_size))
    stack = params["blocks"]["sliding_sparse"]["moe"]
    idx, w = reference._route(stack["router"]["w"][1], u,
                              CFG.num_experts_per_tok,
                              CFG.moe_routed_scaling_factor)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)
    scores = jax.nn.sigmoid(u @ stack["router"]["w"][1])
    np.testing.assert_array_equal(
        np.sort(idx, -1), np.sort(jax.lax.top_k(scores, 4)[1], -1))
    # the program's mixture (sparse layer 1 of the whole stack) is the
    # reference's routed part plus the shared expert
    got, _, stats = moe_apply(
        {"router": {"w": stack["router"]["w"][1]},
         "shared": jax.tree.map(lambda a: a[1], stack["shared"]),
         "experts": params["blocks"]["experts"]}, u, CFG.moe_args,
        return_stats=True, expert_layer=1)
    routed, _ = reference.routed_part(
        params["blocks"]["experts"], stack["router"]["w"][1], u, CFG_DICT,
        layer=1)
    want = routed + reference._swiglu_of(stack["shared"], u, 1)
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert float(stats["held_rows"]) == 9 * 4 and float(
        stats["elsewhere"]) == 0


def test_key_blocked_prefill_attention_equals_the_whole_table():
    """A chunk of 11 tokens at position 9 of a 96-position table, read
    8 keys at a time (3 trips of the 12 the table has) against the same
    run scored over the whole gathered view: the same outputs, the same
    pool — and blocks past the chunk's last position are never read
    (they hold NaN here)."""
    pool = _pool()
    k0, v0 = (jax.random.normal(jax.random.key(i), pool.k.shape)
              for i in (1, 2))
    row = np.arange(1, 25, dtype=np.int32)[None]
    S, H, P, D = 1, 2, 16, 16
    q, k, v = (jax.random.normal(jax.random.key(i), (S, H, n, D))
               for i, n in ((3, 3 * P), (4, P), (5, P)))
    pos = (9 + jnp.arange(P))[None]
    lens = jnp.asarray([11])
    want, (kw, vw) = attention.paged_attend(
        q, k, v, (k0, v0), 1, pos, lens, jnp.asarray(row), block_size=BS)
    # positions 24.. are past the chunk: poison their blocks
    poison = jnp.arange(k0.shape[1]) >= 7 * BS
    k1, v1 = (jnp.where(poison[None, :, None], jnp.nan, a)
              for a in (k0, v0))
    got, (kg, vg) = attention.paged_attend(
        q, k, v, (k1, v1), 1, pos, lens, jnp.asarray(row), block_size=BS,
        key_block=8)
    real = np.tile(np.arange(P) < 11, 3)
    np.testing.assert_allclose(got[:, :, real], want[:, :, real], atol=1e-5)
    assert bool(jnp.isfinite(got[:, :, real]).all())
    np.testing.assert_array_equal(kg[:, :7 * BS], kw[:, :7 * BS])


def test_the_window_mask_counts_the_querys_own_position():
    """``window_attend`` on ones: a query past the window averages
    exactly ``window`` values, its own among them."""
    pool = _pool()
    S, P, H, D = 1, 12, 2, 16
    pos = jnp.arange(P)[None]
    k = jnp.ones((S, H, P, D))
    v = jnp.broadcast_to(jnp.arange(P, dtype=jnp.float32)[None, None, :,
                                                          None], (S, H, P, D))
    o, _ = attention.window_attend(
        jnp.zeros((S, H, P, D)), k, v, (pool.wk, pool.wv), 0, pos,
        jnp.asarray([P]), 0, window=WINDOW, ring=RING)
    # uniform scores: the mean of the positions seen
    want = [np.mean(np.arange(max(0, i - WINDOW + 1), i + 1))
            for i in range(P)]
    np.testing.assert_allclose(o[0, 0, :, 0], want, rtol=1e-6)


# ---------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------
@pytest.mark.parametrize("kw,match", [
    (dict(prefix_cache=True), "prefix_cache=True"),
    (dict(prefix_cache=True, kv_tier_bytes=1 << 20), "prefix_cache=True"),
    (dict(spec=SpecConfig()), "speculative decoding"),
    (dict(kv_dtype="int8"), "scaled or float8 KV"),
    (dict(kv_dtype="fp8"), "scaled or float8 KV"),
    (dict(kv_dtype="fake_quant"), "scaled or float8 KV"),
    (dict(weights_dtype="int8"), "scaled weight layout"),
    (dict(weights_dtype="fp8"), "scaled weight layout"),
    (dict(attn_kernel="pallas"), "attn_kernel='pallas'"),
    (dict(adapters=True), "adapters"),
    (dict(block_size=8), "block_size other than"),
])
def test_what_the_window_store_cannot_do_is_refused(params, kw, match):
    with pytest.raises(NotImplementedError, match=match):
        _engine(params, **kw)


@pytest.mark.parametrize("axis", ["tp", "ep", "sp"])
def test_a_mesh_is_refused_for_a_window_family(params, axis):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), (axis,))
    kw = {"tp": {}, "ep": {"ep_axis": "ep"}, "sp": {"sp_axis": "sp"}}[axis]
    with pytest.raises(NotImplementedError, match="a mesh"):
        _engine(params, mesh=mesh, **kw)
    with pytest.raises(NotImplementedError, match="no partition specs"):
        FAMILY.partition_specs("tp")


def test_chains_and_the_prefill_phase_are_refused(params):
    eng = _engine(params)
    (ids,) = _prompts(11, [9])
    with pytest.raises(NotImplementedError, match="export_kv_chain"):
        eng.export_kv_chain(ids)
    with pytest.raises(NotImplementedError, match="export_kv_chain"):
        eng.import_kv_chain({})
    with pytest.raises(NotImplementedError, match="prefill_only"):
        eng.submit(ids, 4, prefill_only=True)
    with pytest.raises(NotImplementedError, match="one device"):
        FAMILY.decode(params, None, None, None, None, None, BS,
                      tp_axis="tp")


def test_the_config_reads_the_hugging_face_keys():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna-xs.2.json")) as f:
        d = json.load(f)
    cfg = LagunaConfig.from_dict(d)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads,
            cfg.num_experts, cfg.num_experts_per_tok, cfg.sliding_window,
            cfg.vocab_size) == (2048, 128, 8, 256, 8, 512, 100352)
    assert cfg.attn_kinds == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert cfg.num_attention_heads_per_layer == (48, 64, 64, 64, 48)
    assert (cfg.heads_of(FULL), cfg.heads_of(SLIDING)) == (48, 64)
    assert [(r.kind, r.count, r.cache_first, r.expert_first)
            for r in cfg.runs] == [("full_dense", 1, 0, None),
                                   ("sliding_sparse", 3, 0, 0),
                                   ("full_sparse", 1, 1, 3)]
    assert cfg.rope_full.rope_type == "yarn" and cfg.rope_full.factor == 64
    assert cfg.rope_sliding == laguna.RopeSetting()
    args = cfg.moe_args
    assert (args.n_experts, args.top_k, args.experts_held, args.routed_scale,
            args.scoring, args.dropless) == (256, 8, None, 2.5, "sigmoid",
                                             True)
    fam = laguna_family(cfg)
    assert (fam.n_layers, fam.window.n_layers, fam.window.ring) == (2, 3, 528)
    # the published pattern is the default of the published depth
    whole = LagunaConfig()
    assert whole.attn_kinds[:5] == cfg.attn_kinds and len(whole.runs) == 20
    assert LagunaConfig.from_dict(CFG.to_dict()) == CFG
    with pytest.raises(NotImplementedError, match="gating"):
        LagunaConfig.tiny(gating=False)
    with pytest.raises(ValueError, match="ONE head count"):
        LagunaConfig.tiny(num_attention_heads_per_layer=(6, 8, 6, 8, 6))
