"""SmallThinker at tiny widths on the CPU (hidden 64, 14 query heads over
2 kv heads of 16 features — 7 a kv head, as published — a window of
32 over blocks of 8, 16 experts top-6, the published period: global
NoPE, sliding x3): the window store, the NoPE global layer beside the
rotated sliding ones, the router that reads the layer's INPUT, the
ReGLU experts, and ``ServeEngine`` itself against the plain reference
(benchmarks/lib/reference_smallthinker.py: f32, highest precision, no
cache, no ring, full ``[T, T]`` masks, a loop over experts with a mask;
nothing imported from ``quintnet_tpu``).

Tolerances. Everything here is f32 on the CPU, where a matmul is exact
f32: the paged programs differ from the reference in the ORDER of their
sums (keys read in ring order, the grouped matmul through sorted rows),
a few ulp of values of size about 1. ``ATOL`` 2e-4 is a hundred times
that and far under what any missing piece does: every control of the
reference (the window ignored, rotation on the global layer, the router
fed the experts' input, SwiGLU, sigmoid scores) moves the logits by
more than ``100 * ATOL``.

The guide's share test does not apply: every expert, every head and
the whole vocabulary are held; the configuration is cut in depth alone.
"""

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models import smallthinker
from quintnet_tpu.models.smallthinker import (GLOBAL, SLIDING,
                                              SmallThinkerConfig,
                                              smallthinker_attention,
                                              smallthinker_block,
                                              smallthinker_init,
                                              smallthinker_rope_tables)
from quintnet_tpu.nn import attention
from quintnet_tpu.nn.layers import rms_norm_apply
from quintnet_tpu.nn.moe import MoEArgs, moe_apply, moe_route
from quintnet_tpu.serve import ServeEngine, SpecConfig, smallthinker_family
from quintnet_tpu.serve.kv_pool import KVPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-4
BS = 8


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_smallthinker", os.path.join(
            ROOT, "benchmarks", "lib", "reference_smallthinker.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()
CFG = SmallThinkerConfig.tiny()
CFG_DICT = CFG.to_dict()
FAMILY = smallthinker_family(CFG, block_size=BS)
WINDOW = CFG.sliding_window_size
RING = WINDOW + BS
WIDTH = 16                     # the tests' block tables: 128 positions


@pytest.fixture(scope="module")
def params():
    return smallthinker_init(jax.random.key(7), CFG)


@pytest.fixture(autouse=True)
def key_blocks_of_two_table_entries(monkeypatch):
    """A prefill chunk reads the global layer's cache 16 positions (two
    table entries) at a time here, so every prefill below walks several
    key blocks. Read at trace time: every test traces under it."""
    monkeypatch.setattr(smallthinker, "PREFILL_KEY_BLOCK", 16)


def _engine(params, **kw):
    opts = dict(max_slots=3, block_size=BS, num_blocks=64, max_seq_len=128,
                prefill_len=64, chunked_prefill=True, prefix_cache=False)
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
    (ids,) = _prompts(1, [75])                      # past two windows
    logits, stats = _prefill(params, pool, ids, 0, 75, 128,
                             _table(pool, 128), 1)
    want, _ = _full_forward(params, ids, positions=[74])
    np.testing.assert_allclose(logits, want[0], atol=ATOL)
    # every layer is sparse; the bucket's pad columns are routed nowhere
    assert float(stats["dropped"]) == 0.0
    assert float(stats["assigned"]) == 75 * 6 * 4


@pytest.mark.parametrize("calls", [
    [(0, 64, 64), (64, 100, 64)],                 # wide, then a chunk past 0
    [(0, 48, 64), (48, 90, 64), (90, 96, 16)],    # the last WRITES, then reads
    [(0, 5, 16), (5, 100, 128)],                  # a narrow bucket first
])
def test_chunked_prefill_equals_the_reference(params, calls):
    """Chunks wider than the ring's spare rows take the store's
    read-then-write order and find the ring as the earlier calls left
    it; a bucket of at most ``block_size + 1`` takes write-then-read:
    each call's logits at its last position are the reference's."""
    (ids,) = _prompts(2, [100])
    want, _ = _full_forward(params, ids)
    pool = _pool()
    row = _table(pool, 128)
    for lo, hi, width in calls:
        logits, _ = _prefill(params, pool, ids, lo, hi, width, row, 2)
        np.testing.assert_allclose(logits, want[hi - 1], atol=ATOL)


def test_decode_past_two_ring_wraps(params):
    """A prompt of 20 through the chunk program, then every further
    position to 110 through the decode step — the ring of 40 wraps at 40
    and 80 — teacher-forced, beside an empty slot and a row that is
    mid-prefill (whose ring must not be touched); then a verify run of
    ``block_size + 1`` tokens. All equal the reference's full forward."""
    pool = _pool()
    ids, other = _prompts(3, [119, 30])
    want, _ = _full_forward(params, ids)
    rows = {0: _table(pool, 128), 2: _table(pool, 32)}
    _prefill(params, pool, ids, 0, 20, 64, rows[0], 0)
    _prefill(params, pool, other, 0, 12, 16, rows[2], 2)
    ring2 = np.asarray(pool.wk[:, 2 * RING:3 * RING])
    tables = np.zeros((3, WIDTH), np.int32)
    tables[0] = rows[0]
    for pos in range(20, 110):
        tok = np.zeros((3,), np.int32)
        at = np.zeros((3,), np.int32)
        tok[0], at[0] = ids[pos], pos
        logits, stats = _decode(params, pool, tok, at, tables)
        np.testing.assert_allclose(logits[0], want[pos], atol=ATOL,
                                   err_msg=f"position {pos}")
        assert float(stats["assigned"]) == 6 * 4
    np.testing.assert_array_equal(
        np.asarray(pool.wk[:, 2 * RING:3 * RING]), ring2)
    run = np.zeros((3, BS + 1), np.int32)
    run[0] = ids[110:110 + BS + 1]
    k, v, wk, wv = pool.caches()
    logits, *_ = FAMILY.verify(
        params, k, v, jnp.asarray(run), jnp.asarray([110, 0, 0], jnp.int32),
        jnp.asarray([BS + 1, 0, 0], jnp.int32), jnp.asarray(tables), BS,
        window=(wk, wv))
    np.testing.assert_allclose(logits[0], want[110:119], atol=ATOL)


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_a_control_that_leaves_a_piece_out_fails(params, control):
    """The reference with the window ignored, the global layer rotated,
    the router fed ``N2(x)``, SwiGLU or sigmoid scores is NOT what the
    programs compute: the comparison above would refuse each."""
    (ids,) = _prompts(6, [100])
    pool = _pool()
    logits, _ = _prefill(params, pool, ids, 0, 100, 128,
                         _table(pool, 128), 0)
    wrong, _ = _full_forward(params, ids, positions=[99],
                             controls=(control,))
    assert float(jnp.abs(logits - wrong[0]).max()) > 100 * ATOL


# ---------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------
def _layer(params, kind, i=0):
    return jax.tree.map(lambda a: a[i], params["blocks"][kind])


def test_the_router_reads_the_input_and_the_experts_the_normed_stream(
        params):
    """One layer by hand: the router's decision is ``moe_route`` on x,
    the layer's INPUT; the experts run on ``N2(x + attention)``. The
    block equals that, and not the mixture routed on ``N2`` — which is
    what a router placed after attention would give."""
    cfg, args = CFG, CFG.moe_args
    x = jax.random.normal(jax.random.key(3), (1, 12, cfg.hidden_size))
    p = _layer(params, SLIDING)
    pool = _pool()
    positions = jnp.arange(12)[None]
    lens = jnp.asarray([12])
    rope = smallthinker_rope_tables(positions, cfg)
    experts = params["blocks"]["experts"]
    got, *_ = smallthinker_block(
        p, x, pool.caches(), 0, positions, lens, None, 0, BS, cfg, rope,
        attn=SLIDING, chunk=True, experts=experts, expert_layer=1)
    y, _ = smallthinker_attention(
        p["attn"], rms_norm_apply(p["ln1"], x, eps=cfg.rms_norm_eps),
        _pool().caches(), 0, positions, lens, None, 0, BS, cfg, rope,
        chunk=True)
    h = x + y
    u = rms_norm_apply(p["ln2"], h, eps=cfg.rms_norm_eps)
    moe = {**p["moe"], "experts": experts}
    want = h + moe_apply(moe, u, args, expert_layer=1,
                         routing=moe_route(p["moe"], x, args))[0]
    after = h + moe_apply(moe, u, args, expert_layer=1)[0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(got - after).max()) > 1e-3


def test_regl_u_experts_against_a_hand_written_one():
    """``activation="relu"``: each chosen expert computes ``(relu(u
    W_gate) * u W_up) W_down`` and the softmax gates, renormalised over
    the chosen six, weigh the outputs — by hand, one token and one
    expert at a time; ``"silu"`` (the default) is not that."""
    from quintnet_tpu.nn.moe import moe_held_init

    d, hid, e, k = 16, 8, 8, 6
    p = moe_held_init(jax.random.key(1), d, hid, e, held=e)
    u = jax.random.normal(jax.random.key(2), (1, 5, d))
    args = MoEArgs(n_experts=e, top_k=k, dropless=True, scoring="softmax",
                   activation="relu")
    got = moe_apply(p, u, args)[0][0]
    w = {n: np.asarray(p["experts"][n]["w"]) for n in ("gate", "up", "down")}
    router = np.asarray(p["router"]["w"])
    for t in range(5):
        x = np.asarray(u[0, t], np.float64)
        r = x @ router
        s = np.exp(r - r.max())
        s /= s.sum()
        top = np.argsort(-s)[:k]
        gates = s[top] / s[top].sum()
        want = sum(g * ((np.maximum(x @ w["gate"][j], 0) * (x @ w["up"][j]))
                        @ w["down"][j]) for g, j in zip(gates, top))
        np.testing.assert_allclose(got[t], want, atol=1e-5)
    silu = moe_apply(p, u, args._replace(activation="silu"))[0][0]
    assert float(jnp.abs(silu - got).max()) > 1e-3


def _by_hand(p, h, rope):
    """Causal attention of a whole row, no cache, by hand: the query
    heads of kv head ``i // 7``, rotated with ``rope`` (cos, sin) where
    given."""
    hd, hkv = CFG.head_dim, CFG.num_key_value_heads
    heads = CFG.num_attention_heads
    t = h.shape[1]
    q = (h @ p["q"]["w"]).reshape(t, heads, hd)
    k = (h @ p["k"]["w"]).reshape(t, hkv, hd)
    v = (h @ p["v"]["w"]).reshape(t, hkv, hd)
    if rope is not None:
        cos, sin = (a[0][:, None] for a in rope)
        q, k = (attention.apply_rope(a, cos, sin) for a in (q, k))
    k = jnp.repeat(k, heads // hkv, axis=1)
    v = jnp.repeat(v, heads // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(1, t, heads * hd) @ p["o"]["w"]


@pytest.mark.parametrize("kind", [GLOBAL, SLIDING])
def test_the_global_layer_is_nope_and_the_sliding_ones_rotate(params, kind):
    """Under the window (12 positions of 32) a layer is causal attention
    of its row: the global one's equals it UNROTATED, a sliding one's
    rotated with theta ``rope_theta`` over every feature — and not the
    other way round."""
    p = _layer(params, kind)["attn"]
    h = jax.random.normal(jax.random.key(5), (1, 12, CFG.hidden_size))
    positions = jnp.arange(12)[None]
    pool = _pool()
    tables = jnp.asarray(_table(pool, 16))[None]
    rope = smallthinker_rope_tables(positions, CFG)
    got, _ = smallthinker_attention(
        p, h, pool.caches(), 0, positions, jnp.asarray([12]), tables, 0, BS,
        CFG, rope if kind == SLIDING else None, chunk=True)
    right = _by_hand(p, h, rope if kind == SLIDING else None)
    wrong = _by_hand(p, h, None if kind == SLIDING else rope)
    np.testing.assert_allclose(got, right, atol=1e-5)
    assert float(jnp.abs(got - wrong).max()) > 1e-3


@pytest.mark.parametrize("order", ["read_then_write", "write_then_read"])
def test_a_ring_of_4112_rows_in_both_orders(order):
    """The published window: rings of 4,096 + 16 rows. A sequence of
    4,608 positions through 1,024-wide chunks (read, then write) or
    through runs of ``block_size + 1`` = 17 (write, then read), then
    single-token steps past the ring's wrap: every query's output equals
    attention over the sequence's own keys under the window mask (the
    query's own position and the 4,095 before it), 7 query heads a kv
    head, at narrow heads."""
    window, block = 4096, 16
    ring = window + block
    hkv, g, dh = 1, 7, 8
    total = 4608 + 6
    key = jax.random.split(jax.random.key(9), 3)
    qs = jax.random.normal(key[0], (total, g, dh))
    ks = jax.random.normal(key[1], (total, hkv, dh))
    vs = jax.random.normal(key[2], (total, hkv, dh))
    wk = jnp.zeros((1, 2 * ring, hkv * dh), jnp.float32)
    wv = jnp.zeros_like(wk)

    @jax.jit
    def run(wk, wv, lo, q, k, v):
        p = q.shape[0]
        positions = (lo + jnp.arange(p))[None]
        o, bufs = attention.window_attend(
            q.transpose(1, 0, 2).reshape(1, hkv, g * p, dh),
            k.transpose(1, 0, 2)[None], v.transpose(1, 0, 2)[None],
            (wk, wv), 0, positions, jnp.asarray([p]), 0, window=window,
            ring=ring, max_diag_rows=0)
        return o.reshape(g, p, dh).transpose(1, 0, 2), bufs

    @jax.jit
    def want_at(lo, q):
        i = lo + jnp.arange(q.shape[0])[:, None]
        j = jnp.arange(total)[None, :]
        s = jnp.einsum("pgd,td->gpt", q, ks[:, 0]) / np.sqrt(dh)
        s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
        return jnp.einsum("gpt,td->pgd", jax.nn.softmax(s, axis=-1),
                          vs[:, 0])

    width = 1024 if order == "read_then_write" else block + 1
    spans = [(lo, min(lo + width, 4608)) for lo in range(0, 4608, width)]
    spans += [(p, p + 1) for p in range(4608, total)]
    for lo, hi in spans:
        o, (wk, wv) = run(wk, wv, lo, qs[lo:hi], ks[lo:hi], vs[lo:hi])
        if hi > 4000 or lo == 0:
            np.testing.assert_allclose(o, want_at(lo, qs[lo:hi]),
                                       atol=2e-5, err_msg=f"{lo}:{hi}")


def test_seven_heads_a_kv_head_on_the_lane_diagonal(params):
    """On a bf16 pool the decode program and a verify run of
    ``block_size + 1`` contract the cached rows as stored for 7 query
    heads a kv head (the global layer walks each row's live blocks in
    place, a sliding layer reads its ring uncut), and every program's
    census is the window family's pinned one (analysis/specs): no
    collective, no pool- or ring-shaped scan operand, one row walk in
    decode. The bf16 engine's first token agrees with the f32 one's."""
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
        eng.params, *pool.caches(), jnp.zeros((S, BS + 1), jnp.int32),
        jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.zeros((S, eng.table_width), jnp.int32)), "verify"))
    for fn, args, name in calls:
        chunk = name.startswith("serve_prefill")
        want = expected_serve_window_moe(
            full_runs=sum(r.attn == GLOBAL for r in runs),
            sliding_runs=sum(r.attn == SLIDING for r in runs),
            rows=1 if chunk else S, ring=RING, width=pool.wk.shape[-1],
            chunk=chunk)
        assert analysis.collective_census(fn, *args).as_dict() == \
            want["census"], name
        for shape in (pool.k.shape, pool.wk.shape):
            assert analysis.pool_scan_operands(
                fn, *args, pool_shape=shape) == want["pool_scan_operands"]
        assert analysis.row_walk_calls(
            fn, *args, pool_shape=pool.k.shape) == want["row_walk_calls"], \
            name
        assert analysis.store_reads(
            fn, *args, store_shape=pool.wk.shape) == want["store_reads"], name
        assert analysis.row_walk_calls(
            fn, *args, pool_shape=pool.wk.shape) == want["ring_walk_calls"], \
            name
        assert analysis.view_head_splits(fn, *args, **geometry) == \
            want["view_head_splits"], name
        assert analysis.widened_view_dots(fn, *args, **geometry) == \
            want["widened_view_dots"]
    decode = next(c for c in calls if c[2] == "serve_decode")
    text = str(jax.make_jaxpr(decode[0])(*decode[1])).replace(" ", "")
    assert f"{RING},2,16]" not in text            # no ring cut into heads
    (ids,) = _prompts(10, [50])
    rid = eng.submit(ids, 4)
    eng.run()
    f32 = _engine(params)
    rid32 = f32.submit(ids, 4)
    f32.run()
    assert eng.result(rid)[50] == f32.result(rid32)[50]


# ---------------------------------------------------------------------
# the engine, through its normal path
# ---------------------------------------------------------------------
def _assert_greedy(params, prompt, out, n):
    assert list(out[:len(prompt)]) == list(prompt) and len(out) == (
        len(prompt) + n)
    logits, _ = _full_forward(params, np.asarray(out[:-1], np.int32))
    np.testing.assert_array_equal(
        np.argmax(logits[len(prompt) - 1:], axis=-1), out[len(prompt):])


def test_the_engine_serves_what_the_reference_generates(params):
    """Four requests over three slots, prompts of 5 to 90 tokens through
    64-wide chunks, 24 tokens each (past a ring's wrap): greedy tokens
    equal the reference's full forward; the rings' ledger is empty at
    the end, and the step ring carries the window's counters — the ring
    rows READ beside the live ones. This engine's store is f32, which
    no walk takes: every slot's ring is sliced out whole, idle slots'
    too, and counted so (a bf16 store's walk is counted in
    test_the_ring_walk_reads_what_it_counts)."""
    eng = _engine(params)
    prompts = _prompts(8, [90, 5, 40, 70])
    rids = [eng.submit(p, 24) for p in prompts]
    eng.run()
    for p, rid in zip(prompts, rids):
        _assert_greedy(params, p, eng.result(rid), 24)
    assert eng.pool.window_owner == [None] * 3
    assert eng.pool.num_used == 0 and eng.metrics.moe_dropped_tokens == 0
    static = eng.recorder.static
    assert static["layer_pattern"] == [GLOBAL, SLIDING, SLIDING, SLIDING]
    seen = 0
    for rec in eng.recorder.snapshot():
        if not rec["decoding"]:
            continue
        a = rec["attrs"]
        assert a["global_rows"] == rec["context_tokens"]
        assert eng._ring_read == RING
        assert a["window_read_rows"] == eng.max_slots * RING * 3
        assert 0 < a["window_rows"] <= a["window_read_rows"]
        assert a["decode_expert_rows"] == rec["decoding"] * 6 * 4
        seen += 1
    assert seen


def test_the_ring_walk_reads_what_it_counts(params, monkeypatch):
    """A bf16 store: the decode program's sliding layers walk each row's
    ring in place (in the kernel's interpreter here), trips of 16
    positions — two pages of 8, three trips a full ring of 40
    (``RING_KEY_BLOCK`` 16) — so a
    step's ``window_read_rows`` is, over the program's three rows, each
    row's ``max(ceil(min(pos + 1, 40) / 16), 1)`` trips x 16 x 3 sliding
    layers: one request decoding from position 5 to past the ring's
    wrap at 40 beside two idle slots (a trip each). The same engine with
    the interpreter off (the CPU's own form: every ring sliced out
    whole) counts ``max_slots x 40 x 3`` and serves the same greedy
    tokens."""
    pa = importlib.import_module("quintnet_tpu.ops.paged_attention")
    monkeypatch.setattr(attention, "RING_KEY_BLOCK", 16)
    (ids,) = _prompts(11, [5])
    outs, steps = [], 0
    for interpret in (True, False):
        monkeypatch.setattr(pa, "INTERPRET", interpret)
        eng = _engine(params, kv_dtype="bf16", weights_dtype="bf16")
        rid = eng.submit(ids, 50)
        eng.run()
        outs.append(list(eng.result(rid)))
        kb = eng._ring_read
        assert kb == (16 if interpret else RING)
        for rec in eng.recorder.snapshot():
            if not rec["decoding"]:
                continue
            assert rec["decoding"] == 1
            pos = rec["context_tokens"]            # the one row's position
            trips = max(-(-min(pos + 1, RING) // kb), 1) + eng.max_slots - 1
            assert rec["attrs"]["window_read_rows"] == trips * kb * 3, pos
            steps += interpret and pos >= RING
    assert steps                  # the walk's count past a full ring
    assert outs[0] == outs[1]


def test_a_sequence_preempted_and_prefilled_again(params):
    eng = _engine(params, num_blocks=13)
    prompts = _prompts(9, [40, 36, 44])
    rids = [eng.submit(p, 20) for p in prompts]
    eng.run()
    assert eng.metrics.preempted >= 1
    for p, rid in zip(prompts, rids):
        _assert_greedy(params, p, eng.result(rid), 20)


@pytest.mark.parametrize("kw,match", [
    (dict(prefix_cache=True), "prefix_cache=True"),
    (dict(prefix_cache=True, kv_tier_bytes=1 << 20), "prefix_cache=True"),
    (dict(spec=SpecConfig()), "speculative decoding"),
    (dict(kv_dtype="fp8"), "scaled or float8 KV"),
    (dict(weights_dtype="int8"), "scaled weight layout"),
    (dict(attn_kernel="pallas"), "attn_kernel='pallas'"),
    (dict(adapters=True), "adapters"),
    (dict(block_size=16), "block_size other than"),
])
def test_what_the_window_store_cannot_do_is_refused(params, kw, match):
    with pytest.raises(NotImplementedError, match=match):
        _engine(params, **kw)


def test_a_mesh_and_chains_are_refused(params):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(NotImplementedError, match="a mesh"):
        _engine(params, mesh=mesh)
    with pytest.raises(NotImplementedError, match="no partition specs"):
        FAMILY.partition_specs("tp")
    eng = _engine(params)
    (ids,) = _prompts(11, [9])
    with pytest.raises(NotImplementedError, match="export_kv_chain"):
        eng.export_kv_chain(ids)
    with pytest.raises(NotImplementedError, match="prefill_only"):
        eng.submit(ids, 4, prefill_only=True)


def test_the_config_reads_the_hugging_face_keys():
    """The published config's keys, lists cut to the layers run; a
    rotated global layer, rope scaling and tied embeddings are refused;
    the runs walk global x1 then sliding x3, every layer sparse."""
    layout = [0, 1, 1, 1] * 13
    cfg = SmallThinkerConfig.from_dict(dict(
        num_hidden_layers=4, sliding_window_layout=layout,
        rope_layout=layout, model_name="x", unknown_key=3))
    assert cfg.attn_kinds == (GLOBAL, SLIDING, SLIDING, SLIDING)
    assert [(r.kind, r.first, r.count, r.cache_first, r.expert_first)
            for r in cfg.runs] == [(GLOBAL, 0, 1, 0, 0),
                                   (SLIDING, 0, 3, 0, 1)]
    args = cfg.moe_args
    assert (args.n_experts, args.top_k, args.scoring, args.activation,
            args.normalize_gates, args.routed_scale) == (
                64, 6, "softmax", "relu", True, 1.0)
    with pytest.raises(NotImplementedError, match="rope_layout"):
        SmallThinkerConfig(num_hidden_layers=4, rope_layout=(1, 1, 1, 1))
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        SmallThinkerConfig(num_hidden_layers=4,
                           rope_scaling={"type": "yarn"})
    with pytest.raises(ValueError, match="lists"):
        SmallThinkerConfig(num_hidden_layers=8, rope_layout=(0, 1),
                           sliding_window_layout=(0, 1))
