"""One device-to-host read a serving step (``ServeEngine._fetch``).

A step reads back once, after its last dispatch: the decode's tokens
and keys, the first token and key of every row whose prefill ended in
the step, and every program's routing stats. Such a row still rides
the step's decode: its first token and key are written into the
decode's rows on the device (``serve_join_row``), and the host commits
the first token before the decode's. What must not move: the tokens,
the schedule and the routing ledger. The goldens below are the tree's
from before the reads were gathered (commit cf7942e), on the same
traces.
"""

import jax
import numpy as np
import pytest

from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.models.gpt2_generate import gpt2_generate
from quintnet_tpu.serve import ServeEngine, SpecConfig, gpt2_family

CFG = GPT2Config.tiny(n_layer=2)
MOE = GPT2Config.tiny(n_layer=2, n_experts=4, expert_top_k=2)

# the chunked MoE trace: prompt lengths, token budgets, arrival steps
MOE_TRACE = ((7, 21, 3, 12, 30), (6, 5, 8, 1, 4), (0, 0, 1, 3, 4))
# tokens (temperature 0.9, top-k 7, key 100 + i) and, a step, the
# ledger's routed and dropped tokens and per-expert demand
MOE_TOKENS = [[83, 21, 54, 52, 52, 62], [24, 27, 65, 102, 79],
              [79, 83, 22, 83, 22, 22, 65, 22], [105], [102, 123, 65, 84]]
MOE_STEPS = [
    (140, 43, [34, 27, 43, 36]), (76, 12, [31, 20, 8, 17]),
    (76, 19, [34, 26, 6, 10]), (140, 32, [46, 46, 37, 11]),
    (12, 2, [3, 4, 4, 1]), (76, 8, [25, 18, 18, 15]),
    (76, 17, [25, 20, 19, 12]), (76, 16, [35, 11, 23, 7]),
    (76, 15, [34, 12, 23, 7]), (76, 20, [35, 11, 27, 3]),
    (76, 18, [33, 12, 27, 4]), (12, 2, [4, 3, 5, 0]), (12, 2, [4, 3, 5, 0])]
# the latent MoE's dropless router, chunked, greedy: tokens and, a
# step, routed tokens, rows on held experts, (layer, expert) pairs
# touched, routings held elsewhere, and the decode's rows and pairs
PANGU_TRACE = ((9, 26, 4, 13), (5, 4, 6, 3), (0, 0, 2, 3))
PANGU_TOKENS = [[24, 23, 23, 25, 23], [69, 93, 62, 57],
                [59, 28, 28, 28, 28, 28], [47, 47, 58]]
PANGU_STEPS = [(96, 54, 15, 42, 0, 0), (108, 55, 24, 53, 5, 5),
               (108, 58, 21, 50, 6, 6), (108, 45, 17, 63, 4, 4),
               (120, 61, 38, 59, 18, 14), (120, 54, 27, 66, 15, 12),
               (96, 47, 27, 49, 20, 14), (24, 15, 14, 9, 15, 14),
               (12, 8, 8, 4, 8, 8)]
PANGU_KEYS = ("moe_routed_tokens", "expert_rows", "experts_touched",
              "routed_elsewhere", "decode_expert_rows",
              "decode_experts_touched")


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _run(eng, vocab, trace, seed):
    """Submit ``trace`` (lengths, budgets, arrival steps) with keys
    100 + i, step to the end; returns (generated tokens a request,
    the rids each step finished, the rids)."""
    lengths, max_new, arrivals = trace
    rng = np.random.default_rng(seed)
    prompts = [np.asarray(rng.integers(0, vocab, (n,)), np.int32)
               for n in lengths]
    rids, finished, step = {}, [], 0
    while len(rids) < len(prompts) or eng.has_work:
        for i, at in enumerate(arrivals):
            if at == step:
                rids[i] = eng.submit(prompts[i], max_new[i],
                                     key=jax.random.key(100 + i))
        finished.append(eng.step())
        step += 1
        assert step < 500, "engine failed to drain"
    tokens = [[int(t) for t in eng.result(rids[i])[len(prompts[i]):]]
              for i in range(len(prompts))]
    return tokens, finished, [rids[i] for i in range(len(prompts))]


def _oracle(params, prompt, max_new, key, **kw):
    return gpt2_generate(params, prompt[None], CFG, max_new_tokens=max_new,
                         key=key, **kw)[0]


# ---------------------------------------------------------------------
# one read a step, the rows that joined, the ledger
# ---------------------------------------------------------------------

def _moe_chunked():
    eng = ServeEngine(gpt2_family(MOE), gpt2_init(jax.random.key(0), MOE),
                      max_slots=3, block_size=4, num_blocks=36,
                      max_seq_len=48, chunked_prefill=True, prefill_len=16,
                      prefill_chunk_budget=8, temperature=0.9, top_k=7)
    return eng, MOE.vocab_size, MOE_TRACE, 1


def _gpt2_plain():
    eng = ServeEngine(gpt2_family(CFG), gpt2_init(jax.random.key(0), CFG),
                      max_slots=4, block_size=4, num_blocks=48,
                      max_seq_len=40)
    trace = ((5, 11, 3, 8, 6, 14, 4, 9), (10, 6, 12, 8, 5, 7, 11, 1),
             (0, 0, 1, 2, 4, 5, 7, 9))
    return eng, CFG.vocab_size, trace, 4


def test_an_upload_keeps_its_own_copy_of_the_host_array():
    """The engine writes its host arrays again (a slot cleared, the next
    admission's table row) before the step's one read, while a program
    dispatched earlier in the step may still be reading its inputs: an
    upload must not share the host array's memory, as a device array
    made on the CPU may."""
    import time

    from quintnet_tpu.obs.spans import StepPhases

    ph = StepPhases(time.perf_counter)
    host = np.arange(8, dtype=np.int32)
    dev, scalar = ph.upload(host, np.int32(3))
    host[:] = -1
    assert np.asarray(dev).tolist() == list(range(8))
    assert int(scalar) == 3
    assert ph.h2d_bytes == host.nbytes + 4


@pytest.mark.parametrize("build", [_moe_chunked, _gpt2_plain],
                         ids=["chunked_moe", "plain_gpt2"])
def test_every_step_that_dispatches_reads_once(build):
    eng, vocab, trace, seed = build()
    eng.warmup()
    _tokens, finished, rids = _run(eng, vocab, trace, seed)
    one_token = {r for r, m in zip(rids, trace[1]) if m == 1}
    ring = eng.recorder.snapshot()
    assert len(ring) == len(finished)
    for r, done in zip(ring, finished):
        dispatched = r["admitted"] or r["prefill_chunks"] or r["decoding"]
        assert r["host_syncs"] == (1 if dispatched else 0), r
        # every row whose prefill ended joined the decode, but a row
        # that ends on its first token: it gave its slot back
        ended_on_first = len(one_token & set(done))
        assert r["attrs"].get("joined_rows", 0) \
            == r["admitted"] - ended_on_first, r
        assert "compiled" not in r["attrs"]
    assert sum(r["attrs"].get("joined_rows", 0) for r in ring) \
        == len(rids) - len(one_token)
    assert eng.pool.num_used == 0


def test_the_routing_ledger_is_the_parents():
    eng, vocab, trace, seed = _moe_chunked()
    tokens, _, _ = _run(eng, vocab, trace, seed)
    assert tokens == MOE_TOKENS
    ring = eng.recorder.snapshot()
    got = [(int(r["attrs"]["moe_routed_tokens"]),
            int(r["attrs"]["moe_dropped_tokens"]),
            [int(x) for x in r["attrs"]["moe_expert_tokens"]])
           for r in ring]
    assert got == MOE_STEPS
    assert eng.metrics.moe_routed_tokens == sum(s[0] for s in MOE_STEPS)


def test_the_dropless_ledger_is_the_parents():
    from quintnet_tpu.models.pangu_moe import PanguMoEConfig, pangu_moe_init
    from quintnet_tpu.serve import pangu_moe_family

    cfg = PanguMoEConfig.tiny()
    eng = ServeEngine(pangu_moe_family(cfg),
                      pangu_moe_init(jax.random.key(0), cfg), max_slots=3,
                      block_size=4, num_blocks=96, max_seq_len=88,
                      chunked_prefill=True, prefill_len=16,
                      prefill_chunk_budget=8)
    tokens, _, _ = _run(eng, cfg.vocab_size, PANGU_TRACE, 2)
    assert tokens == PANGU_TOKENS
    got = [tuple(int(r["attrs"][k]) for k in PANGU_KEYS)
           for r in eng.recorder.snapshot()]
    assert got == PANGU_STEPS


# ---------------------------------------------------------------------
# tokens: the oracle's, with the cache on and off, through preemption
# ---------------------------------------------------------------------

@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["cache_on", "cache_off"])
@pytest.mark.parametrize("sampling", [{}, {"temperature": 0.8, "top_k": 5}],
                         ids=["greedy", "sampled"])
def test_a_row_preempted_before_its_first_token_is_read(
        params, prefix_cache, sampling):
    """The youngest admission is the eviction victim, and here it is a
    row admitted in the same step, its first token still on the device:
    the step reads the first tokens before it evicts (a second read,
    the only step with one), and both requests stay the oracle's."""
    eng = ServeEngine(gpt2_family(CFG), params, max_slots=2, block_size=2,
                      num_blocks=7, max_seq_len=16,
                      prefix_cache=prefix_cache, **sampling)
    pa = np.arange(4, dtype=np.int32) + 1
    pb = np.arange(5, dtype=np.int32) + 40
    ka, kb = jax.random.key(1), jax.random.key(2)
    ra = eng.submit(pa, 8, key=ka)
    eng.step()
    eng.step()
    rb = eng.submit(pb, 6, key=kb)
    eng.step()
    rec = eng.recorder.last()
    assert rec["admitted"] == 1 and rec["preempted"] == 1
    assert rec["host_syncs"] == 2
    assert rec["attrs"]["joined_rows"] == 0   # read before the decode
    assert len(eng.request(rb).generated) == 1
    eng.run()
    assert eng.metrics.preempted == 1
    np.testing.assert_array_equal(eng.result(ra),
                                  _oracle(params, pa, 8, ka, **sampling))
    np.testing.assert_array_equal(eng.result(rb),
                                  _oracle(params, pb, 6, kb, **sampling))
    assert all(r["host_syncs"] == 1 for r in eng.recorder.snapshot()
               if not r["preempted"])
    assert eng.pool.num_used == 0


# ---------------------------------------------------------------------
# a row's first token
# ---------------------------------------------------------------------

@pytest.mark.parametrize("chunked", [False, True], ids=["plain", "chunked"])
def test_eos_as_the_first_token_of_a_joined_row(params, chunked):
    """The row joined the decode before anyone knew its first token is
    EOS: the request ends with that one token, retired at its prompt's
    length (the published chain holds the prompt alone), keeping the
    prefill's key, and the decode's token for it counts nowhere."""
    rng = np.random.default_rng(11)
    q = np.asarray(rng.integers(0, CFG.vocab_size, (6,)), np.int32)
    p = np.asarray(rng.integers(0, CFG.vocab_size, (12,)), np.int32)
    kq, kp = jax.random.key(3), jax.random.key(4)
    eos = int(_oracle(params, p, 1, kp)[len(p)])
    extra = (dict(chunked_prefill=True, prefill_len=8,
                  prefill_chunk_budget=8) if chunked else {})
    eng = ServeEngine(gpt2_family(CFG), params, max_slots=2, block_size=4,
                      num_blocks=24, max_seq_len=32, eos_token_id=eos,
                      **extra)
    published = []
    publish = eng.pool.publish

    def spy(tokens, blocks, n_tokens, **kw):
        published.append((np.asarray(tokens).copy(), int(n_tokens)))
        return publish(tokens, blocks, n_tokens, **kw)

    eng.pool.publish = spy
    rq = eng.submit(q, 6, key=kq)
    while eng.request(rq).generated == []:
        eng.step()
    rp = eng.submit(p, 6, key=kp)
    slot = eng._free_slots()[0]
    while True:
        finished = eng.step()
        if rp in finished:
            break
    rec = eng.recorder.last()
    assert rec["attrs"]["joined_rows"] == 1
    assert rec["decode_tokens"] == rec["decoding"] - 1
    req = eng.request(rp)
    assert req.generated == [eos]
    assert eng.result(rp).tolist() == p.tolist() + [eos]
    tokens, n = published[-1]
    assert n == len(p) and tokens[:n].tolist() == p.tolist()
    key2 = jax.random.key_data(
        jax.random.split(jax.random.wrap_key_data(req.key_data))[0])
    np.testing.assert_array_equal(eng._key_data[slot], np.asarray(key2))
    assert eng._pos[slot] == 0 and eng._slot_req[slot] is None
    eng.run()
    ref = _oracle(params, q, 6, kq, eos_token_id=eos)
    out = eng.result(rq)
    np.testing.assert_array_equal(out, ref[:len(out)])
    assert eng.pool.num_used == 0


@pytest.mark.parametrize("chunked", [False, True], ids=["plain", "chunked"])
@pytest.mark.parametrize("ask", [{"max_new_tokens": 1},
                                 {"max_new_tokens": 5,
                                  "prefill_only": True}],
                         ids=["one_token", "prefill_only"])
def test_a_row_known_to_end_never_joins(params, ask, chunked):
    """A one-token budget or the prefill-only phase ends the request on
    its first token, and the host knows it at the prefill: the row
    gives its slot back at once, as it always did — in plain mode the
    next request takes the slot in the same step — and never joins the
    decode."""
    rng = np.random.default_rng(12)
    r_, s_ = (np.asarray(rng.integers(0, CFG.vocab_size, (n,)), np.int32)
              for n in (7, 5))
    extra = (dict(chunked_prefill=True, prefill_len=8) if chunked else {})
    eng = ServeEngine(gpt2_family(CFG), params, max_slots=1, block_size=4,
                      num_blocks=24, max_seq_len=32, **extra)
    kr, ks = jax.random.key(5), jax.random.key(6)
    rr = eng.submit(r_, key=kr, **ask)
    rs = eng.submit(s_, 4, key=ks)
    finished = eng.step()
    rec = eng.recorder.last()
    assert finished[0] == rr and rec["host_syncs"] == 1
    assert len(eng.request(rr).generated) == 1
    assert eng.request(rr).handed_off is bool(ask.get("prefill_only"))
    if chunked:       # the admission loop ran before the chunk ended
        assert rec["admitted"] == 1 and rec["decoding"] == 0
        assert "joined_rows" not in rec["attrs"]
    else:             # the next request took the slot in the same step
        assert rec["admitted"] == 2 and rec["attrs"]["joined_rows"] == 1
    eng.run()
    np.testing.assert_array_equal(
        eng.result(rr)[len(r_):], _oracle(params, r_, 1, kr)[len(r_):])
    np.testing.assert_array_equal(eng.result(rs),
                                  _oracle(params, s_, 4, ks))
    assert eng.pool.num_used == 0


def test_a_speculating_engine_reads_each_first_token_at_once(params):
    """The drafter reads every row's tokens before the verify, so a
    speculating engine joins no row: its tokens stay the oracle's and
    the plain engine's."""
    rng = np.random.default_rng(13)
    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)), np.int32)
               for n in (6, 9, 4)]
    keys = [jax.random.key(20 + i) for i in range(3)]
    # repeating prompts, so the n-gram drafter proposes
    prompts = [np.tile(pr[:3], 4)[:len(pr) + 4] for pr in prompts]
    eng = ServeEngine(gpt2_family(CFG), params, max_slots=3, block_size=4,
                      num_blocks=48, max_seq_len=40, spec=SpecConfig())
    rids = [eng.submit(pr, 8, key=k) for pr, k in zip(prompts, keys)]
    eng.run()
    ring = eng.recorder.snapshot()
    assert any(r["spec_step"] for r in ring)
    assert all(r["attrs"].get("joined_rows", 0) == 0 for r in ring)
    # a read an admission where it admitted the three, one for the step
    assert ring[0]["admitted"] == 3 and ring[0]["host_syncs"] == 3 + 1
    for pr, k, rid in zip(prompts, keys, rids):
        np.testing.assert_array_equal(eng.result(rid),
                                      _oracle(params, pr, 8, k))


def test_an_engine_under_a_mesh_reads_each_first_token_at_once(params):
    """Under a mesh the prefill's outputs live on the mesh and the
    decode's rows are uploaded to one device: a joined row would make
    the decode compile again inside the window. Such an engine reads a
    first token as its prefill ends, and compiles nothing after its
    warm-up."""
    from quintnet_tpu.core.mesh import mesh_from_sizes
    from quintnet_tpu.models.gpt2 import gpt2_to_tp_layout

    eng = ServeEngine(gpt2_family(CFG), gpt2_to_tp_layout(params, CFG, 2),
                      max_slots=3, block_size=4, num_blocks=40,
                      max_seq_len=32, mesh=mesh_from_sizes(tp=2))
    eng.warmup()
    rng = np.random.default_rng(14)
    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)), np.int32)
               for n in (5, 9, 3)]
    keys = [jax.random.key(30 + i) for i in range(3)]
    rids = [eng.submit(pr, 5, key=k) for pr, k in zip(prompts, keys)]
    eng.run()
    ring = eng.recorder.snapshot()
    assert all("compiled" not in r["attrs"] for r in ring)
    assert all(r["attrs"].get("joined_rows", 0) == 0 for r in ring)
    assert ring[0]["admitted"] == 3 and ring[0]["host_syncs"] == 3 + 1
    for pr, k, rid in zip(prompts, keys, rids):
        np.testing.assert_array_equal(eng.result(rid),
                                      _oracle(params, pr, 5, k))
