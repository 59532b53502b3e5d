"""Continuous-batching serving goldens (quintnet_tpu/serve/).

THE contract: the engine's output for every request is token-for-token
identical to an independent ``gpt2_generate``/``llama_generate`` call —
no matter how requests are staggered, packed into slots, grown across
KV blocks, preempted and resumed, or sharded over a tp mesh. Plus the
operational invariants: one compiled decode step per engine (no
recompiles as requests come and go), free-list/pool accounting, FCFS
vs priority admission, EOS retirement, streaming callbacks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.models.gpt2_generate import gpt2_generate
from quintnet_tpu.serve import (KVPool, Request, Scheduler, ServeEngine,
                                generate, generate_stream, gpt2_family)

CFG = GPT2Config.tiny(n_layer=2)


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _prompts(rng, lengths):
    return [np.asarray(rng.integers(0, CFG.vocab_size, (t,)), np.int32)
            for t in lengths]


def _engine(params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("max_seq_len", 40)
    return ServeEngine(gpt2_family(CFG), params, **kw)


def _run_staggered(eng, prompts, max_new, keys, arrivals):
    """Submit request i when the engine has taken ``arrivals[i]`` steps;
    run to completion; return outputs in submission order."""
    order = np.argsort(np.asarray(arrivals), kind="stable")
    rids = {}
    submitted, step = 0, 0
    while submitted < len(prompts) or eng.has_work:
        while (submitted < len(prompts)
               and arrivals[order[submitted]] <= step):
            i = order[submitted]
            rids[i] = eng.submit(prompts[i], max_new[i], key=keys[i])
            submitted += 1
        eng.step()
        step += 1
        assert step < 2000, "engine failed to drain"
    return [eng.result(rids[i]) for i in range(len(prompts))]


# ---------------------------------------------------------------------
# pool + scheduler units
# ---------------------------------------------------------------------

class TestKVPool:
    def _pool(self, num_blocks=8):
        return KVPool(n_layers=2, n_kv_heads=2, head_dim=4, block_size=4,
                      num_blocks=num_blocks)

    def test_null_block_reserved(self):
        p = self._pool()
        got = p.alloc(p.usable_blocks)
        assert got is not None and 0 not in got
        assert p.alloc(1) is None  # exhausted, never hands out block 0

    def test_alloc_free_roundtrip(self):
        p = self._pool()
        a = p.alloc(3)
        assert p.num_used == 3
        p.free(a)
        assert p.num_used == 0 and p.num_free == p.usable_blocks

    def test_alloc_never_partial(self):
        p = self._pool(num_blocks=4)  # 3 usable
        assert p.alloc(5) is None
        assert p.num_free == 3  # nothing leaked

    def test_double_free_raises(self):
        p = self._pool()
        a = p.alloc(1)
        p.free(a)
        with pytest.raises(ValueError, match="double free"):
            p.free(a)

    def test_blocks_for_and_utilization(self):
        p = self._pool()
        assert p.blocks_for(1) == 1
        assert p.blocks_for(4) == 1
        assert p.blocks_for(5) == 2
        p.alloc(7)
        assert p.utilization == 1.0

    def test_paged_write_gather_roundtrip(self):
        """paged_write + paged_gather give back a position-ordered
        dense view of ONE layer through an arbitrary block table, the
        other layer and the pad lanes untouched."""
        from quintnet_tpu.nn.attention import paged_gather, paged_write

        bs, nb, H, Dh, F = 4, 6, 2, 3, 8
        k = jnp.zeros((2, nb * bs, F))
        v = jnp.zeros_like(k)
        tables = jnp.asarray([[3, 1, 0], [5, 2, 4]], jnp.int32)
        # write token at position 5 of row 0 (block 1, offset 1) and
        # position 2 of row 1 (block 5, offset 2)
        pos = jnp.asarray([[5], [2]], jnp.int32)
        kin = 1 + jnp.arange(2 * H * Dh, dtype=jnp.float32).reshape(
            2, H, 1, Dh)
        k, v = paged_write(k, v, jnp.int32(1), kin, kin, pos,
                           jnp.ones((2,), jnp.int32),
                           block_tables=tables, block_size=bs)
        view = paged_gather(k, 1, tables, block_size=bs,
                            head_shape=(H, Dh))           # [2, H, 12, Dh]
        np.testing.assert_array_equal(np.asarray(view[0, :, 5]),
                                      np.asarray(kin[0, :, 0]))
        np.testing.assert_array_equal(np.asarray(view[1, :, 2]),
                                      np.asarray(kin[1, :, 0]))
        assert float(jnp.abs(view[0, :, :5]).sum()) == 0.0
        assert float(jnp.abs(k[0]).sum()) == 0.0          # other layer
        assert float(jnp.abs(k[..., H * Dh:]).sum()) == 0.0   # pad lanes
        assert int((k != 0).sum()) == 2 * H * Dh


class TestScheduler:
    def _mk(self, policy="fcfs", num_blocks=16):
        pool = KVPool(n_layers=1, n_kv_heads=1, head_dim=2, block_size=4,
                      num_blocks=num_blocks)
        return Scheduler(pool, policy=policy), pool

    def _req(self, rid, t0=4, arrival=None, priority=0):
        return Request(rid=rid, prompt=np.zeros((t0,), np.int32),
                       max_new_tokens=4, priority=priority,
                       arrival=arrival if arrival is not None else rid)

    def test_fcfs_order(self):
        s, _ = self._mk()
        for i in (0, 1, 2):
            s.submit(self._req(i))
        assert [s.next_admission(1).rid for _ in range(3)] == [0, 1, 2]

    def test_priority_order_with_arrival_tiebreak(self):
        s, _ = self._mk(policy="priority")
        s.submit(self._req(0, priority=5))
        s.submit(self._req(1, priority=0))
        s.submit(self._req(2, priority=0))
        assert [s.next_admission(1).rid for _ in range(3)] == [1, 2, 0]

    def test_admission_budget_head_of_line(self):
        """If the FRONT request does not fit, nothing jumps the queue."""
        s, pool = self._mk(num_blocks=4)  # 3 usable
        pool.alloc(2)                     # only 1 block left
        s.submit(self._req(0, t0=8))      # needs 3 blocks
        s.submit(self._req(1, t0=2))      # would fit, but is behind
        assert s.next_admission(4) is None
        assert len(s.waiting) == 2

    def test_no_free_slots_blocks_admission(self):
        s, _ = self._mk()
        s.submit(self._req(0))
        assert s.next_admission(0) is None

    def test_preempt_victim_is_youngest_admission(self):
        s, _ = self._mk()
        rs = [self._req(i) for i in range(3)]
        for r in rs:
            s.submit(r)
        for _ in range(3):
            s.next_admission(1)
        assert Scheduler.preempt_victim(rs).rid == 2
        # preempted request resumes ahead of younger arrivals
        s.submit(self._req(9, arrival=99))
        s.push_front(rs[2])
        assert s.waiting[0].rid == 2


# ---------------------------------------------------------------------
# golden parity (the acceptance contract)
# ---------------------------------------------------------------------

LENGTHS = (5, 11, 3, 8, 6, 14, 4, 9)
MAX_NEW = (10, 6, 12, 8, 5, 7, 11, 9)
ARRIVALS = (0, 0, 1, 2, 4, 5, 7, 9)


def _oracle(params, prompt, max_new, key, temperature=0.0, top_k=0,
            eos=None):
    return gpt2_generate(params, prompt[None], CFG, max_new_tokens=max_new,
                         temperature=temperature, top_k=top_k,
                         eos_token_id=eos, key=key)[0]


def test_golden_parity_staggered_greedy(params, rng):
    """8 staggered mixed-length requests, greedy: engine output ==
    independent gpt2_generate per request, token for token."""
    prompts = _prompts(rng, LENGTHS)
    keys = [jax.random.key(40 + i) for i in range(len(prompts))]
    eng = _engine(params)
    outs = _run_staggered(eng, prompts, list(MAX_NEW), keys,
                          list(ARRIVALS))
    for p, m, k, o in zip(prompts, MAX_NEW, keys, outs):
        np.testing.assert_array_equal(o, _oracle(params, p, m, k))
    assert eng.metrics.finished == len(prompts)
    assert eng.metrics.peak_running >= 2  # batching actually happened


def test_golden_parity_staggered_sampling(params, rng):
    """Same trace, fixed-seed temperature/top-k sampling."""
    prompts = _prompts(rng, LENGTHS)
    keys = [jax.random.key(70 + i) for i in range(len(prompts))]
    eng = _engine(params, temperature=0.9, top_k=7)
    outs = _run_staggered(eng, prompts, list(MAX_NEW), keys,
                          list(ARRIVALS))
    for p, m, k, o in zip(prompts, MAX_NEW, keys, outs):
        np.testing.assert_array_equal(
            o, _oracle(params, p, m, k, temperature=0.9, top_k=7))


def test_golden_parity_llama(rng):
    """Llama family (GQA cache, rope-at-position decode) through the
    same engine: greedy parity vs llama_generate."""
    from quintnet_tpu.models.llama import LlamaConfig, llama_init
    from quintnet_tpu.models.llama_generate import llama_generate
    from quintnet_tpu.serve import llama_family

    cfg = LlamaConfig.tiny(n_layers=2)
    lparams = llama_init(jax.random.key(1), cfg)
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (t,)), np.int32)
               for t in (5, 9, 3, 12)]
    eng = ServeEngine(llama_family(cfg), lparams, max_slots=3,
                      block_size=4, num_blocks=32, max_seq_len=32)
    keys = [jax.random.key(7)] * 4
    outs = _run_staggered(eng, prompts, [8, 6, 10, 5], keys, [0, 1, 1, 3])
    for p, m, o in zip(prompts, [8, 6, 10, 5], outs):
        ref = llama_generate(lparams, p[None], cfg, max_new_tokens=m)[0]
        np.testing.assert_array_equal(o, ref)


# ---------------------------------------------------------------------
# scheduling behaviors
# ---------------------------------------------------------------------

def test_staggered_admission_waits_for_slots(params, rng):
    """More requests than slots: the overflow sits in the waiting
    queue and is admitted FCFS as rows retire."""
    prompts = _prompts(rng, (4, 4, 4, 4, 4, 4))
    eng = _engine(params, max_slots=2)
    rids = [eng.submit(p, 5) for p in prompts]
    eng.step()
    assert eng.metrics.running == 2 and eng.metrics.waiting == 4
    eng.run()
    assert eng.metrics.finished == 6
    # FCFS: admission order must follow submission order
    seqs = [eng.request(r).admit_seq for r in rids]
    assert seqs == sorted(seqs)


def test_pool_exhaustion_preemption_and_resume(params, rng):
    """A pool too small for the working set forces eviction of the
    youngest request; the evicted request resumes and still produces
    golden output (recompute + checkpointed key state)."""
    prompts = _prompts(rng, (6, 6, 6))
    keys = [jax.random.key(90 + i) for i in range(3)]
    # 8 usable blocks of 2 tokens = 16 token slots; three requests
    # need up to 3 * (6 + 8) = 42 slots -> guaranteed pressure
    eng = _engine(params, max_slots=3, block_size=2, num_blocks=9,
                  max_seq_len=16, temperature=0.8, top_k=5)
    outs = generate(eng, prompts, max_new_tokens=8, keys=keys)
    assert eng.metrics.preempted >= 1
    for p, k, o in zip(prompts, keys, outs):
        np.testing.assert_array_equal(
            o, _oracle(params, p, 8, k, temperature=0.8, top_k=5))
    # all blocks returned to the pool at the end
    assert eng.pool.num_used == 0


def test_pool_too_small_for_one_request_rejected_at_submit(params, rng):
    """A request the pool can never hold is rejected up front — were it
    queued, admission would return None forever and run() would spin."""
    eng = _engine(params, max_slots=1, block_size=2, num_blocks=3,
                  max_seq_len=16)  # 2 usable blocks = 4 slots
    with pytest.raises(ValueError, match="KV pool too small"):
        eng.submit(_prompts(rng, (3,))[0], 8)
    assert not eng.has_work  # nothing was queued


def test_resume_overflow_of_prefill_len_rejected_at_submit(params, rng):
    """With prefill_len < max_seq_len, a request whose preemption-resume
    prefill (prompt + generated) could exceed prefill_len is rejected —
    mid-run it would be a shape error inside the engine."""
    eng = _engine(params, max_seq_len=40, prefill_len=16)
    with pytest.raises(ValueError, match="exceeds prefill_len"):
        eng.submit(_prompts(rng, (10,))[0], 8)  # 10 + 8 - 1 > 16
    # the same prompt with a budget that fits runs fine
    out = generate(eng, _prompts(rng, (10,))[0:1], max_new_tokens=7)[0]
    assert len(out) == 17


def test_eos_retirement(params, rng):
    """Rows retire at their first EOS: output is the oracle's row
    truncated at EOS (the oracle pads with EOS to max_new), and the
    engine frees the row's blocks early."""
    prompt = _prompts(rng, (6,))[0]
    key = jax.random.key(5)
    plain = _oracle(params, prompt, 12, key)
    eos = int(plain[len(prompt) + 4])  # forces a mid-stream EOS hit
    ref = _oracle(params, prompt, 12, key, eos=eos)

    eng = _engine(params, eos_token_id=eos)
    out = generate(eng, [prompt], max_new_tokens=12, keys=[key])[0]
    assert len(out) < len(prompt) + 12  # actually retired early
    np.testing.assert_array_equal(out, ref[:len(out)])
    assert (np.asarray(ref[len(out):]) == eos).all()
    assert eng.pool.num_used == 0


def test_priority_policy_jumps_queue(params, rng):
    prompts = _prompts(rng, (4, 4, 4))
    eng = _engine(params, max_slots=1, policy="priority")
    r0 = eng.submit(prompts[0], 3)            # admitted first
    r1 = eng.submit(prompts[1], 3, priority=5)
    r2 = eng.submit(prompts[2], 3, priority=0)
    eng.run()
    assert (eng.request(r2).admit_seq < eng.request(r1).admit_seq)
    assert eng.request(r0).admit_seq == 0


def test_streaming_callback(params, rng):
    prompt = _prompts(rng, (5,))[0]
    got = []
    eng = _engine(params)
    out = generate_stream(eng, prompt, max_new_tokens=6,
                          on_token=lambda rid, tok, last:
                          got.append((tok, last)))
    toks = [t for t, _ in got]
    np.testing.assert_array_equal(out[len(prompt):], toks)
    assert [last for _, last in got] == [False] * 5 + [True]


def test_generate_max_steps_error_names_unfinished(params, rng):
    """Exhausting max_steps raises an ACTIONABLE error naming every
    unfinished request id and its progress, instead of whatever
    engine.result does on an unfinished row."""
    eng = _engine(params)
    prompts = _prompts(rng, (4, 4))
    with pytest.raises(RuntimeError) as ei:
        generate(eng, prompts, max_new_tokens=8, max_steps=2)
    msg = str(ei.value)
    assert "unfinished" in msg and "max_steps=2" in msg
    assert "rid 0" in msg and "rid 1" in msg
    assert "/8 tokens" in msg


def test_stream_preempted_mid_stream_orders_tokens(params, rng):
    """generate_stream under policy='priority' with queued background
    work: the low-urgency streaming request is admitted youngest, gets
    preempted when the pool dries, resumes — and still delivers its
    tokens in order with is_last firing exactly once, nothing
    re-delivered across the preemption."""
    eng = _engine(params, max_slots=2, block_size=2, num_blocks=12,
                  max_seq_len=20, policy="priority")
    # bg0 is LONG: it keeps growing blocks while the stream runs, so
    # the pool dries with the stream as the youngest admission (the
    # eviction victim); bg1 is the queued background work
    bg_prompts = _prompts(rng, (6, 4))
    bg_new = (12, 4)
    bg_keys = [jax.random.key(200 + i) for i in range(2)]
    bg = [eng.submit(p, m, key=k, priority=0)
          for p, m, k in zip(bg_prompts, bg_new, bg_keys)]

    sp = _prompts(rng, (4,))[0]
    skey = jax.random.key(300)
    got = []
    out = generate_stream(
        eng, sp, max_new_tokens=8, key=skey, priority=5,
        on_token=lambda rid, tok, last: got.append((rid, tok, last)))
    srid = got[0][0]
    assert eng.request(srid).preemptions >= 1  # actually preempted
    toks = [t for _, t, _ in got]
    np.testing.assert_array_equal(out[len(sp):], toks)  # in order, once
    lasts = [last for *_, last in got]
    assert lasts.count(True) == 1 and lasts[-1] is True
    np.testing.assert_array_equal(out, _oracle(params, sp, 8, skey))
    # the queued background work is untouched by the streaming detour
    eng.run()
    for p, m, k, r in zip(bg_prompts, bg_new, bg_keys, bg):
        np.testing.assert_array_equal(eng.result(r),
                                      _oracle(params, p, m, k))


# ---------------------------------------------------------------------
# pause / drain / progress export+restore (the migration surface)
# ---------------------------------------------------------------------

def test_export_restore_progress_cross_engine_exact(params, rng):
    """The fleet migration contract at engine level: progress exported
    mid-flight from engine A (running slot: evolved key; waiting row:
    submit-time key) restored on a fresh engine B continues
    token-identically — sampling on."""
    prompts = _prompts(rng, (5, 6))
    keys = [jax.random.key(40 + i) for i in range(2)]
    a = _engine(params, max_slots=1, temperature=0.9, top_k=7)
    rids = [a.submit(p, 8, key=k) for p, k in zip(prompts, keys)]
    for _ in range(3):
        a.step()
    progs = a.export_progress()
    assert [p.rid for p in progs] == rids
    assert len(progs[0].generated) >= 1        # running, mid-flight
    assert progs[1].generated == []            # still waiting

    b = _engine(params, max_slots=2, temperature=0.9, top_k=7)
    new_rids = [b.restore_progress(p) for p in progs]
    b.run()
    for p, k, nr in zip(prompts, keys, new_rids):
        np.testing.assert_array_equal(
            b.result(nr),
            _oracle(params, p, 8, k, temperature=0.9, top_k=7))


def test_restore_progress_validation(params, rng):
    from quintnet_tpu.serve import RequestProgress

    eng = _engine(params)
    prompt = _prompts(rng, (4,))[0]
    key_data = np.asarray(jax.random.key_data(jax.random.key(0)))
    with pytest.raises(ValueError, match="key_data"):
        eng.restore_progress(RequestProgress(
            rid=0, prompt=prompt, generated=[1], key_data=None,
            max_new_tokens=4))
    with pytest.raises(ValueError, match="nothing left"):
        eng.restore_progress(RequestProgress(
            rid=0, prompt=prompt, generated=[1, 2], key_data=key_data,
            max_new_tokens=2))
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        eng.restore_progress(RequestProgress(
            rid=0, prompt=np.zeros(39, np.int32), generated=[],
            key_data=key_data, max_new_tokens=4))


def test_pause_admissions_and_drain(params, rng):
    """drain() finishes the active slots and leaves the waiting queue
    intact with admissions paused; resume_admissions picks the queue
    back up."""
    eng = _engine(params, max_slots=1)
    p1, p2 = _prompts(rng, (4, 4))
    r1 = eng.submit(p1, 4, key=jax.random.key(1))
    eng.step()                                  # r1 active
    r2 = eng.submit(p2, 4, key=jax.random.key(2))
    finished = eng.drain()
    assert r1 in finished
    assert eng.admissions_paused
    assert eng.request(r2).state == "waiting"   # queued, not dropped
    assert eng.pool.num_used == 0
    eng.resume_admissions()
    eng.run()
    np.testing.assert_array_equal(eng.result(r2),
                                  _oracle(params, p2, 4,
                                          jax.random.key(2)))


def test_submit_validation(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        eng.submit(np.zeros(39, np.int32), 2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros(4, np.int32), 0)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros(0, np.int32), 2)
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(np.zeros(4, np.int32), 2, deadline_s=0)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_deadline_mid_decode_retires_typed_and_publishes(params, rng):
    """A request whose deadline passes MID-GENERATION is retired with a
    typed DeadlineExceeded — not finished late, not silently dropped —
    and its blocks are PUBLISHED: the pool holds no live references
    afterwards and a retry of the same prompt re-prefills almost
    nothing. An unconstrained request in the same batch is untouched."""
    from quintnet_tpu.serve import DeadlineExceeded

    clk = _FakeClock()
    eng = _engine(params, clock=clk)
    p1, p2 = _prompts(rng, (6, 5))
    k2 = jax.random.key(21)
    r1 = eng.submit(p1, 16, key=jax.random.key(20), deadline_s=5.0)
    r2 = eng.submit(p2, 8, key=k2)
    for _ in range(3):
        eng.step()
    got_before = len(eng.request(r1).generated)
    assert 0 < got_before < 16          # genuinely mid-generation
    clk.t = 10.0                        # r1's deadline lapses
    finished = eng.step()
    assert r1 in finished
    with pytest.raises(DeadlineExceeded) as ei:
        eng.result(r1)
    assert ei.value.generated == got_before
    assert eng.metrics.deadline_exceeded == 1
    # the survivor finishes golden
    eng.run()
    np.testing.assert_array_equal(eng.result(r2),
                                  _oracle(params, p2, 8, k2))
    assert eng.pool.num_used == 0       # nothing leaked: published,
    #                                     released, only cached remains
    # the published prefix is live: resubmitting the same prompt hits
    # the cache instead of re-prefilling
    hits0 = eng.metrics.prefix_hit_tokens
    eng.submit(p1, 4, key=jax.random.key(22))
    eng.run()
    assert eng.metrics.prefix_hit_tokens > hits0


def test_deadline_expired_while_waiting_is_typed_too(params, rng):
    """A queued (never admitted) request whose deadline passes is
    failed with DeadlineExceeded(generated=0) at the next step — the
    scheduler does not leak it, and admissions behind it proceed."""
    from quintnet_tpu.serve import DeadlineExceeded

    clk = _FakeClock()
    eng = _engine(params, max_slots=1, clock=clk)
    p1, p2, p3 = _prompts(rng, (4, 4, 5))
    k3 = jax.random.key(32)
    r1 = eng.submit(p1, 8, key=jax.random.key(30))
    r2 = eng.submit(p2, 8, key=jax.random.key(31), deadline_s=5.0)
    r3 = eng.submit(p3, 6, key=k3)
    eng.step()                          # r1 occupies the single slot
    assert eng.request(r2).state == "waiting"
    clk.t = 6.0
    eng.step()
    with pytest.raises(DeadlineExceeded) as ei:
        eng.result(r2)
    assert ei.value.generated == 0
    eng.run()
    np.testing.assert_array_equal(eng.result(r3),
                                  _oracle(params, p3, 6, k3))
    # exported progress carries REMAINING deadline budget for the
    # migration contract (none of the survivors had one here)
    assert eng.result(r1) is not None


# ---------------------------------------------------------------------
# the one-compiled-program invariant
# ---------------------------------------------------------------------

def test_no_recompilation_over_20_step_trace(params, rng):
    """Admitting/retiring/preempting across a 20-step trace must hit
    the SAME two compiled programs: zero backend compiles observed via
    jax.monitoring after warmup, jit cache size stays 1 per program."""
    from quintnet_tpu.obs.recorder import startup

    eng = _engine(params, max_slots=3, block_size=2, num_blocks=12,
                  max_seq_len=16)
    # warmup: one full lifecycle (admission/prefill, decode, retire)
    eng.submit(_prompts(rng, (4,))[0], 3)
    eng.run()
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}

    # the program's own record counts every backend compile or load
    compiled0 = startup().totals.get("programs", 0)
    assert compiled0 > 0                # (and it was listening)
    prompts = _prompts(rng, (3, 5, 4, 6, 3, 5))
    arrivals = [0, 1, 3, 6, 10, 14]
    submitted, step = 0, 0
    rids = []
    for step in range(20):
        while (submitted < len(prompts)
               and arrivals[submitted] <= step):
            rids.append(eng.submit(prompts[submitted], 4))
            submitted += 1
        eng.step()
    assert submitted == len(prompts)
    assert eng.metrics.finished >= 4  # retirements happened mid-trace
    assert startup().totals.get("programs", 0) == compiled0
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}


# ---------------------------------------------------------------------
# TP-sharded engine
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_tp2_engine_matches_single_device(params, rng):
    """The whole engine step under a tp=2 shard_map (head-sharded pool,
    RowParallel psum per cached layer): outputs identical to the
    unsharded engine's — which are themselves golden vs gpt2_generate."""
    from quintnet_tpu.core.mesh import mesh_from_sizes
    from quintnet_tpu.models.gpt2 import gpt2_to_tp_layout

    prompts = _prompts(rng, (5, 9, 3))
    keys = [jax.random.key(50 + i) for i in range(3)]
    mesh = mesh_from_sizes(tp=2)
    tp_params = gpt2_to_tp_layout(params, CFG, 2)
    eng = _engine(tp_params, mesh=mesh)
    outs = generate(eng, prompts, max_new_tokens=[8, 6, 10], keys=keys)
    for p, m, k, o in zip(prompts, (8, 6, 10), keys, outs):
        np.testing.assert_array_equal(o, _oracle(params, p, m, k))


# ---------------------------------------------------------------------
# the pool in place (PR 28): carried whole through the layer scan,
# addressed (layer, slot), its rows lane-aligned
# ---------------------------------------------------------------------
_POOL_KINDS = ("gpt2", "llama", "llama-int8", "hybrid")
_BS, _LIVE = 4, {0: (5, 9, 2), 2: (7, 11, 3)}    # slot -> its blocks
_POS = np.array([6, 0, 9], np.int32)             # slot 1 is dead


def _pool_engine(kind):
    """A three-slot engine of ``kind`` and its decode, smallest-bucket
    prefill and verify programs as ``{name: (jitted, tail)}``: the
    call is ``jitted(params, *pools, *tail)`` with the pools leading
    the outputs. The tables hold two live rows; the prefill is a chunk
    at an offset (positions 5..10 of slot 0); the verify scores 2 and
    3 tokens on the live rows and none on the dead one."""
    from quintnet_tpu.serve import SpecConfig

    kw = dict(max_slots=3, block_size=_BS, num_blocks=24, max_seq_len=32)
    if kind == "hybrid":
        from quintnet_tpu.models.granite_hybrid import (
            GraniteHybridConfig, granite_hybrid_init)
        from quintnet_tpu.serve import granite_hybrid_family

        cfg = GraniteHybridConfig.tiny()
        eng = ServeEngine(granite_hybrid_family(cfg),
                          granite_hybrid_init(jax.random.key(7), cfg),
                          prefix_cache=False, **kw)
    elif kind == "gpt2":
        eng = ServeEngine(gpt2_family(CFG),
                          gpt2_init(jax.random.key(0), CFG),
                          spec=SpecConfig(max_draft=4), **kw)
    else:
        from quintnet_tpu.models.llama import LlamaConfig, llama_init
        from quintnet_tpu.serve import llama_family

        cfg = LlamaConfig.tiny()
        eng = ServeEngine(
            llama_family(cfg), llama_init(jax.random.key(4), cfg),
            kv_dtype="int8" if kind == "llama-int8" else None,
            spec=SpecConfig(max_draft=4), **kw)
    tables = np.zeros((3, eng.table_width), np.int32)
    for slot, blocks in _LIVE.items():
        tables[slot, :len(blocks)] = blocks
    tables, pos = jnp.asarray(tables), jnp.asarray(_POS)
    keys = jnp.asarray(eng._key_data)
    b0 = eng.prefill_buckets[0]
    lens = jnp.asarray([2, 0, 3], jnp.int32)
    progs = {
        "decode": (eng._decode.fn,
                   (jnp.zeros((3,), jnp.int32), pos, tables, keys)),
        "prefill": (eng._prefills[b0].fn,
                    (jnp.zeros((1, b0), jnp.int32), jnp.int32(5),
                     jnp.int32(11), tables[0], jnp.int32(0), jnp.int32(0),
                     keys[0])
                    + ((jnp.int32(0),) if kind == "hybrid" else ())),
    }
    ids = jnp.zeros((3, 3), jnp.int32)
    if kind == "hybrid":
        fam, pool = eng.family, eng.pool

        def verify(params, k, v, ssm, conv, ids, starts, lens, tables):
            return fam.verify(params, k, v, ids, starts, lens, tables,
                              _BS, policy=pool.policy,
                              state=(ssm, conv))[1:]

        progs["verify"] = (jax.jit(verify, donate_argnums=(1, 2, 3, 4)),
                           (ids, pos, lens, tables))
    else:
        progs["verify"] = (eng._verifies[2].fn,
                           (ids, pos, lens, tables, keys))
    return eng, progs


# what each program of _pool_engine writes: (slot, position) pairs
_WRITTEN = {
    "decode": [(0, 6), (2, 9)],
    "prefill": [(0, p) for p in range(5, 11)],
    "verify": [(0, 6), (0, 7), (2, 9), (2, 10), (2, 11)],
}


@pytest.mark.parametrize("kind", _POOL_KINDS)
def test_pool_rides_no_scan_as_xs_or_ys(kind):
    """THE structural gate of PR 28: no serving program of any family
    slices a per-sequence buffer (k, v, scales, recurrent state) into
    its layer scan as xs or stacks one out as ys — they ride the carry
    whole (analysis.pool_scan_operands reads zero)."""
    from quintnet_tpu.analysis import pool_scan_operands

    eng, progs = _pool_engine(kind)
    pools = eng.pool.caches()
    for name, (fn, tail) in progs.items():
        for buf in pools:
            assert pool_scan_operands(
                fn, eng.params, *pools, *tail,
                pool_shape=buf.shape) == 0, (kind, name, buf.shape)


def test_pool_scan_operands_sees_the_old_form():
    """The counter is shown the form PR 28 deleted — the pool sliced in
    as xs, stacked back as ys — and the form that replaced it."""
    from quintnet_tpu.analysis import pool_scan_operands

    pool = jnp.zeros((3, 8, 4))
    idx = jnp.asarray([1, 5])
    row = jnp.ones((2, 4))

    def old_form(pool):
        def body(h, layer_pool):
            layer_pool = layer_pool.at[idx].set(row)
            return h + layer_pool[idx].sum(), layer_pool
        return jax.lax.scan(body, 0.0, pool)

    def carried(pool):
        def body(carry, layer):
            h, pool = carry
            pool = pool.at[layer, idx].set(row)
            return (h + pool[layer, idx].sum(), pool), None
        return jax.lax.scan(body, (0.0, pool), jnp.arange(3))[0]

    def xs_only(pool):
        return jax.lax.scan(lambda h, p: (h + p[idx].sum(), None), 0.0,
                            pool)[0]

    assert pool_scan_operands(old_form, pool, pool_shape=pool.shape) == 1
    assert pool_scan_operands(xs_only, pool, pool_shape=pool.shape) == 1
    assert pool_scan_operands(carried, pool, pool_shape=pool.shape) == 0
    np.testing.assert_array_equal(old_form(pool)[1], carried(pool)[1])


@pytest.mark.parametrize("kind", ("gpt2", "llama-int8", "hybrid"))
def test_every_pool_buffer_is_donated_and_aliased(kind):
    """k, v, the scale arrays, the recurrent state: every buffer a
    program carries comes back in the buffer it arrived in."""
    from quintnet_tpu.analysis import donation_report

    eng, progs = _pool_engine(kind)
    pools = eng.pool.caches()
    for name in ("decode", "prefill"):
        fn, tail = progs[name]
        rep = donation_report(fn, eng.params, *pools, *tail)
        rows = [a for a in rep.args
                if any(a.path.startswith(f"[0][{i}]")
                       for i in range(1, len(pools) + 1))]
        assert [a.shape for a in rows] == [p.shape for p in pools]
        assert all(a.donated and a.aliasable for a in rows), (
            kind, name, rep.summary())


def _noise(rng, like):
    if like.dtype == jnp.int8:
        return jnp.asarray(rng.integers(-100, 100, like.shape), jnp.int8)
    if like.ndim == 3 and like.shape[-1] < 8:        # scales [L, nb, H]
        return jnp.asarray(rng.uniform(0.5, 2.0, like.shape), like.dtype)
    return jnp.asarray(rng.standard_normal(like.shape), like.dtype)


@pytest.mark.parametrize("name", ("decode", "prefill", "verify"))
@pytest.mark.parametrize("kind", _POOL_KINDS)
def test_untouched_rows_stay_untouched(kind, name):
    """One step of a program over a pool full of noise: every row it
    does not address — every layer of every block in no live table,
    every pad lane of those, every state row of another slot — is
    bit-identical afterwards, and every slot it does address has moved
    in EVERY layer (what a layer index off by one breaks, and one
    step's logits do not show)."""
    eng, progs = _pool_engine(kind)
    fn, tail = progs[name]
    rng = np.random.default_rng(11)
    pools = [_noise(rng, p) for p in eng.pool.caches()]
    before = [np.array(p) for p in pools]
    after = [np.asarray(p) for p in fn(eng.params, *pools, *tail)[
        :len(pools)]]
    slots = np.array([_LIVE[s][p // _BS] * _BS + p % _BS
                      for s, p in _WRITTEN[name]])
    touched = np.unique(slots // _BS)
    scaled = eng.pool.policy.scaled
    hd = eng.pool.n_kv_heads * eng.pool.head_dim
    for b, a in zip(before[:2], after[:2]):                     # k, v
        L, n, f = b.shape
        if scaled:      # a touched block is requantized whole
            still = np.ones(n // _BS, bool)
            still[touched] = False
            still = np.repeat(still, _BS)
        else:
            still = np.ones(n, bool)
            still[slots] = False
        still[:_BS] = False            # the null block: anyone's scratch
        np.testing.assert_array_equal(a[:, still], b[:, still])
        moved = (a[:, slots, :hd] != b[:, slots, :hd]).any(axis=-1)
        assert moved.all(), (kind, name, moved)
    rest = list(zip(before[2:], after[2:]))
    if scaled:                                  # k_scale, v_scale
        for b, a in rest:
            still = np.ones(b.shape[1], bool)
            still[touched] = False
            still[0] = False
            np.testing.assert_array_equal(a[:, still], b[:, still])
            assert (a[:, touched] != b[:, touched]).any(axis=-1).all()
    elif rest:                                  # ssm, conv: row = slot
        rows = sorted({s for s, _p in _WRITTEN[name]})
        for b, a in rest:
            still = np.ones(b.shape[1], bool)
            still[rows] = False
            np.testing.assert_array_equal(a[:, still], b[:, still])
            flat = (a[:, rows] != b[:, rows]).reshape(
                b.shape[0], len(rows), -1)
            assert flat.any(axis=-1).all(), (kind, name)


@pytest.mark.parametrize("n_head,head_dim,width",
                         [(3, 64, 256), (2, 128, 256)])
class TestThePadIsInvisible:
    """Feature widths 3 x 64 = 192 (padded to two lanes' worth) and
    2 x 128 (aligned, no pad): the pool's rows are lane-aligned, what
    a token costs is the model's, no pad lane reaches a score or an
    output, and every host-side record keeps the shape it has on the
    wire, bit for bit."""

    def _cfg(self, n_head, head_dim):
        return GPT2Config.tiny(n_layer=2, n_head=n_head,
                               n_embd=n_head * head_dim)

    def _poison(self, pool, hd):
        poisoned = [p.at[..., hd:].set(jnp.nan) for p in (pool.k, pool.v)]
        pool.update(*poisoned)

    def test_width_and_bytes_per_token(self, n_head, head_dim, width):
        pool = KVPool(n_layers=2, n_kv_heads=n_head, head_dim=head_dim,
                      block_size=4, num_blocks=4)
        assert pool.k.shape == pool.v.shape == (2, 16, width)
        assert pool.bytes_per_token == 2 * 2 * n_head * head_dim * 4
        assert pool.bytes_per_block == 4 * pool.bytes_per_token

    def test_pad_lanes_reach_no_output(self, n_head, head_dim, width, rng):
        """NaN in every pad lane: the same tokens, greedy and sampled,
        and the same real lanes in the pool afterwards."""
        cfg = self._cfg(n_head, head_dim)
        params = gpt2_init(jax.random.key(3), cfg)
        prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (t,)),
                              np.int32) for t in (9, 5, 13)]
        keys = [jax.random.key(i) for i in range(3)]
        outs, lanes = [], []
        for poison in (False, True):
            eng = ServeEngine(gpt2_family(cfg), params, max_slots=2,
                              block_size=4, num_blocks=24, max_seq_len=32,
                              temperature=0.8, top_k=5)
            hd = n_head * head_dim
            if poison:
                self._poison(eng.pool, hd)
            rids = [eng.submit(p, 6, key=k) for p, k in zip(prompts, keys)]
            eng.run()
            outs.append([np.asarray(eng.result(r)) for r in rids])
            lanes.append(np.asarray(eng.pool.k[..., :hd]))
        for a, b in zip(*outs):
            np.testing.assert_array_equal(a, b)
        # block 0 is scratch; everything else holds the same bytes
        np.testing.assert_array_equal(lanes[0][:, 4:], lanes[1][:, 4:])

    def test_records_round_trip_bit_for_bit(self, n_head, head_dim, width,
                                            rng):
        """export_chain -> the handoff's wire -> import_chain, and a
        host-tier spill and reload: the records are [L, bs, H, Dh] as
        they always were and hold exactly what was written."""
        import json

        from quintnet_tpu.fleet import wire
        from quintnet_tpu.serve.kv_tier import HostTier

        def mk():
            pool = KVPool(n_layers=2, n_kv_heads=n_head, head_dim=head_dim,
                          block_size=4, num_blocks=4,
                          host_tier=HostTier(byte_budget=1 << 24))
            self._poison(pool, n_head * head_dim)
            return pool

        src, toks = mk(), np.arange(10, dtype=np.int32)
        blocks = src.acquire(3)
        shape = (2, 12, n_head, head_dim)
        k_new = rng.standard_normal(shape).astype(np.float32)
        v_new = rng.standard_normal(shape).astype(np.float32)
        idx = np.concatenate([np.arange(b * 4, (b + 1) * 4)
                              for b in blocks])
        src.update(*src.write_slots(idx, k_new, v_new))
        src.publish(toks, blocks, 10)
        src.release(blocks)
        chain = src.export_chain(toks)
        for j, rec in enumerate(chain["blocks"]):
            assert rec["k"].shape == (2, 4, n_head, head_dim)
            np.testing.assert_array_equal(rec["k"],
                                          k_new[:, j * 4:(j + 1) * 4])
            np.testing.assert_array_equal(rec["v"],
                                          v_new[:, j * 4:(j + 1) * 4])
        # the disaggregated handoff: framed, shipped, imported
        got, _ns = wire.kv_chain_from_wire(json.loads(json.dumps(
            wire.kv_chain_to_wire(chain))))
        dst = mk()
        assert dst.import_chain(got) == 10
        again = dst.export_chain(toks)
        for a, b in zip(chain["blocks"], again["blocks"]):
            np.testing.assert_array_equal(a["k"], b["k"])
            np.testing.assert_array_equal(a["v"], b["v"])
        # a host-tier spill (every block evicted) and reload
        held = dst.acquire(dst.num_available)
        assert dst.host_tier.summary()["records"] == 3
        dst.release(held)
        _covered, keys = dst.plan_promotion(toks)
        assert len(keys) == 3
        assert dst.promote_chain(keys) == (3, 3)
        back = dst.export_chain(toks)
        for a, b in zip(chain["blocks"], back["blocks"]):
            np.testing.assert_array_equal(a["k"], b["k"])
            np.testing.assert_array_equal(a["v"], b["v"])
