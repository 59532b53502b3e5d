"""Speculative-decoding goldens (quintnet_tpu/serve/spec.py).

THE contract: speculation is a pure latency optimization — spec-on
output is BIT-identical to spec-off output for every request, greedy
AND sampled, under preemption, with the prefix cache on, across
migration, for both model families. Plus the operational invariants:
tentative blocks are committed-or-rolled-back within the step that
acquired them (published chains never observe draft slots), the PRNG
split chain advances once per COMMITTED token only, and the bounded-
compile promise extends to <= prefill buckets + verify buckets + 1
decode program.
"""

import jax
import numpy as np
import pytest

from quintnet_tpu.analysis.specs import verify_buckets
from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.models.gpt2_generate import gpt2_generate
from quintnet_tpu.serve import (KVPool, NgramDrafter, ServeEngine,
                                SpecConfig, gpt2_family)

CFG = GPT2Config.tiny(n_layer=2)


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


# params whose greedy dynamics settle into long repetitive runs (so
# acceptance-dependent assertions have something to accept) — verified
# behaviour of this (init key, n_positions) pair
CFG_REP = GPT2Config.tiny(n_layer=2, n_positions=256)


@pytest.fixture(scope="module")
def rep_params():
    return gpt2_init(jax.random.key(1), CFG_REP)


def _engine(params, cfg=CFG, spec=None, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("max_seq_len", 40)
    return ServeEngine(gpt2_family(cfg), params, spec=spec, **kw)


def _oracle(params, prompt, max_new, key, temperature=0.0, top_k=0,
            cfg=CFG):
    return np.asarray(gpt2_generate(
        params, prompt[None], cfg, max_new_tokens=max_new,
        temperature=temperature, top_k=top_k, key=key)[0])


def _run_staggered(eng, prompts, max_new, keys, arrivals):
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
# drafter + config units
# ---------------------------------------------------------------------

class TestDrafter:
    def _d(self, **kw):
        return NgramDrafter(SpecConfig(**kw))

    def test_run_prediction(self):
        # a token run predicts itself: [..., 7,7,7,7] -> draft 7s
        ctx = np.array([3, 1, 7, 7, 7, 7, 7, 7], np.int32)
        d = self._d().draft(ctx, 4)
        np.testing.assert_array_equal(d, [7, 7, 7, 7])

    def test_periodic_prediction(self):
        # period-3 cycle: the suffix matched one period back predicts
        # the whole next period
        ctx = np.tile(np.array([5, 9, 2], np.int32), 4)
        d = self._d().draft(ctx, 6)
        np.testing.assert_array_equal(d, [5, 9, 2, 5, 9, 2])

    def test_periodic_extension_past_buffer_end(self):
        # the most recent match's literal continuation is 1 token (it
        # butts against the end of the buffer); periodic extension
        # must still fill the whole draft budget
        ctx = np.array([4, 4, 4, 4, 4, 4, 4, 4, 4, 4], np.int32)
        np.testing.assert_array_equal(self._d().draft(ctx, 6), [4] * 6)

    def test_no_match_is_empty(self):
        ctx = np.arange(10, dtype=np.int32)  # all tokens distinct
        assert self._d().draft(ctx, 8).size == 0

    def test_cap_and_max_draft(self):
        ctx = np.tile(np.array([5, 9], np.int32), 8)
        assert len(self._d().draft(ctx, 3)) == 3
        assert len(self._d(max_draft=4).draft(ctx, 99)) == 4
        assert self._d().draft(ctx, 0).size == 0

    def test_ngram_min_gate(self):
        # unigram match exists but bigram does not -> ngram_min=2
        # drafts nothing
        ctx = np.array([8, 1, 2, 3, 9, 4, 5, 9], np.int32)
        assert self._d(ngram_min=2).draft(ctx, 4).size == 0
        assert self._d().draft(ctx, 2).size > 0


class TestSpecConfig:
    def test_bucket_ladder_pinned_in_specs(self):
        assert SpecConfig().buckets == verify_buckets(8) == (2, 4, 8)
        assert SpecConfig(max_draft=6).buckets == (2, 4, 6)
        assert SpecConfig(max_draft=2).buckets == (2,)

    def test_bucket_for_smallest_cover(self):
        c = SpecConfig()
        assert c.bucket_for(1) == 2
        assert c.bucket_for(2) == 2
        assert c.bucket_for(3) == 4
        assert c.bucket_for(8) == 8

    def test_validation(self):
        with pytest.raises(ValueError, match="max_draft"):
            SpecConfig(max_draft=0)
        with pytest.raises(ValueError, match="min_draft"):
            SpecConfig(min_draft=0)
        with pytest.raises(ValueError, match="ngram_min"):
            SpecConfig(ngram_min=3, ngram_max=2)
        with pytest.raises(ValueError, match="end at"):
            SpecConfig(max_draft=8, buckets=(2, 4))
        # min_draft clamps to max_draft: the default 2 must not make
        # max_draft=1 (1 draft + bonus) unconstructible
        assert SpecConfig(max_draft=1).min_draft == 1
        assert SpecConfig(min_draft=9).min_draft == 8


# ---------------------------------------------------------------------
# KVPool tentative (speculative-tail) accounting
# ---------------------------------------------------------------------

class TestTentativePool:
    def _pool(self, num_blocks=8):
        return KVPool(n_layers=2, n_kv_heads=2, head_dim=4, block_size=4,
                      num_blocks=num_blocks)

    def test_acquire_commit_becomes_private(self):
        p = self._pool()
        t = p.tentative_acquire(2)
        assert all(p.is_tentative(b) and p.refcount(b) == 1 for b in t)
        p.commit_tentative(t)
        assert not any(p.is_tentative(b) for b in t)
        p.release(t)
        assert p.num_free == p.usable_blocks

    def test_rollback_returns_to_free_list(self):
        p = self._pool()
        t = p.tentative_acquire(3)
        assert p.num_used == 3 and p.num_tentative == 3
        p.rollback_tentative(t)
        assert p.num_used == 0 and p.num_tentative == 0
        assert p.num_free == p.usable_blocks

    def test_publish_refuses_tentative_blocks(self):
        p = self._pool()
        t = p.tentative_acquire(1)
        tokens = np.arange(4, dtype=np.int32)
        with pytest.raises(ValueError, match="tentative"):
            p.publish(tokens, t, 4)
        # after commit the same publish succeeds
        p.commit_tentative(t)
        p.publish(tokens, t, 4)
        assert p.is_cached(t[0])

    def test_commit_unknown_block_raises(self):
        p = self._pool()
        a = p.acquire(1)
        with pytest.raises(ValueError, match="not tentative"):
            p.commit_tentative(a)
        with pytest.raises(ValueError, match="not tentative"):
            p.rollback_tentative(a)

    def test_never_partial_and_null_block_respected(self):
        p = self._pool(num_blocks=4)  # 3 usable
        assert p.tentative_acquire(5) is None
        assert p.num_tentative == 0
        got = p.tentative_acquire(3)
        assert 0 not in got


# ---------------------------------------------------------------------
# the golden contract: spec-on == spec-off == oracle
# ---------------------------------------------------------------------

@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.8, 5)])
def test_spec_on_equals_spec_off_and_oracle(params, temperature, top_k):
    """Staggered multi-request traffic through a spec-on engine matches
    a spec-off engine AND the independent one-shot oracle per request,
    token for token — greedy and sampled. Sampling is the strong half
    of the claim: candidate tokens are sampled with exactly the keys
    plain decode would consume, so acceptance preserves bits, not just
    the distribution."""
    rng = np.random.default_rng(3)
    pat = rng.integers(0, CFG.vocab_size, (5,)).astype(np.int32)
    prompts = [np.tile(pat, 3),
               rng.integers(0, CFG.vocab_size, (7,)).astype(np.int32),
               np.tile(pat, 2),
               rng.integers(0, CFG.vocab_size, (4,)).astype(np.int32)]
    keys = [jax.random.key(100 + i) for i in range(len(prompts))]
    max_new = [18, 14, 16, 12]
    arrivals = [0, 1, 3, 6]

    outs = {}
    for name, spec in (("off", None), ("on", SpecConfig())):
        eng = _engine(params, spec=spec, temperature=temperature,
                      top_k=top_k)
        outs[name] = _run_staggered(eng, prompts, max_new, keys, arrivals)
    for a, b in zip(outs["off"], outs["on"]):
        np.testing.assert_array_equal(a, b)
    for p, k, n, o in zip(prompts, keys, max_new, outs["on"]):
        np.testing.assert_array_equal(
            o, _oracle(params, p, n, k, temperature, top_k))


def test_spec_parity_under_preemption(params):
    """A pool too small for the whole working set forces preemptions
    mid-speculation; evicted requests resume bit-identically (sampled
    traffic — the checkpointed key after a verify step must equal the
    key plain decode would have evolved)."""
    rng = np.random.default_rng(11)
    shared = rng.integers(0, CFG.vocab_size, (8,)).astype(np.int32)
    prompts = [np.concatenate([
        shared, rng.integers(0, CFG.vocab_size, (t,)).astype(np.int32)])
        for t in (3, 4, 5, 6)]
    keys = [jax.random.key(40 + i) for i in range(4)]
    max_new = [14, 14, 14, 14]
    arrivals = [0, 0, 1, 2]

    outs = {}
    preempted = {}
    for name, spec in (("off", None), ("on", SpecConfig())):
        eng = _engine(params, spec=spec, num_blocks=13, max_slots=3,
                      temperature=0.7, top_k=6)
        outs[name] = _run_staggered(eng, prompts, max_new, keys, arrivals)
        preempted[name] = eng.metrics.preempted
    assert preempted["on"] > 0  # the scenario actually preempts
    for a, b in zip(outs["off"], outs["on"]):
        np.testing.assert_array_equal(a, b)


def test_spec_parity_with_prefix_cache_and_hits(params):
    """Prefix-cache-on + speculation: shared-prompt traffic still
    matches spec-off output exactly, the cache still hits (speculation
    must not poison the index — published chains carry committed
    tokens only), and tentative blocks are all resolved at drain."""
    rng = np.random.default_rng(21)
    shared = rng.integers(0, CFG.vocab_size, (12,)).astype(np.int32)
    prompts = [np.concatenate([
        shared, rng.integers(0, CFG.vocab_size, (t,)).astype(np.int32)])
        for t in (2, 3, 4)]
    keys = [jax.random.key(60 + i) for i in range(3)]
    max_new = [12, 12, 12]
    arrivals = [0, 6, 12]   # staggered so retires publish before hits

    outs = {}
    for name, spec in (("off", None), ("on", SpecConfig())):
        eng = _engine(params, spec=spec, prefix_cache=True)
        outs[name] = _run_staggered(eng, prompts, max_new, keys, arrivals)
        assert eng.metrics.prefix_hit_tokens > 0
        assert eng.pool.num_tentative == 0
    for a, b in zip(outs["off"], outs["on"]):
        np.testing.assert_array_equal(a, b)


def test_spec_parity_llama():
    from quintnet_tpu.models.llama import LlamaConfig, llama_init
    from quintnet_tpu.serve import llama_family

    cfg = LlamaConfig.tiny(n_layers=2)
    lparams = llama_init(jax.random.key(0), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 8)]
    keys = [jax.random.key(9 + i) for i in range(2)]
    outs = {}
    for name, spec in (("off", None), ("on", SpecConfig())):
        eng = ServeEngine(llama_family(cfg), lparams, max_slots=2,
                          block_size=4, num_blocks=32,
                          max_seq_len=min(48, cfg.n_positions), spec=spec)
        outs[name] = _run_staggered(eng, prompts, [24, 24], keys, [0, 1])
    for a, b in zip(outs["off"], outs["on"]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------
# speculation actually speculates (and the win is observable)
# ---------------------------------------------------------------------

def test_accepts_drafts_and_fewer_steps(rep_params):
    """On repetition-prone traffic the verify path must actually commit
    multi-token steps: accepted drafts > 0, tokens_per_decode_step > 1,
    and the spec-on engine takes FEWER engine steps than spec-off for
    bit-identical output."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG_REP.vocab_size, (12,)).astype(np.int32)
    steps = {}
    outs = {}
    for name, spec in (("off", None), ("on", SpecConfig())):
        eng = ServeEngine(gpt2_family(CFG_REP), rep_params, max_slots=2,
                          block_size=8, num_blocks=32, max_seq_len=100,
                          spec=spec)
        rid = eng.submit(prompt, 60, key=jax.random.key(1))
        eng.run(max_steps=500)
        outs[name] = eng.result(rid)
        steps[name] = eng.metrics.steps
        if name == "on":
            s = eng.metrics.summary()
            assert s["accepted_draft_tokens"] > 10
            assert s["tokens_per_decode_step"] > 1.5
            assert s["spec_steps"] > 0
            assert s["draft_acceptance_rate"] > 0.5
    np.testing.assert_array_equal(outs["off"], outs["on"])
    assert steps["on"] < steps["off"] / 2


def test_eos_mid_draft_truncates_commit(rep_params):
    """An EOS inside the accepted draft retires the request at the EOS
    — tokens past it are never committed (same semantics as plain
    decode hitting EOS)."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG_REP.vocab_size, (12,)).astype(np.int32)
    # find the dominant repeated token of the plain continuation
    eng0 = ServeEngine(gpt2_family(CFG_REP), rep_params, max_slots=1,
                       block_size=8, num_blocks=32, max_seq_len=100)
    rid0 = eng0.submit(prompt, 40, key=jax.random.key(1))
    eng0.run(max_steps=300)
    gen = eng0.result(rid0)[len(prompt):]
    eos = int(np.bincount(gen).argmax())  # appears in a long run
    outs = {}
    for name, spec in (("off", None), ("on", SpecConfig())):
        eng = ServeEngine(gpt2_family(CFG_REP), rep_params, max_slots=1,
                          block_size=8, num_blocks=32, max_seq_len=100,
                          eos_token_id=eos, spec=spec)
        rid = eng.submit(prompt, 40, key=jax.random.key(1))
        eng.run(max_steps=300)
        outs[name] = eng.result(rid)
    np.testing.assert_array_equal(outs["off"], outs["on"])
    gen_on = outs["on"][len(prompt):]
    assert eos in gen_on and int(gen_on[-1]) == eos  # stopped AT the EOS


def test_export_mid_speculation_carries_committed_only(rep_params,
                                                       params):
    """Export progress while drafts are being accepted: the payload's
    generated tokens are a prefix of the oracle output (no draft ever
    leaks), and restoring on a SPEC-OFF engine finishes the request
    token-identically — migration across heterogeneous spec configs."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG_REP.vocab_size, (12,)).astype(np.int32)
    key = jax.random.key(1)
    oracle = _oracle(rep_params, prompt, 60, key, cfg=CFG_REP)

    eng = ServeEngine(gpt2_family(CFG_REP), rep_params, max_slots=1,
                      block_size=8, num_blocks=32, max_seq_len=100,
                      spec=SpecConfig())
    eng.submit(prompt, 60, key=key)
    for _ in range(60):
        eng.step()
        if eng.metrics.accepted_draft_tokens > 0:
            break   # export while speculation is in flight
    assert eng.metrics.accepted_draft_tokens > 0  # mid-speculation
    assert eng.has_work  # and the request is not finished yet
    progress = eng.export_progress()
    assert len(progress) == 1
    got = np.asarray(progress[0].generated, np.int32)
    assert 0 < len(got) < 60
    np.testing.assert_array_equal(
        got, oracle[len(prompt):len(prompt) + len(got)])

    dest = ServeEngine(gpt2_family(CFG_REP), rep_params, max_slots=1,
                       block_size=8, num_blocks=32, max_seq_len=100)
    rid = dest.restore_progress(progress[0])
    dest.run(max_steps=300)
    np.testing.assert_array_equal(dest.result(rid), oracle)


# ---------------------------------------------------------------------
# bounded-compile invariant with verify buckets
# ---------------------------------------------------------------------

def test_compile_count_bounded_over_mixed_spec_trace(rep_params):
    """Mixed speculating/non-speculating traffic (repetition-prone AND
    novel prompts, staggered, preempting) compiles at most
    len(prefill_buckets) prefill + len(verify_buckets) verify + 1
    decode programs — the no-recompile invariant extended to the
    verify family, enforced by assert_compile_count."""
    from quintnet_tpu.obs.recorder import startup

    rng = np.random.default_rng(5)
    eng = ServeEngine(gpt2_family(CFG_REP), rep_params, max_slots=3,
                      block_size=8, num_blocks=24, max_seq_len=100,
                      spec=SpecConfig())
    eng.warmup()   # compiles every bucket up front
    stats0 = eng.compile_stats()
    assert stats0 == {"prefill": len(eng.prefill_buckets),
                      "decode": 1,
                      "verify": len(eng.spec.buckets)}
    # one full request lifecycle primes the submit-path helpers
    # (fold_in etc.) that compile once outside the sentinels
    eng.submit(np.zeros((3,), np.int32), 2)
    eng.run(max_steps=50)

    # the program's own record counts every backend compile or load
    compiled0 = startup().totals.get("programs", 0)
    assert compiled0 > 0                # (and it was listening)
    prompts = [rng.integers(0, CFG_REP.vocab_size,
                            (n,)).astype(np.int32)
               for n in (12, 7, 9, 5)]
    arrivals = [0, 2, 5, 9]
    submitted, step = 0, 0
    while submitted < len(prompts) or eng.has_work:
        while (submitted < len(prompts)
               and arrivals[submitted] <= step):
            eng.submit(prompts[submitted], 40)
            submitted += 1
        eng.step()
        step += 1
        assert step < 1000
    assert startup().totals.get("programs", 0) == compiled0
    assert eng.metrics.spec_steps > 0          # speculation happened
    assert eng.metrics.decode_steps > eng.metrics.spec_steps  # mixed
    assert eng.compile_stats() == stats0       # nothing new compiled
    eng.assert_compile_count(prefill=stats0["prefill"], decode=1,
                             verify=stats0["verify"])


def test_spec_off_engine_unchanged_surface(params):
    """A spec-off engine exposes the pre-speculation compile surface:
    no verify key in compile_stats, no verify sentinels — fleets mixing
    spec-on and spec-off replicas account each correctly."""
    eng = _engine(params)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, CFG.vocab_size, (5,)).astype(np.int32), 4)
    eng.run(max_steps=50)
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    assert "decode" in eng.compile_sentinels()
    assert not any(k.startswith("verify[")
                   for k in eng.compile_sentinels())
    eng.assert_compile_count()  # verify default: nothing to check
