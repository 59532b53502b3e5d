"""Prefix-cached, bucketed prefill goldens (quintnet_tpu/serve/).

THE contract: with prefix caching enabled, every request's token stream
is BIT-IDENTICAL to cache-off — which is itself golden against
independent ``gpt2_generate`` calls — for greedy and fixed-seed
sampling, across staggered shared-prefix traffic, preemption-resume,
and cross-engine migration. Plus the sharing-core invariants: refcount
acquire/release, copy-on-write on partial-block reuse, LRU eviction
ordering vs the LIFO free list, double-release rejection, and the
adversarial guarantee that an evicted cached block is never reachable
from any live block table.
"""

import jax
import numpy as np
import pytest

from quintnet_tpu.analysis.recompile import RecompileError
from quintnet_tpu.analysis.specs import prefill_buckets
from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.models.gpt2_generate import gpt2_generate
from quintnet_tpu.serve import KVPool, ServeEngine, generate, gpt2_family

CFG = GPT2Config.tiny(n_layer=2)


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _engine(params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("max_seq_len", 40)
    return ServeEngine(gpt2_family(CFG), params, **kw)


def _oracle(params, prompt, max_new, key, temperature=0.0, top_k=0):
    return gpt2_generate(params, prompt[None], CFG, max_new_tokens=max_new,
                         temperature=temperature, top_k=top_k, key=key)[0]


# ---------------------------------------------------------------------
# pool sharing core
# ---------------------------------------------------------------------

class TestSharingCore:
    def _pool(self, num_blocks=8, block_size=4):
        return KVPool(n_layers=1, n_kv_heads=1, head_dim=2,
                      block_size=block_size, num_blocks=num_blocks)

    def _toks(self, n, seed=0):
        rng = np.random.default_rng(seed)
        return rng.integers(0, 100, (n,)).astype(np.int32)

    def test_refcount_acquire_release_invariants(self):
        p = self._pool()
        toks = self._toks(8)
        a = p.acquire(2)
        assert [p.refcount(b) for b in a] == [1, 1]
        p.publish(toks, a, 8)
        # a second holder pins the published chain
        p.acquire_cached(a)
        assert [p.refcount(b) for b in a] == [2, 2]
        p.release(a)
        # still referenced: neither free nor cached-retained
        assert p.num_used == 2 and p.num_cached == 0
        p.release(a)
        # refcount zero + published -> retained as cache, NOT freed
        assert p.num_used == 0 and p.num_cached == 2
        assert p.num_free == p.usable_blocks - 2

    def test_double_release_rejected_o1(self):
        p = self._pool()
        a = p.acquire(1)
        p.release(a)
        with pytest.raises(ValueError, match="double free"):
            p.release(a)
        # duplicate ids inside ONE call cannot over-decrement either
        b = p.acquire(1)
        with pytest.raises(ValueError, match="double free"):
            p.release(b + b)
        # membership set (not an O(n) list scan) backs the check
        assert p._free_set == set(p._free)

    def test_release_unpublished_goes_to_free_list(self):
        p = self._pool()
        a = p.acquire(3)
        p.release(a)
        assert p.num_cached == 0 and p.num_free == p.usable_blocks

    def test_acquire_cached_requires_known_block(self):
        p = self._pool()
        with pytest.raises(ValueError, match="neither referenced"):
            p.acquire_cached([3])

    def test_lifo_free_list_preferred_over_cached_eviction(self):
        """Allocation drains the LIFO free list before touching the
        cached retention set; cached blocks are evicted only when the
        free list is dry, in LRU order."""
        p = self._pool(num_blocks=8)   # 7 usable
        toks = self._toks(8, seed=1)
        cached = p.acquire(2)
        p.publish(toks, cached, 8)
        p.release(cached)              # 2 cached, 5 free
        assert (p.num_free, p.num_cached) == (5, 2)
        got = p.acquire(5)
        # free list served first: the cached pair untouched
        assert set(got).isdisjoint(cached)
        assert p.num_cached == 2 and p.num_free == 0
        # now eviction must kick in
        assert p.acquire(1) is not None
        assert p.num_cached == 1 and p.cache_evictions == 1

    def test_lru_eviction_order_is_least_recently_touched(self):
        p = self._pool(num_blocks=8)
        t1, t2 = self._toks(4, seed=2), self._toks(4, seed=3)
        c1 = p.acquire(1)
        p.publish(t1, c1, 4)
        p.release(c1)
        c2 = p.acquire(1)
        p.publish(t2, c2, 4)
        p.release(c2)
        # touch the OLDER chain via a lookup hit + pin/unpin
        plan = p.lookup(np.concatenate([t1, t1[:1]]))
        assert plan.shared_blocks == c1
        p.acquire_cached(c1)
        p.release(c1)
        p.acquire(p.num_free)          # dry the free list
        evicted = p.acquire(1)         # forces one eviction
        assert evicted == c2           # c1 was touched later -> survives
        assert p.lookup(np.concatenate([t2, t2[:1]])).shared_blocks == []

    def test_publish_duplicate_key_keeps_incumbent(self):
        p = self._pool()
        toks = self._toks(4, seed=4)
        a = p.acquire(1)
        p.publish(toks, a, 4)
        b = p.acquire(1)
        p.publish(toks, b, 4)          # identical content, later
        p.release(a)
        p.release(b)
        # incumbent cached; duplicate went back to the free list
        assert p.lookup(np.concatenate([toks, toks[:1]])
                        ).shared_blocks == a
        assert p.num_cached == 1

    def test_lookup_caps_at_len_minus_one(self):
        """A fully-cached prompt still prefills >= 1 token (the logits
        source): plan_admission never returns start == len(tokens)."""
        p = self._pool()
        toks = self._toks(8, seed=5)
        a = p.acquire(2)
        p.publish(toks, a, 8)
        p.release(a)
        plan = p.plan_admission(toks, 9)
        assert plan.cached_tokens == 4        # capped to the first block
        assert plan.shared_blocks == a[:1]
        assert plan.n_new_blocks == 3 - 1

    def test_admission_budget_counts_only_uncached_blocks(self):
        p = self._pool(num_blocks=5)   # 4 usable
        toks = self._toks(8, seed=6)
        a = p.acquire(2)
        p.publish(toks, a, 7)          # 1 full block + partial leaf (3)
        p.release(a)                   # 2 cached, 2 free
        # cache-cold: the full 3 blocks count against the budget
        cold = p.plan_admission(self._toks(8, seed=7), 9)
        assert cold.n_new_blocks == 3
        assert p.can_admit(cold)
        # cache hit: 4 full + 3 COW slots resident, only 2 new blocks
        # needed; the pinned chain is excluded from the evictable count
        hot = p.plan_admission(toks, 9)
        assert hot.cached_tokens == 7
        assert hot.shared_blocks == a[:1]
        assert (hot.cow_src, hot.cow_len) == (a[1], 3)
        assert hot.n_new_blocks == 2
        assert p.can_admit(hot)

    def test_plan_degrades_instead_of_wedging_at_capacity_edge(self):
        """A maximal-chain plan can need more simultaneous blocks than
        the pool holds (pinned chain + transient COW pin + new blocks)
        even on an otherwise idle pool — the plan must degrade (drop
        the COW hit, then the chain) rather than report an
        inadmissible plan forever and head-of-line-block the queue."""
        p = self._pool(num_blocks=6)       # 5 usable
        toks = self._toks(19, seed=9)
        a = p.acquire(3)
        p.publish(toks, a, 11)             # 2 full blocks + leaf (3)
        p.release(a)                       # 3 cached, 2 free
        # request sharing the 11-token prefix, table must cover 19
        # slots = 5 blocks: the maximal plan (2 shared + 3 new + COW
        # pin) needs 6 distinct blocks > 5 usable
        plan = p.plan_admission(toks, 19)
        assert p.can_admit(plan)           # degraded, not wedged
        assert plan.cow_src is None        # the COW hit was dropped
        assert plan.cached_tokens == 8     # full-block chain kept
        assert plan.n_new_blocks == 3
        # and an engine at that exact edge still serves the request
        params = gpt2_init(jax.random.key(0), CFG)
        eng = _engine(params, max_slots=1, block_size=4, num_blocks=6,
                      max_seq_len=20)
        prompt = np.asarray(
            np.random.default_rng(9).integers(0, CFG.vocab_size, (11,)),
            np.int32)
        r1 = eng.submit(prompt, 4, key=jax.random.key(1))
        eng.run(max_steps=50)
        r2 = eng.submit(np.concatenate(
            [eng.result(r1)[:11], prompt[:4]]), 4, key=jax.random.key(2))
        eng.run(max_steps=50)
        assert eng.request(r2).state == "finished"
        np.testing.assert_array_equal(
            eng.result(r2),
            _oracle(params, np.asarray(eng.request(r2).prompt), 4,
                    jax.random.key(2)))

    def test_prefix_cache_off_is_inert(self):
        p = KVPool(n_layers=1, n_kv_heads=1, head_dim=2, block_size=4,
                   num_blocks=8, prefix_cache=False)
        toks = self._toks(8, seed=8)
        a = p.acquire(2)
        p.publish(toks, a, 8)          # no-op
        p.release(a)
        assert p.num_cached == 0 and p.num_free == p.usable_blocks
        assert p.lookup(toks).cached_tokens == 0


# ---------------------------------------------------------------------
# copy-on-write
# ---------------------------------------------------------------------

def test_cow_on_partial_block_divergence(params):
    """Request B extends A's published chain INTO a partially-filled
    cached block and then diverges: B must copy the filled slots into
    a private block (counted as hit tokens), write its own
    continuation there, and leave the cached block's content and index
    entry untouched — while B's output stays golden."""
    rng = np.random.default_rng(3)
    eng = _engine(params, block_size=4)
    pa = np.asarray(rng.integers(0, CFG.vocab_size, (10,)), np.int32)
    ra = eng.submit(pa, 4, key=jax.random.key(1))
    eng.run()
    oa = eng.result(ra)                 # published chain covers 13 toks
    pool = eng.pool
    leaf_key = pool._key(np.asarray(oa[:13], np.int32), 13)
    leaf = pool._index[leaf_key]
    assert pool._block_fill[leaf] == 1  # partially filled (13 % 4)
    k_before = np.asarray(pool.k[:, leaf * 4:(leaf + 1) * 4]).copy()

    # B: A's 13 published tokens + a diverging continuation
    pb = np.concatenate(
        [oa[:13], np.asarray(rng.integers(0, CFG.vocab_size, (5,)),
                             np.int32)])
    rb = eng.submit(pb, 4, key=jax.random.key(2))
    eng.run()
    np.testing.assert_array_equal(
        eng.result(rb), _oracle(params, pb, 4, jax.random.key(2)))
    assert eng.metrics.prefix_hit_tokens == 13   # 12 full + 1 COW slot
    # the cached leaf is untouched and still indexed
    k_after = np.asarray(pool.k[:, leaf * 4:(leaf + 1) * 4])
    np.testing.assert_array_equal(k_before[:, :1], k_after[:, :1])
    assert pool._index[leaf_key] == leaf
    # B's table never referenced the cached leaf (it wrote a copy)
    assert pool.refcount(leaf) == 0


# ---------------------------------------------------------------------
# adversarial eviction
# ---------------------------------------------------------------------

def test_evicted_block_never_reachable_from_live_tables(params):
    """Memory pressure evicts cached blocks while other requests run:
    at every step, every evicted block id must be absent from every
    ACTIVE slot's block table (eviction only ever takes refcount-zero
    blocks)."""
    rng = np.random.default_rng(4)
    eng = _engine(params, max_slots=3, block_size=2, num_blocks=12,
                  max_seq_len=16)

    def live_blocks():
        return {b for s in eng._active_slots()
                for b in eng._slot_blocks[s]}

    # instrument the eviction point: AT THE MOMENT a cached block is
    # evicted it must be unreferenced, absent from every live table,
    # and gone from the index (an evicted block may be legally handed
    # out again afterwards — that is the allocator working)
    orig_evict = eng.pool._evict_lru
    evictions = []

    def checked_evict():
        b = orig_evict()
        assert eng.pool.refcount(b) == 0
        assert b not in live_blocks()
        assert b not in eng.pool._block_key
        assert all(v != b for v in eng.pool._index.values())
        evictions.append(b)
        return b

    eng.pool._evict_lru = checked_evict
    rids = []
    for i in range(8):
        p = np.asarray(rng.integers(0, CFG.vocab_size, (5,)), np.int32)
        rids.append(eng.submit(p, 6, key=jax.random.key(600 + i)))
    while eng.has_work:
        eng.step()
        live = live_blocks()
        # step-end consistency: live tables never overlap the free
        # list or the cached retention set, and hold real references
        assert live.isdisjoint(eng.pool._free_set)
        assert live.isdisjoint(eng.pool._cached_free)
        assert all(eng.pool.refcount(b) >= 1 for b in live)
    assert len(evictions) > 0            # pressure actually evicted
    for r in rids:
        assert eng.request(r).state == "finished"


# ---------------------------------------------------------------------
# golden parity: cache-on == cache-off == oracle
# ---------------------------------------------------------------------

def _shared_prefix_prompts(rng, n, prefix_len=18, tails=(3, 4, 5, 6)):
    shared = np.asarray(rng.integers(0, CFG.vocab_size, (prefix_len,)),
                        np.int32)
    out = []
    for i in range(n):
        t = tails[i % len(tails)]
        tail = np.asarray(rng.integers(0, CFG.vocab_size, (t,)), np.int32)
        out.append(np.concatenate([shared, tail]))
    return out


def _staggered(eng, prompts, max_new, keys, arrivals):
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


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.9, 7)])
def test_cache_on_equals_cache_off_and_oracle(params, temperature, top_k):
    """Staggered shared-prefix trace, greedy AND sampled: the cache-on
    engine's streams equal the cache-off engine's AND the independent
    oracle's, token for token — with a nonzero hit rate proving the
    cache actually served tokens."""
    rng = np.random.default_rng(11)
    prompts = _shared_prefix_prompts(rng, 6)
    keys = [jax.random.key(800 + i) for i in range(6)]
    max_new = [8, 6, 9, 5, 7, 8]
    arrivals = [0, 0, 4, 9, 14, 19]   # late arrivals see a warm cache

    on = _engine(params, temperature=temperature, top_k=top_k)
    outs_on = _staggered(on, prompts, max_new, keys, arrivals)
    off = _engine(params, temperature=temperature, top_k=top_k,
                  prefix_cache=False)
    outs_off = _staggered(off, prompts, max_new, keys, arrivals)

    assert on.metrics.prefix_hit_tokens > 0
    assert off.metrics.prefix_hit_tokens == 0
    for p, m, k, o_on, o_off in zip(prompts, max_new, keys, outs_on,
                                    outs_off):
        np.testing.assert_array_equal(o_on, o_off)
        np.testing.assert_array_equal(
            o_on, _oracle(params, p, m, k, temperature=temperature,
                          top_k=top_k))


def test_preempt_resume_parity_and_nearly_free_resume(params):
    """Preemption under pool pressure with caching on: outputs stay
    golden, and when a preempted request resumes while its published
    chain is still resident the re-prefill is a prefix hit."""
    rng = np.random.default_rng(12)
    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (6,)), np.int32)
               for _ in range(3)]
    keys = [jax.random.key(900 + i) for i in range(3)]
    eng = _engine(params, max_slots=3, block_size=2, num_blocks=16,
                  max_seq_len=16, temperature=0.8, top_k=5)
    outs = generate(eng, prompts, max_new_tokens=8, keys=keys)
    assert eng.metrics.preempted >= 1
    for p, k, o in zip(prompts, keys, outs):
        np.testing.assert_array_equal(
            o, _oracle(params, p, 8, k, temperature=0.8, top_k=5))
    assert eng.pool.num_used == 0


def test_migration_onto_warm_engine_is_a_cache_hit(params):
    """The fleet's kill-migration path with caching: progress exported
    from engine A mid-flight restores on engine B which has ALREADY
    served the same prompt — B's resume prefill hits its prefix cache
    and the continuation stays token-identical (sampling on)."""
    rng = np.random.default_rng(13)
    prompt = np.asarray(rng.integers(0, CFG.vocab_size, (9,)), np.int32)
    key = jax.random.key(77)
    a = _engine(params, temperature=0.9, top_k=7)
    rid = a.submit(prompt, 10, key=key)
    for _ in range(4):
        a.step()
    progs = a.export_progress()
    assert len(progs) == 1 and len(progs[0].generated) >= 1

    b = _engine(params, temperature=0.9, top_k=7)
    # B has served the identical prompt before (a different sampling
    # key, so only the PROMPT prefix is shared)
    b.submit(prompt, 4, key=jax.random.key(78))
    b.run()
    b.metrics = type(b.metrics)(clock=b.clock)
    new_rid = b.restore_progress(progs[0])
    b.run()
    assert b.metrics.prefix_hit_tokens > 0   # resume rode the cache
    np.testing.assert_array_equal(
        b.result(new_rid),
        _oracle(params, prompt, 10, key, temperature=0.9, top_k=7))
    del rid


# ---------------------------------------------------------------------
# bucketed prefill + the bounded-compile invariant
# ---------------------------------------------------------------------

def test_bucket_ladder_pinned_in_specs():
    assert prefill_buckets(40) == (16, 32, 40)
    assert prefill_buckets(16) == (16,)
    assert prefill_buckets(12) == (12,)
    assert prefill_buckets(100) == (16, 32, 64, 100)


def test_bucket_choice_does_not_change_tokens(params):
    """The same request served through different buckets (alone: big
    tail -> big bucket; after a cache warm-up: small tail -> small
    bucket) produces the identical stream — bucket width is pure
    padding."""
    rng = np.random.default_rng(14)
    prompt = np.asarray(rng.integers(0, CFG.vocab_size, (20,)), np.int32)
    key = jax.random.key(500)
    eng = _engine(params, temperature=0.7, top_k=9)
    assert len(eng.prefill_buckets) >= 2
    r1 = eng.submit(prompt, 6, key=key)   # cold: tail 20 -> bucket 32
    eng.run()
    r2 = eng.submit(prompt, 6, key=key)   # warm: tiny tail -> bucket 16
    eng.run()
    np.testing.assert_array_equal(eng.result(r1), eng.result(r2))
    assert eng.metrics.prefix_hit_tokens > 0
    assert eng.compile_stats()["prefill"] == 2  # two buckets exercised


def test_compile_count_bounded_by_buckets_over_mixed_trace(params, rng):
    """A mixed preempting + shared-prefix trace compiles at most
    n_buckets prefill programs and exactly one decode program —
    asserted via assert_compile_count AND a jax.monitoring listener
    observing zero backend compiles after every bucket is warm."""
    from quintnet_tpu.obs.recorder import startup

    eng = _engine(params, max_slots=3, block_size=2, num_blocks=16,
                  max_seq_len=16)
    assert eng.prefill_buckets == (16,)  # short prefill_len: one bucket
    del eng

    eng = _engine(params)                # prefill_len 40 -> 3 buckets
    shared = _shared_prefix_prompts(rng, 4)
    # warm every bucket: prompts sized into each bucket
    for n in (5, 20, 33):
        eng.submit(np.asarray(rng.integers(0, CFG.vocab_size, (n,)),
                              np.int32), 2)
        eng.run()
    n_buckets = len(eng.prefill_buckets)
    assert eng.compile_stats() == {"prefill": n_buckets, "decode": 1}

    # the program's own record counts every backend compile or load
    compiled0 = startup().totals.get("programs", 0)
    assert compiled0 > 0                # (and it was listening)
    for i, p in enumerate(shared):
        eng.submit(p, 5, key=jax.random.key(i))
    eng.run()
    assert startup().totals.get("programs", 0) == compiled0
    eng.assert_compile_count(prefill=n_buckets, decode=1)
    with pytest.raises(RecompileError, match="expected 1 compiled"):
        eng.assert_compile_count(prefill=1, decode=1)


def test_validation_rejects_uncovering_buckets(params):
    with pytest.raises(ValueError, match="does not cover"):
        _engine(params, prefill_bucket_sizes=(8, 16))  # prefill_len 40
