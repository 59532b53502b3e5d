"""Tiered KV cache goldens (quintnet_tpu/serve/kv_tier.py + the tier
hooks in kv_pool.py / engine.py / fleet/proc.py).

THE contract: spilling the prefix cache to host RAM changes WHAT IS
WARM, never WHAT IS COMPUTED — demote→promote round-trips are
byte-exact (pool bytes AND quantization scales), a tiered engine's
token streams are bit-identical to the tier-off engine and to the
independent ``gpt2_generate`` oracle (greedy and fixed-seed sampling,
f32 and int8), promotion is asynchronous (other slots emit tokens
every step while the queue head is PROMOTING), the host tier is
byte-budgeted with its own LRU, demotion never blocks a decode step,
namespaced (adapter) chains stay isolated across BOTH tiers, and the
fleet's peer lookup ships a warm chain replica→replica instead of
re-prefilling. Plus the satellite invariants: the lazy-deletion
eviction heap agrees with the exhaustive ``min()`` oracle, and
``import_chain`` admits the longest block-aligned prefix that fits
instead of all-or-nothing.
"""

import os

import jax
import numpy as np
import pytest

from quintnet_tpu.fleet import ProcessFleet
from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.models.gpt2_generate import gpt2_generate
from quintnet_tpu.serve import KVPool, ServeEngine, gpt2_family
from quintnet_tpu.serve.kv_tier import HostTier, record_nbytes

CFG = GPT2Config.tiny(n_layer=2)
FACTORY_FILE = os.path.join(os.path.dirname(__file__),
                            "_proc_factories.py")


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _engine(params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 10)
    kw.setdefault("max_seq_len", 40)
    return ServeEngine(gpt2_family(CFG), params, **kw)


def _oracle(params, prompt, max_new, key=None, temperature=0.0,
            top_k=0):
    return np.asarray(gpt2_generate(
        params, np.asarray(prompt, np.int32)[None], CFG,
        max_new_tokens=max_new, temperature=temperature, top_k=top_k,
        key=key)[0])


def _run_one(eng, prompt, max_new, key=None):
    rid = eng.submit(np.asarray(prompt, np.int32), max_new, key=key)
    while eng.has_work:
        eng.step()
    return np.asarray(eng.result(rid))


# ---------------------------------------------------------------------
# HostTier unit: the byte-budgeted LRU store
# ---------------------------------------------------------------------

def _rec(nbytes, fill=4, seed=0):
    """A synthetic record whose k+v payload is exactly ``nbytes``."""
    rng = np.random.default_rng(seed)
    half = nbytes // 2
    return {"fill": fill,
            "k": rng.integers(0, 100, (half,)).astype(np.uint8),
            "v": rng.integers(0, 100, (nbytes - half,)
                              ).astype(np.uint8)}


class TestHostTier:
    def test_budget_must_be_positive(self):
        with pytest.raises(ValueError, match="byte_budget"):
            HostTier(byte_budget=0)
        with pytest.raises(ValueError, match="byte_budget"):
            HostTier(byte_budget=-1)

    def test_put_get_and_lru_eviction_under_pressure(self):
        t = HostTier(byte_budget=300)
        assert t.put(b"a", _rec(100, seed=1))
        assert t.put(b"b", _rec(100, seed=2))
        assert t.put(b"c", _rec(100, seed=3))
        assert t.bytes_used == 300 and len(t) == 3
        # touch "a" so "b" becomes the LRU victim
        assert t.get(b"a") is not None
        assert t.put(b"d", _rec(100, seed=4))
        assert t.bytes_used <= t.byte_budget
        assert t.contains(b"a") and not t.contains(b"b")
        assert t.evictions == 1 and t.demotions == 4

    def test_contains_does_not_touch_lru(self):
        t = HostTier(byte_budget=200)
        t.put(b"a", _rec(100, seed=1))
        t.put(b"b", _rec(100, seed=2))
        assert t.contains(b"a")      # a probe, not a use
        t.put(b"c", _rec(100, seed=3))
        assert not t.contains(b"a")  # "a" was still the LRU victim

    def test_oversized_record_refused_not_wedged(self):
        t = HostTier(byte_budget=100)
        assert not t.put(b"big", _rec(200))
        assert len(t) == 0 and t.bytes_used == 0
        assert t.put(b"ok", _rec(80))

    def test_same_key_overwrite_replaces_bytes(self):
        t = HostTier(byte_budget=300)
        t.put(b"a", _rec(100, seed=1))
        t.put(b"a", _rec(200, seed=2))
        assert len(t) == 1 and t.bytes_used == 200
        assert t.evictions == 0      # replacement, not pressure

    def test_summary_is_plain_scalars(self):
        t = HostTier(byte_budget=100)
        t.put(b"a", _rec(60))
        s = t.summary()
        assert s["bytes_used"] == 60
        assert s["records"] == 1 and s["demotions"] == 1
        assert all(isinstance(v, int) for v in s.values())


# ---------------------------------------------------------------------
# pool layer: demotion on eviction + byte-exact promotion round-trip
# ---------------------------------------------------------------------

def _tier_pool(num_blocks=4, block_size=4, policy=None,
               byte_budget=1 << 20):
    return KVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                  block_size=block_size, num_blocks=num_blocks,
                  policy=policy,
                  host_tier=HostTier(byte_budget=byte_budget))


def _publish_chain(pool, toks, seed=0):
    """Publish a chain with distinct per-block payloads (and, on a
    scaled policy, distinct per-block scales)."""
    rng = np.random.default_rng(seed)
    blocks = pool.acquire(pool.blocks_for(len(toks)))
    bs = pool.block_size
    ks, vs = pool.k_scale, pool.v_scale
    shape = (pool.n_layers, bs, pool.n_kv_heads, pool.head_dim)
    for b in blocks:
        # records go in through the pool's edge, in the shape they
        # have on the wire
        k, v = pool.write_slots(np.arange(b * bs, (b + 1) * bs),
                                rng.integers(-50, 50, shape),
                                rng.integers(-50, 50, shape))
        if pool.policy.scaled:
            sshape = (pool.n_layers, pool.n_kv_heads)
            ks = ks.at[:, b].set(rng.uniform(0.5, 2.0, sshape)
                                 .astype(np.float32))
            vs = vs.at[:, b].set(rng.uniform(0.5, 2.0, sshape)
                                 .astype(np.float32))
        pool.update(k, v, *(() if not pool.policy.scaled else (ks, vs)))
    pool.publish(toks, blocks, len(toks))
    pool.release(blocks)
    return blocks


def _force_evict_all_cached(pool):
    """Drain the free list, then evict every cached block (demoting
    each to the host tier); the acquired blocks are released back."""
    n = pool.num_free + pool.num_cached
    held = pool.acquire(n)
    assert held is not None
    pool.release(held)


class TestPoolTier:
    @pytest.mark.parametrize("policy", [None, "int8"])
    def test_demote_promote_round_trip_byte_exact(self, policy):
        toks = np.arange(8, dtype=np.int32)
        p = _tier_pool(policy=policy)
        _publish_chain(p, toks, seed=3)
        before = p.export_chain(toks)
        assert before["n_tokens"] == 8

        _force_evict_all_cached(p)
        tier = p.host_tier
        assert tier.demotions == 2 and len(tier) == 2
        assert p.lookup(toks, max_tokens=8).shared_blocks == []
        # snapshot the demoted records to check re-demotion later
        first = {k: {f: np.array(a) for f, a in r.items()
                     if f != "fill"}
                 for k, r in tier._records.items()}

        covered, keys = p.plan_promotion(toks)
        assert covered == 8 and len(keys) == 2
        assert p.promote_chain(keys) == (2, 2)
        assert tier.promotions == 2 and tier.promoted_tokens == 8
        # promoted chain is an ordinary device hit again, byte-exact
        assert p.lookup(toks, max_tokens=8).shared_blocks != []
        after = p.export_chain(toks)
        assert after["n_tokens"] == 8
        for a, b in zip(before["blocks"], after["blocks"]):
            assert a["fill"] == b["fill"]
            for f in a:
                if f == "fill":
                    continue
                assert np.asarray(a[f]).dtype == np.asarray(b[f]).dtype
                np.testing.assert_array_equal(a[f], b[f])

        # re-demote: the overwritten host records are byte-identical
        # to the first demotion's (demote -> promote -> demote is a
        # fixed point)
        _force_evict_all_cached(p)
        for key, snap in first.items():
            rec = tier._records[key]
            for f, arr in snap.items():
                np.testing.assert_array_equal(rec[f], arr)

    def test_plan_promotion_three_outcomes(self):
        toks = np.arange(8, dtype=np.int32)
        p = _tier_pool(num_blocks=8)
        # miss in both tiers
        assert p.plan_promotion(toks) == (0, [])
        _publish_chain(p, toks)
        # pure device hit: covered, nothing to promote
        covered, keys = p.plan_promotion(toks)
        assert covered == 8 and keys == []
        # host hit after demotion
        _force_evict_all_cached(p)
        covered, keys = p.plan_promotion(toks)
        assert covered == 8 and len(keys) == 2
        # tier-off pool reports no third outcome
        off = KVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        assert off.plan_promotion(toks) == (0, [])

    def test_promote_respects_block_budget(self):
        toks = np.arange(16, dtype=np.int32)
        p = _tier_pool(num_blocks=6)
        _publish_chain(p, toks)
        _force_evict_all_cached(p)
        _, keys = p.plan_promotion(toks)
        assert len(keys) == 4
        taken, blocks = p.promote_chain(keys, max_blocks=1)
        assert (taken, blocks) == (1, 1)
        # the promoted key is now device-resident: the next feed
        # consumes it for free and promotes the next budget's worth
        taken, blocks = p.promote_chain(keys, max_blocks=2)
        assert (taken, blocks) == (3, 2)
        taken, blocks = p.promote_chain(keys[3:], max_blocks=4)
        assert (taken, blocks) == (1, 1)
        assert p.plan_promotion(toks)[1] == []

    def test_vanished_host_record_truncates_chain(self):
        """A record budget-evicted mid-promotion is terminal for the
        chain: later keys are unreachable past the gap by any device
        walk, so they are consumed unpromoted (admission re-prefills
        from the gap) instead of imported as orphans."""
        toks = np.arange(12, dtype=np.int32)
        p = _tier_pool(num_blocks=6)
        _publish_chain(p, toks)
        _force_evict_all_cached(p)
        _, keys = p.plan_promotion(toks)
        assert len(keys) == 3
        del p.host_tier._records[keys[1]]
        p.host_tier.bytes_used = sum(
            record_nbytes(r) for r in p.host_tier._records.values())
        taken, blocks = p.promote_chain(keys)
        assert taken == 3 and blocks == 1      # only keys[0] landed
        covered, rest = p.plan_promotion(toks)
        assert covered == 4 and rest == []

    def test_namespaced_chains_isolated_across_tiers(self):
        toks = np.arange(8, dtype=np.int32)
        p = _tier_pool(num_blocks=4)
        blocks = p.acquire(2)
        p.publish(toks, blocks, 8, namespace="tenant-a")
        p.release(blocks)
        _force_evict_all_cached(p)
        assert len(p.host_tier) == 2
        # the other namespace (and the namespace-less default) miss
        assert p.plan_promotion(toks, namespace="tenant-b") == (0, [])
        assert p.plan_promotion(toks) == (0, [])
        covered, keys = p.plan_promotion(toks, namespace="tenant-a")
        assert covered == 8 and len(keys) == 2
        p.promote_chain(keys)
        assert p.lookup(toks, max_tokens=8,
                        namespace="tenant-b").shared_blocks == []
        assert p.lookup(toks, max_tokens=8,
                        namespace="tenant-a").shared_blocks != []

    def test_peek_counts_device_plus_host_extension(self):
        toks = np.arange(16, dtype=np.int32)
        p = _tier_pool(num_blocks=6)
        _publish_chain(p, toks)
        assert p.peek_chain_tokens(toks) == 16
        _force_evict_all_cached(p)
        assert p.peek_chain_tokens(toks) == 16       # host-resident
        _, keys = p.plan_promotion(toks)
        p.promote_chain(keys, max_blocks=2)
        assert p.peek_chain_tokens(toks) == 16       # 2 dev + 2 host
        assert p.peek_chain_tokens(toks[:8]) == 8
        assert p.peek_chain_tokens(
            np.arange(100, 108, dtype=np.int32)) == 0


# ---------------------------------------------------------------------
# satellite: partial import_chain (longest block-aligned prefix)
# ---------------------------------------------------------------------

class TestPartialImport:
    def _chain(self, n_tokens):
        src = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        toks = np.arange(n_tokens, dtype=np.int32)
        blocks = src.acquire(src.blocks_for(n_tokens))
        k = src.k
        for i, b in enumerate(blocks):
            k = k.at[:, b * 4:(b + 1) * 4].set(i + 1)
        src.update(k, src.v)
        src.publish(toks, blocks, n_tokens)
        src.release(blocks)
        return toks, src.export_chain(toks)

    def test_imports_longest_prefix_that_fits(self):
        toks, chain = self._chain(12)                # 3 full blocks
        dst = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=4)     # 3 usable
        held = dst.acquire(1)                        # only 2 left
        assert dst.import_chain(chain) == 8
        plan = dst.lookup(toks, max_tokens=12)
        assert len(plan.shared_blocks) == 2
        # the imported prefix carries the right bytes
        back = dst.export_chain(toks[:8])
        for i, rec in enumerate(back["blocks"]):
            np.testing.assert_array_equal(
                rec["k"], np.full_like(rec["k"], i + 1))
        dst.release(held)

    def test_zero_fit_still_returns_zero(self):
        toks, chain = self._chain(8)
        dst = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=4)
        held = dst.acquire(3)                        # nothing left
        assert dst.import_chain(chain) == 0
        dst.release(held)

    def test_full_fit_unchanged(self):
        toks, chain = self._chain(12)
        dst = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        assert dst.import_chain(chain) == 12


# ---------------------------------------------------------------------
# satellite: lazy-deletion eviction heap == exhaustive min() oracle
# ---------------------------------------------------------------------

class TestEvictionHeap:
    @pytest.mark.parametrize("tiered", [False, True])
    def test_eviction_order_matches_min_oracle(self, tiered):
        """Random publish/touch traffic, then drain: every forced
        eviction must pick exactly the block the exhaustive
        ``min(_cached_free, key=_lru.get)`` oracle picks — including
        after enough stale heap entries to trigger compaction."""
        p = KVPool(n_layers=1, n_kv_heads=1, head_dim=2,
                   block_size=2, num_blocks=10,
                   host_tier=(HostTier(byte_budget=1 << 20)
                              if tiered else None))
        rng = np.random.default_rng(7)
        next_tok = [0]

        def publish_one():
            blocks = p.acquire(1)
            if blocks is None:
                return
            toks = np.arange(next_tok[0], next_tok[0] + 2,
                             dtype=np.int32)
            next_tok[0] += 2
            p.publish(toks, blocks, 2)
            p.release(blocks)

        for _ in range(4):
            while p.num_free:
                publish_one()
            # touch randomly, enough to force at least one heap
            # compaction (threshold 8 * num_blocks + 64)
            for _ in range(200):
                cached = sorted(p._cached_free)
                b = cached[rng.integers(len(cached))]
                p.acquire_cached([b])
                p.release([b])
            held = []
            while p._cached_free:
                expect = min(p._cached_free, key=p._lru.__getitem__)
                got = p.acquire(1)
                assert got == [expect]
                held.extend(got)
            p.release(held)

    def test_stale_heap_entries_never_evict_a_live_block(self):
        """A block touched after entering the retention set leaves
        stale (stamp, block) pairs in the heap; popping one must not
        evict the block out of LRU order."""
        p = KVPool(n_layers=1, n_kv_heads=1, head_dim=2,
                   block_size=2, num_blocks=4)   # 3 usable
        t1, t2 = (np.arange(2, dtype=np.int32),
                  np.arange(10, 12, dtype=np.int32))
        a = p.acquire(1)
        p.publish(t1, a, 2)
        p.release(a)
        b = p.acquire(1)
        p.publish(t2, b, 2)
        p.release(b)
        # touch the OLDER chain repeatedly: heap now holds many stale
        # entries for ``a`` below ``b``'s stamp
        for _ in range(5):
            p.acquire_cached(a)
            p.release(a)
        p.acquire(p.num_free)
        assert p.acquire(1) == b     # b is LRU despite a's stale spam
        assert p.acquire(1) == a


# ---------------------------------------------------------------------
# engine layer: parity goldens + async promotion
# ---------------------------------------------------------------------

class TestEngineTier:
    def _workload(self, rng, n=4, prefix_len=12, total_len=16):
        base = np.asarray(rng.integers(0, CFG.vocab_size, (prefix_len,)),
                          np.int32)
        prompts = []
        for _ in range(n):
            tail = np.asarray(
                rng.integers(0, CFG.vocab_size, (total_len - prefix_len,)),
                np.int32)
            prompts.append(np.concatenate([base, tail]))
        return prompts

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    @pytest.mark.parametrize("temp,topk", [(0.0, 0), (0.8, 5)])
    def test_tiered_on_equals_off_equals_oracle(self, params, rng,
                                                kv_dtype, temp, topk):
        """The acceptance golden: with the pool small enough that
        every admission evicts (and so demotes) the previous chain,
        resubmitted prompts host-hit and promote — and every token
        stream is bit-identical to the tier-off engine AND the
        independent oracle, greedy and fixed-seed sampled, f32 and
        int8."""
        kw = dict(num_blocks=10, kv_dtype=kv_dtype,
                  temperature=temp, top_k=topk)
        on = _engine(params, kv_tier_bytes=1 << 20, **kw)
        off = _engine(params, **kw)
        # total_len=20 puts a chain-SPECIFIC block boundary (@16)
        # inside the admission walk's len-1 cap — the boundary the
        # LRU evicts first and a resubmission must promote back
        prompts = self._workload(rng, total_len=20)
        # distinct chains + resubmissions of evicted ones
        seq = prompts + [prompts[0], prompts[2], prompts[0]]
        for i, prompt in enumerate(seq):
            keys = (None, None) if temp == 0.0 else (
                jax.random.key(100 + i), jax.random.key(100 + i))
            got_on = _run_one(on, prompt, 6, key=keys[0])
            got_off = _run_one(off, prompt, 6, key=keys[1])
            np.testing.assert_array_equal(got_on, got_off)
            np.testing.assert_array_equal(
                got_on, _oracle(params, prompt, 6,
                                key=(None if temp == 0.0
                                     else jax.random.key(100 + i)),
                                temperature=temp, top_k=topk))
        # the workload actually exercised the tier
        tier = on.kv_tier
        assert tier.demotions > 0 and tier.promotions > 0
        assert on._decode_blocked_demotions == 0
        assert on.metrics.summary()["host_hit_tokens"] > 0

    def test_promotion_is_async_other_slots_keep_decoding(self, params,
                                                          rng):
        """Sarathi discipline applied to memcpy: with a 1-block/step
        promotion budget, the queue head sits PROMOTING for several
        steps — and the already-running slot emits a token on every
        one of them."""
        eng = _engine(params, num_blocks=14, max_slots=2,
                      kv_tier_bytes=1 << 20,
                      kv_tier_promote_budget_bytes=1)
        # DISTINCT prompts: shared prefixes would cross-promote during
        # the warm-up and shrink the host chain under test
        prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (16,)),
                              np.int32) for _ in range(3)]
        for prompt in prompts:           # warm, then evict A's chain
            _run_one(eng, prompt, 4)
        assert eng.kv_tier.demotions > 0
        covered, keys = eng.pool.plan_promotion(prompts[0][:16],
                                                max_tokens=15)
        assert len(keys) >= 2            # multi-step promotion ahead

        long_tokens = []
        rid_long = eng.submit(
            np.asarray(rng.integers(0, CFG.vocab_size, (6,)), np.int32),
            16, on_token=lambda r, t, l: long_tokens.append(t))
        eng.step()                       # admit + first token
        rid_a = eng.submit(prompts[0], 4)

        overlap_steps = 0
        while eng.has_work:
            promoting = bool(eng._promoting)
            n0 = len(long_tokens)
            eng.step()
            if promoting and len(long_tokens) > n0:
                overlap_steps += 1
        # the head really was parked PROMOTING while the long request
        # kept streaming, one budgeted block per step
        assert overlap_steps >= 2
        assert eng.kv_tier.promotions >= 2
        assert eng.metrics.summary()["kv_promotions"] >= 2
        np.testing.assert_array_equal(
            np.asarray(eng.result(rid_a)),
            _oracle(params, prompts[0], 4))
        np.testing.assert_array_equal(
            np.asarray(eng.result(rid_long))[6:],
            np.asarray(long_tokens, np.int32))

    def test_host_eviction_racing_promotion_degrades_to_prefill(
            self, params, rng):
        """The record a promotion was counting on vanishes mid-flight
        (host-budget pressure): the promotion force-finishes instead
        of wedging, admission re-prefills the gap, and the output is
        still oracle-identical."""
        eng = _engine(params, num_blocks=14, max_slots=2,
                      kv_tier_bytes=1 << 20,
                      kv_tier_promote_budget_bytes=1)
        prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (16,)),
                              np.int32) for _ in range(3)]
        for prompt in prompts:
            _run_one(eng, prompt, 4)
        bg_prompt = np.asarray(rng.integers(0, CFG.vocab_size, (6,)),
                               np.int32)
        rid_bg = eng.submit(bg_prompt, 12)
        eng.step()
        rid_a = eng.submit(prompts[0], 4)
        # let the promotion start, then yank the rest of the tier out
        # from under it — the budget-eviction race, made deterministic
        for _ in range(50):
            if eng._promoting:
                break
            eng.step()
        assert eng._promoting
        eng.kv_tier._records.clear()
        eng.kv_tier.bytes_used = 0
        while eng.has_work:
            eng.step()
        assert not eng._promoting        # truncated, not wedged
        np.testing.assert_array_equal(
            np.asarray(eng.result(rid_a)), _oracle(params, prompts[0], 4))
        np.testing.assert_array_equal(
            np.asarray(eng.result(rid_bg)), _oracle(params, bg_prompt, 12))

    def test_tier_requires_prefix_cache(self, params):
        with pytest.raises(ValueError, match="prefix_cache"):
            _engine(params, kv_tier_bytes=1 << 20, prefix_cache=False)
        with pytest.raises(ValueError, match="kv_tier_bytes"):
            _engine(params, kv_tier_bytes=-1)

    def test_limits_report_tier(self, params):
        assert _engine(params, kv_tier_bytes=1 << 20
                       ).limits()["kv_tier"] is True
        assert _engine(params).limits()["kv_tier"] is False


# ---------------------------------------------------------------------
# fleet layer: peer lookup ships a warm chain instead of re-prefilling
# ---------------------------------------------------------------------

def test_fleet_peer_lookup_beats_reprefill(params, rng):
    """2 process replicas, round-robin: the first request warms
    replica 0; the identical prompt then dispatches to replica 1,
    whose tier peer lookup probes the fleet (``kv_peek``), finds
    replica 0's chain, and ships it over the existing
    ``kv_export``/``kv_import`` wire before the submit lands — a
    host-hit on ANY replica beats a re-prefill, token-identically."""
    spec = {"file": FACTORY_FILE, "func": "build_tiny_gpt2",
            "kwargs": {"temperature": 0.8, "top_k": 5,
                       "max_seq_len": 40, "num_blocks": 24,
                       "kv_tier_bytes": 1 << 20}}
    fleet = ProcessFleet(spec, n_replicas=2, policy="round_robin",
                         platform="cpu")
    try:
        prompt = np.asarray(rng.integers(0, CFG.vocab_size, (12,)),
                            np.int32)
        k1, k2 = jax.random.key(11), jax.random.key(22)
        out1 = fleet.generate([prompt], max_new_tokens=6, keys=[k1],
                              timeout=300)[0]
        probes0 = fleet.metrics.tier_probes
        out2 = fleet.generate([prompt], max_new_tokens=6, keys=[k2],
                              timeout=300)[0]
        assert fleet.metrics.tier_probes > probes0
        assert fleet.metrics.tier_peer_transfers >= 1
        np.testing.assert_array_equal(
            out1, _oracle(params, prompt, 6, key=k1,
                          temperature=0.8, top_k=5))
        np.testing.assert_array_equal(
            out2, _oracle(params, prompt, 6, key=k2,
                          temperature=0.8, top_k=5))
        s = fleet.summary()
        assert s["tier_peer_transfers"] >= 1
        assert s["tier_peer_fallbacks"] == 0
    finally:
        fleet.close()
