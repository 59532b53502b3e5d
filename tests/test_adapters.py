"""Multi-tenant LoRA serving goldens (quintnet_tpu/serve/adapters.py).

THE contract: a heterogeneous-adapter batch — different tenants'
adapters plus base-model requests sharing one decode step — produces,
per request, output token-identical to a DEDICATED engine serving that
adapter's ``lora_merge_tree`` merged weights, greedy AND sampled,
including with the prefix cache on, speculation on, under preemption,
and across fleet kill-migration onto a replica that has never seen the
adapter. Plus the operational invariants: the registry's LRU never
evicts a pinned adapter, the prefix index is namespaced per adapter
(identical tokens under different adapters can never alias KV), and
the bounded-compile promise extends to <= prefill buckets + verify
buckets + one decode per ``analysis/specs.lora_rank_buckets`` bucket —
adapters registering/evicting mid-trace trigger ZERO recompiles.
"""

import os

import jax
import numpy as np
import pytest

from quintnet_tpu.analysis.specs import lora_rank_buckets
from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.models.lora import (LoRAConfig, lora_init,
                                      lora_merge_tree, save_lora)
from quintnet_tpu.serve import (AdapterRegistry, KVPool, ServeEngine,
                                SpecConfig, generate, gpt2_family)

CFG = GPT2Config.tiny(n_layer=2, n_positions=128)


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _adapter(params, seed, rank, alpha=None, targets=None):
    """A non-trivial adapter (b moved off its zero init so deltas are
    real) + its config."""
    kw = {"targets": tuple(targets)} if targets else {}
    cfg = LoRAConfig(rank=rank, alpha=alpha or 2.0 * rank, **kw)
    lora = lora_init(jax.random.key(seed), params["blocks"], cfg)
    lora = jax.tree.map(
        lambda l: l + 0.02 * jax.random.normal(
            jax.random.key(seed + 100), l.shape), lora)
    return lora, cfg


@pytest.fixture(scope="module")
def tenants(params, tmp_path_factory):
    """Two tenants of different ranks, saved through the real
    safetensors path the registry consumes."""
    root = tmp_path_factory.mktemp("adapters")
    out = {}
    for aid, seed, rank in (("tenant-a", 1, 4), ("tenant-b", 2, 8)):
        lora, cfg = _adapter(params, seed, rank)
        path = str(root / f"{aid}.safetensors")
        save_lora(lora, cfg, path)
        out[aid] = (lora, cfg, path)
    return out


def _registry(tenants):
    reg = AdapterRegistry()
    for aid, (_l, _c, path) in tenants.items():
        reg.register(aid, path)
    return reg


def _engine(params, adapters=None, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_seq_len", 64)
    return ServeEngine(gpt2_family(CFG), params, adapters=adapters, **kw)


def _dedicated(params, tenants, aid, prompt, max_new, key, **kw):
    """The golden reference: a dedicated engine serving the adapter's
    lora_merge_tree merged weights (or the plain base for aid=None)."""
    merged = (params if aid is None
              else lora_merge_tree(params, tenants[aid][0],
                                   tenants[aid][1]))
    eng = _engine(merged, max_slots=1, **kw)
    return generate(eng, [prompt], max_new_tokens=max_new, keys=[key])[0]


def _prompts(rng, lens):
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------
# registry units
# ---------------------------------------------------------------------

class TestRegistry:
    def test_register_load_evict_reload(self, tenants):
        reg = _registry(tenants)
        assert reg.adapter_ids == ["tenant-a", "tenant-b"]
        assert reg.is_resident("tenant-a")
        reg.evict("tenant-a")
        assert not reg.is_resident("tenant-a")
        assert reg.is_registered("tenant-a")   # registration survives
        entry = reg.acquire("tenant-a")        # reloads from source
        assert entry.resident and entry.loads == 2
        reg.release("tenant-a")

    def test_pinned_adapter_cannot_evict(self, tenants):
        reg = _registry(tenants)
        reg.acquire("tenant-a")
        with pytest.raises(ValueError, match="pinned"):
            reg.evict("tenant-a")
        with pytest.raises(ValueError, match="pinned"):
            reg.unregister("tenant-a")
        reg.release("tenant-a")
        reg.evict("tenant-a")                  # unpinned: fine

    def test_byte_budget_lru_eviction(self, tenants):
        _, _, path_a = tenants["tenant-a"]
        _, _, path_b = tenants["tenant-b"]
        one = AdapterRegistry().register("x", path_a).nbytes
        t = [0.0]
        # rank-8 t1 is 2x the bytes of rank-4 t0/t2: all three resident
        # would be 4x one; a 3.2x budget forces exactly the LRU out
        reg = AdapterRegistry(byte_budget=int(one * 3.2),
                              clock=lambda: t[0])
        for i, p in enumerate([path_a, path_b, path_a]):
            t[0] = float(i)
            reg.register(f"t{i}", p)
        assert not reg.is_resident("t0")       # least-recently-used
        assert reg.is_resident("t1") and reg.is_resident("t2")
        assert reg.evictions == 1
        # touching t1 then loading t0 back evicts t2 (now the LRU)
        t[0] = 3.0
        reg.ensure_resident("t1")
        t[0] = 4.0
        reg.acquire("t0")
        assert not reg.is_resident("t2")
        # a PINNED working set may exceed the budget rather than fail
        t[0] = 5.0
        reg.acquire("t1")
        reg.acquire("t2")
        assert reg.bytes_resident > reg.byte_budget
        assert reg.stats()["pinned"] == 3

    def test_in_memory_entries_never_lru_evicted(self, params, tenants):
        lora, cfg = _adapter(params, 9, 4)
        reg = AdapterRegistry(byte_budget=1)   # absurdly small
        reg.register("mem", tree=lora, cfg=cfg)
        reg.register("f1", tenants["tenant-a"][2])
        reg.register("f2", tenants["tenant-b"][2])
        # way over budget: only file-backed entries are eviction
        # candidates, and the newest registrant is protected — so f1
        # went while the sourceless tree and the fresh file survive
        assert reg.is_resident("mem")
        assert not reg.is_resident("f1")
        assert reg.is_resident("f2")
        with pytest.raises(ValueError, match="in-memory"):
            reg.evict("mem")

    def test_register_validation(self, params, tenants):
        reg = _registry(tenants)
        with pytest.raises(ValueError, match="already registered"):
            reg.register("tenant-a", tenants["tenant-a"][2])
        with pytest.raises(ValueError, match="invalid adapter id"):
            reg.register("", tenants["tenant-a"][2])
        with pytest.raises(ValueError, match="source path"):
            AdapterRegistry().register("x")
        with pytest.raises(KeyError, match="unknown adapter"):
            reg.acquire("nope")
        with pytest.raises(ValueError, match="released more"):
            reg.release("tenant-a")


# ---------------------------------------------------------------------
# engine-side validation
# ---------------------------------------------------------------------

class TestEngineValidation:
    def test_adapter_blind_engine_rejects_adapter_id(self, params):
        eng = _engine(params)
        with pytest.raises(ValueError, match="without adapters"):
            eng.submit(np.zeros((4,), np.int32), 2, adapter_id="a")

    def test_unknown_and_overrank_adapters_fail_at_submit(
            self, params, tenants):
        reg = _registry(tenants)
        lora, cfg = _adapter(params, 11, 16)   # above the default top
        reg.register("huge", tree=lora, cfg=cfg)
        eng = _engine(params, adapters=reg)    # ladder tops out at 8
        with pytest.raises(KeyError, match="unknown adapter"):
            eng.submit(np.zeros((4,), np.int32), 2, adapter_id="ghost")
        with pytest.raises(ValueError, match="rank 16"):
            eng.submit(np.zeros((4,), np.int32), 2, adapter_id="huge")
        # the failed pin was rolled back
        assert reg.entry("huge").refs == 0

    def test_unserved_target_rejected_not_dropped(self, params, tenants):
        """An adapter training targets the engine is NOT configured to
        pack must be rejected — silently dropping a trained factor
        would diverge from the adapter's merged-weights golden."""
        reg = _registry(tenants)   # tenants train qkv/proj/fc
        eng = _engine(params, adapters=reg,
                      lora_targets=("qkv", "proj"))   # no fc packing
        with pytest.raises(ValueError, match="mlp.fc"):
            eng.submit(np.zeros((4,), np.int32), 2,
                       adapter_id="tenant-a")
        assert reg.entry("tenant-a").refs == 0   # pin rolled back

    def test_changed_on_disk_reload_rejected(self, params, tmp_path):
        """A source file rewritten with a different config (same rank,
        new alpha) must fail the reload — serving new factors under
        the stale registered scale would be neither adapter."""
        lora, cfg = _adapter(params, 21, 4, alpha=8.0)
        path = str(tmp_path / "mut.safetensors")
        save_lora(lora, cfg, path)
        reg = AdapterRegistry()
        reg.register("mut", path)
        reg.evict("mut")
        save_lora(lora, LoRAConfig(rank=4, alpha=32.0), path)
        with pytest.raises(ValueError, match="changed on disk"):
            reg.ensure_resident("mut")

    def test_shape_mismatch_fails_the_request_only(self, params, tenants):
        reg = _registry(tenants)
        other = gpt2_init(jax.random.key(9),
                          GPT2Config.tiny(n_layer=2, n_embd=48, n_head=2))
        wrong, wcfg = _adapter(other, 12, 4)
        reg.register("wrong-dims", tree=wrong, cfg=wcfg)
        eng = _engine(params, adapters=reg)
        with pytest.raises(ValueError, match="do not match"):
            eng.submit(np.zeros((4,), np.int32), 2,
                       adapter_id="wrong-dims")
        # the engine itself is fine: a good request still runs
        rid = eng.submit(np.zeros((4,), np.int32), 2,
                         adapter_id="tenant-a")
        eng.run(max_steps=50)
        assert eng.result(rid).shape == (6,)


# ---------------------------------------------------------------------
# parity goldens vs dedicated merged-weight engines
# ---------------------------------------------------------------------

def test_heterogeneous_batch_matches_dedicated_greedy(params, tenants):
    """Mixed adapters + base-model slots in ONE decode step, staggered
    arrivals: every request equals its dedicated merged-weight engine."""
    reg = _registry(tenants)
    eng = _engine(params, adapters=reg)
    rng = np.random.default_rng(0)
    prompts = _prompts(rng, (5, 7, 6, 4))
    keys = [jax.random.key(10 + i) for i in range(4)]
    aids = ["tenant-a", "tenant-b", None, "tenant-a"]
    arrivals = [0, 0, 1, 3]
    rids, submitted, step = {}, 0, 0
    while submitted < len(prompts) or eng.has_work:
        while submitted < len(prompts) and arrivals[submitted] <= step:
            rids[submitted] = eng.submit(
                prompts[submitted], 8, key=keys[submitted],
                adapter_id=aids[submitted])
            submitted += 1
        eng.step()
        step += 1
        assert step < 500
    assert eng.metrics.peak_running >= 3   # tenants truly shared steps
    for i in range(4):
        ref = _dedicated(params, tenants, aids[i], prompts[i], 8, keys[i])
        np.testing.assert_array_equal(eng.result(rids[i]), ref)
    # per-adapter ledgers saw the traffic
    per = eng.metrics.summary()["adapters"]
    assert per["tenant-a"]["requests"] == 2
    assert per["tenant-b"]["gen_tokens"] == 8
    # every retire released its pin
    assert all(reg.entry(a).refs == 0 for a in reg.adapter_ids)


def test_heterogeneous_batch_matches_dedicated_sampled(params, tenants):
    reg = _registry(tenants)
    kw = dict(temperature=0.8, top_k=20)
    eng = _engine(params, adapters=reg, **kw)
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, (5, 7, 6))
    keys = [jax.random.key(20 + i) for i in range(3)]
    aids = ["tenant-a", "tenant-b", None]
    rids = [eng.submit(p, 8, key=k, adapter_id=a)
            for p, k, a in zip(prompts, keys, aids)]
    eng.run(max_steps=200)
    for i in range(3):
        ref = _dedicated(params, tenants, aids[i], prompts[i], 8,
                         keys[i], **kw)
        np.testing.assert_array_equal(eng.result(rids[i]), ref)


def test_parity_with_prefix_cache_and_namespacing(params, tenants):
    """The same prompt served under tenant-a, tenant-b AND the base
    model: per-adapter chains hit within a tenant (second wave
    re-prefills almost nothing) while IDENTICAL token prefixes under
    other adapters never alias — the namespaced-index guarantee."""
    reg = _registry(tenants)
    eng = _engine(params, adapters=reg)
    rng = np.random.default_rng(2)
    shared = rng.integers(0, CFG.vocab_size, (16,)).astype(np.int32)
    aids = ["tenant-a", "tenant-b", None]
    keys = [jax.random.key(30 + i) for i in range(6)]
    # wave 1: one request per namespace, identical prompt
    w1 = [eng.submit(shared, 6, key=keys[i], adapter_id=aids[i])
          for i in range(3)]
    eng.run(max_steps=200)
    hits_w1 = eng.metrics.prefix_hit_tokens
    # wave 2: same prompt again per namespace -> intra-namespace hits
    w2 = [eng.submit(shared, 6, key=keys[3 + i], adapter_id=aids[i])
          for i in range(3)]
    eng.run(max_steps=200)
    assert eng.metrics.prefix_hit_tokens > hits_w1
    for i in range(3):
        for rid, key in ((w1[i], keys[i]), (w2[i], keys[3 + i])):
            ref = _dedicated(params, tenants, aids[i], shared, 6, key)
            np.testing.assert_array_equal(eng.result(rid), ref)


def test_pool_prefix_index_is_namespaced():
    """KVPool unit for the same guarantee: a chain published under one
    adapter id is invisible to other namespaces and to the base."""
    pool = KVPool(n_layers=1, n_kv_heads=1, head_dim=4, block_size=4,
                  num_blocks=8)
    toks = np.arange(8, dtype=np.int32)
    blocks = pool.acquire(2)
    pool.publish(toks, blocks, 8, namespace="tenant-a")
    hit = pool.lookup(toks, namespace="tenant-a")
    assert hit.cached_tokens == 8 and hit.shared_blocks == blocks
    assert pool.lookup(toks, namespace="tenant-b").cached_tokens == 0
    assert pool.lookup(toks).cached_tokens == 0
    base_blocks = pool.acquire(2)
    pool.publish(toks, base_blocks, 8)          # base namespace
    assert pool.lookup(toks).shared_blocks == base_blocks
    assert pool.lookup(toks,
                       namespace="tenant-a").shared_blocks == blocks
    # adversarial byte collision: 'abc' + NUL == the little-endian
    # bytes of token 0x00636261, so without the base-key NUL prefix a
    # base prompt opening with that token could alias namespace 'abc'
    abc = KVPool(n_layers=1, n_kv_heads=1, head_dim=4, block_size=1,
                 num_blocks=8)
    t = np.asarray([7], np.int32)
    blk = abc.acquire(1)
    abc.publish(t, blk, 1, namespace="abc")
    crafted = np.asarray([0x00636261, 7], np.int32)
    assert abc.lookup(crafted).cached_tokens == 0


def test_parity_under_preemption(params, tenants):
    """A pool too small for the batch forces preempt-resume; adapter
    bindings survive eviction (unbound at preempt, re-bound at resume)
    and outputs stay token-identical."""
    reg = _registry(tenants)
    eng = _engine(params, adapters=reg, max_slots=3, block_size=4,
                  num_blocks=14, max_seq_len=40)
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, (8, 9, 7))
    keys = [jax.random.key(40 + i) for i in range(3)]
    aids = ["tenant-a", "tenant-b", "tenant-a"]
    rids = [eng.submit(p, 12, key=k, adapter_id=a)
            for p, k, a in zip(prompts, keys, aids)]
    eng.run(max_steps=500)
    assert eng.metrics.preempted > 0
    for i in range(3):
        ref = _dedicated(params, tenants, aids[i], prompts[i], 12,
                         keys[i], block_size=4, num_blocks=14,
                         max_seq_len=40)
        np.testing.assert_array_equal(eng.result(rids[i]), ref)


def test_parity_with_speculation(params, tenants):
    """Spec-on + adapters: repetitive prompts draft and commit
    multi-token runs; committed output equals the dedicated merged
    engine (which is itself spec-off — speculation is bit-exact)."""
    reg = _registry(tenants)
    eng = _engine(params, adapters=reg, max_slots=3, max_seq_len=96,
                  spec=SpecConfig())
    rng = np.random.default_rng(4)
    pat = rng.integers(0, CFG.vocab_size, (4,)).astype(np.int32)
    rp = np.tile(pat, 5)[:18]
    keys = [jax.random.key(50), jax.random.key(51)]
    rid_a = eng.submit(rp, 30, key=keys[0], adapter_id="tenant-a")
    rid_b = eng.submit(rp[:10], 10, key=keys[1], adapter_id="tenant-b")
    eng.run(max_steps=300)
    assert eng.metrics.spec_steps > 0      # speculation actually ran
    ref_a = _dedicated(params, tenants, "tenant-a", rp, 30, keys[0],
                       max_seq_len=96)
    ref_b = _dedicated(params, tenants, "tenant-b", rp[:10], 10, keys[1],
                       max_seq_len=96)
    np.testing.assert_array_equal(eng.result(rid_a), ref_a)
    np.testing.assert_array_equal(eng.result(rid_b), ref_b)


def test_llama_family_parity(tenants):
    """Same contract through the llama family (separate q/k/v/o +
    SwiGLU targets, GQA pool)."""
    from quintnet_tpu.models.llama import LlamaConfig, llama_init
    from quintnet_tpu.models.lora import LLAMA_TARGETS
    from quintnet_tpu.serve import llama_family

    lcfg_m = LlamaConfig.tiny()
    lp = llama_init(jax.random.key(0), lcfg_m)
    lora, cfg = _adapter(lp, 5, 4, targets=LLAMA_TARGETS)
    reg = AdapterRegistry()
    reg.register("t", tree=lora, cfg=cfg)
    fam = llama_family(lcfg_m)
    eng = ServeEngine(fam, lp, max_slots=2, block_size=8, num_blocks=32,
                      max_seq_len=64, adapters=reg)
    rng = np.random.default_rng(5)
    p = rng.integers(0, lcfg_m.vocab_size, (6,)).astype(np.int32)
    k = jax.random.key(42)
    rid = eng.submit(p, 8, key=k, adapter_id="t")
    rid_base = eng.submit(p, 8, key=k)     # same prompt, base slot
    eng.run(max_steps=100)
    merged = lora_merge_tree(lp, lora, cfg)
    for ref_params, rid_ in ((merged, rid), (lp, rid_base)):
        ded = ServeEngine(fam, ref_params, max_slots=1, block_size=8,
                          num_blocks=32, max_seq_len=64)
        ref = generate(ded, [p], max_new_tokens=8, keys=[k])[0]
        np.testing.assert_array_equal(eng.result(rid_), ref)


# ---------------------------------------------------------------------
# fleet: affinity routing + kill-migration onto a cold replica
# ---------------------------------------------------------------------

class _StubReplica:
    def __init__(self, name, tokens, resident):
        self.name = name
        self.outstanding_tokens = tokens
        self._resident = set(resident)

    def adapter_resident(self, aid):
        return aid in self._resident


def test_router_adapter_affinity_prefilter():
    from quintnet_tpu.fleet.router import Router

    cold = _StubReplica("r0", 0, ())
    warm = _StubReplica("r1", 100, ("a",))
    r = Router("least_work")
    # least_work alone would pick the idle cold replica...
    assert r.pick([cold, warm]) is cold
    # ...but adapter affinity narrows to the warm one first
    assert r.pick([cold, warm], adapter_id="a") is warm
    # no warm candidate -> the full list stands (soft preference)
    assert r.pick([cold, warm], adapter_id="zzz") is cold


def test_fleet_kill_migration_onto_cold_replica(params, tenants):
    """r0 (adapter-warm) dies mid-flight with its breaker held open;
    every in-flight adapter request resumes on r1 — whose registry has
    NEVER held the adapter resident — token-identical to the dedicated
    merged engine. The cold replica warms itself from the safetensors
    source on demand."""
    from quintnet_tpu.fleet.fleet import ServeFleet
    from quintnet_tpu.ft import ChaosMonkey

    paths = {aid: t[2] for aid, t in tenants.items()}

    def factory():
        reg = AdapterRegistry()
        for aid, path in paths.items():
            reg.register(aid, path)
        return _engine(params, adapters=reg, max_slots=2)

    monkey = ChaosMonkey(kill_at_step=6, mode="raise", target="r0")
    # trip_after=1 + long reset: r0 stays down, so migration MUST land
    # on the cold replica instead of a warm restart
    fleet = ServeFleet(factory, n_replicas=2, chaos=monkey,
                       trip_after=1, breaker_reset_s=1e9)
    try:
        for aid in paths:
            fleet.replicas[1].engine.adapters.evict(aid)
        assert not fleet.replicas[1].adapter_resident("tenant-a")
        rng = np.random.default_rng(6)
        prompts = _prompts(rng, (6, 5, 7))
        keys = [jax.random.key(60 + i) for i in range(3)]
        aids = ["tenant-a", "tenant-b", "tenant-a"]
        fids = [fleet.submit(p, 16, key=k, adapter_id=a)
                for p, k, a in zip(prompts, keys, aids)]
        outs = [fleet.result(f, timeout=120) for f in fids]
        assert fleet.metrics.replica_deaths >= 1
        assert fleet.metrics.migrations >= 1
        for i in range(3):
            ref = _dedicated(params, tenants, aids[i], prompts[i], 16,
                             keys[i])
            np.testing.assert_array_equal(outs[i], ref)
        # the cold replica loaded what it was handed
        assert fleet.replicas[1].adapter_resident("tenant-a")
        # fleet-wide compile accounting handles decode[r*] sentinels
        fleet.assert_compile_count()
        agg = fleet.engine_summary()["adapters"]
        assert agg["tenant-a"]["requests"] == 2
        assert agg["tenant-b"]["requests"] == 1
    finally:
        fleet.close()


# ---------------------------------------------------------------------
# the zero-recompile invariant
# ---------------------------------------------------------------------

def test_zero_recompiles_as_adapters_join_and_leave(params, tenants,
                                                    tmp_path):
    """Mixed trace with adapters REGISTERED AND EVICTED mid-flight:
    after warmup, zero backend compiles (jax.monitoring), compile
    counts pinned at the sentinel bound derived from
    analysis/specs.lora_rank_buckets."""
    from quintnet_tpu.obs.recorder import startup

    reg = _registry(tenants)
    eng = _engine(params, adapters=reg)
    assert eng.lora_rank_buckets == lora_rank_buckets(8)
    eng.warmup()   # every prefill bucket, decode rank bucket, (verify)
    stats0 = eng.compile_stats()
    assert stats0 == {"prefill": len(eng.prefill_buckets),
                      "decode": len(eng.lora_rank_buckets)}
    # one full lifecycle primes submit-path helpers outside sentinels
    eng.submit(np.zeros((3,), np.int32), 2)
    eng.run(max_steps=50)

    rng = np.random.default_rng(7)
    new_lora, new_cfg = _adapter(params, 30, 2)   # third rank class
    new_path = str(tmp_path / "c.safetensors")
    save_lora(new_lora, new_cfg, new_path)

    # the program's own record counts every backend compile or load
    compiled0 = startup().totals.get("programs", 0)
    assert compiled0 > 0                # (and it was listening)
    plan = [("tenant-a", 9), (None, 6), ("tenant-b", 7)]
    rids = [eng.submit(rng.integers(0, CFG.vocab_size, (n,))
                       .astype(np.int32), 6, adapter_id=a)
            for a, n in plan]
    eng.run(max_steps=200)
    # JOIN: a brand-new tenant registers and serves mid-session
    reg.register("tenant-c", new_path)
    rid_c = eng.submit(rng.integers(0, CFG.vocab_size, (5,))
                       .astype(np.int32), 6, adapter_id="tenant-c")
    # LEAVE: an idle tenant's weights evict; traffic continues
    reg.evict("tenant-a")
    rid_a = eng.submit(rng.integers(0, CFG.vocab_size, (4,))
                       .astype(np.int32), 6, adapter_id="tenant-a")
    eng.run(max_steps=200)
    assert all(eng.request(r).state == "finished"
               for r in rids + [rid_c, rid_a])
    assert startup().totals.get("programs", 0) == compiled0
    assert eng.compile_stats() == stats0       # nothing new compiled
    eng.assert_compile_count(prefill=stats0["prefill"],
                             decode=stats0["decode"])


def test_rank_bucket_selection(params, tenants):
    """The decode step runs in the smallest ladder bucket covering the
    batch's largest bound rank (base-only batches use the floor)."""
    reg = _registry(tenants)
    eng = _engine(params, adapters=reg)
    assert eng._decode_rank_bucket() == eng.lora_rank_buckets[0]
    rid = eng.submit(np.zeros((4,), np.int32), 4, adapter_id="tenant-a")
    eng.step()
    assert eng._decode_rank_bucket() == 4      # rank-4 adapter bound
    rid_b = eng.submit(np.zeros((5,), np.int32), 4,
                       adapter_id="tenant-b")
    eng.step()
    assert eng._decode_rank_bucket() == 8      # rank-8 joined the batch
    eng.run(max_steps=100)
    assert eng._decode_rank_bucket() == eng.lora_rank_buckets[0]
    assert {eng.request(r).state for r in (rid, rid_b)} == {"finished"}


def test_adapter_blind_engine_surface_unchanged(params):
    """An adapters=None engine exposes the pre-adapter compile surface
    byte-for-byte: single `decode` sentinel, no rank buckets — fleets
    mixing adapter-on and adapter-off replicas account each
    correctly."""
    eng = _engine(params)
    eng.submit(np.zeros((4,), np.int32), 3)
    eng.run(max_steps=50)
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    assert "decode" in eng.compile_sentinels()
    assert not any(k.startswith("decode[")
                   for k in eng.compile_sentinels())
    eng.assert_compile_count()


# ---------------------------------------------------------------------
# tp-sharded engine (slow tier, like the other tp serve goldens)
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_tp2_adapter_parity(params, tenants):
    """The whole multi-LoRA step under a tp=2 shard_map: packed factors
    sharded per-target like their weights (a in-sharded, b out-sharded,
    gpt2's fused qkv re-blocked by the family layout hook), outputs
    identical to the dedicated merged engines."""
    from quintnet_tpu.core.mesh import mesh_from_sizes
    from quintnet_tpu.models.gpt2 import gpt2_to_tp_layout

    reg = _registry(tenants)
    mesh = mesh_from_sizes(tp=2)
    tp_params = gpt2_to_tp_layout(params, CFG, 2)
    eng = _engine(tp_params, adapters=reg, mesh=mesh)
    rng = np.random.default_rng(8)
    prompts = _prompts(rng, (6, 5, 7))
    keys = [jax.random.key(70 + i) for i in range(3)]
    aids = ["tenant-a", "tenant-b", None]
    rids = [eng.submit(p, 8, key=k, adapter_id=a)
            for p, k, a in zip(prompts, keys, aids)]
    eng.run(max_steps=100)
    for i in range(3):
        ref = _dedicated(params, tenants, aids[i], prompts[i], 8,
                         keys[i])
        np.testing.assert_array_equal(eng.result(rids[i]), ref)
