"""Disaggregated prefill/decode serving goldens
(quintnet_tpu/fleet/proc.py ``pools=`` + serve/kv_pool.py chain
export/import + fleet/wire.py KV frames).

THE contract, in layers:

- **pool**: an exported chain imports byte-exactly (blocks + scales)
  and becomes a warm prefix hit; a full pool or cache-off import
  returns 0 (the caller re-prefills — the chain is cache, not state);
- **engine**: a ``prefill_only`` request commits + streams its first
  token with the REAL last flag, retires with blocks published, and
  the decode-side continuation — warm via the imported chain or cold
  via local re-prefill — is BIT-identical to a colocated engine
  serving the whole request (greedy AND sampled, f32 AND int8);
- **fleet** (fast smoke + slow chaos tier): a real two-pool
  ProcessFleet serves token-identical to the colocated oracle with
  the KV handoff observable in the metrics, and every handoff fault —
  SIGKILL'd exporter, corrupted frame, stalled receiver — finishes
  every request token-identical via retry or local-prefill fallback,
  with the failure visible in the typed event log;
- **degradation ladder**: prefill pool down -> the decode pool
  absorbs prefill work (still token-identical, /healthz says
  ``degraded``); decode pool hard-down (every breaker tripped) ->
  new work sheds typed ``Overloaded('pool_down')`` while admitted
  work requeues behind the breaker.
"""

import functools
import http.client
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.fleet import (ANY_POOL, FrontDoor, Overloaded,
                                ProcessFleet, RetryPolicy, eligible)
from quintnet_tpu.fleet.admission import SHED_REASONS
from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.obs.events import EVENT_KINDS
from quintnet_tpu.serve import ServeEngine, gpt2_family
from quintnet_tpu.serve.kv_pool import KVPool
from quintnet_tpu.serve.kv_quant import make_policy
from quintnet_tpu.serve.scheduler import RequestProgress

CFG = GPT2Config.tiny(n_layer=2)
FACTORY_FILE = os.path.join(os.path.dirname(__file__),
                            "_proc_factories.py")


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _spec(**kw):
    kwargs = {"temperature": 0.8, "top_k": 5, "max_seq_len": 40,
              "num_blocks": 32, "block_size": 4}
    kwargs.update(kw)
    return {"file": FACTORY_FILE, "func": "build_tiny_gpt2",
            "kwargs": kwargs}


def _engine(params, **kw):
    kwargs = dict(max_slots=2, block_size=4, num_blocks=32,
                  max_seq_len=40, temperature=0.8, top_k=5)
    kwargs.update(kw)
    return ServeEngine(gpt2_family(CFG), params, **kwargs)


def _colocated_outputs(params, prompts, keys, max_new=8, **kw):
    """The oracle: ONE engine (same spec) serving each request whole."""
    eng = _engine(params, **kw)
    outs = []
    for p, k in zip(prompts, keys):
        rid = eng.submit(p, max_new, key=k)
        eng.run(max_steps=400)
        outs.append(np.asarray(eng.result(rid)))
    return outs


def _advance(key, n):
    for _ in range(n):
        key = jax.random.split(key, 2)[0]
    return key


def _wait_until(pred, *, timeout=60.0, msg=""):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for: {msg}")
        time.sleep(0.02)


# ---------------------------------------------------------------------
# pool layer
# ---------------------------------------------------------------------


class TestPoolChainExportImport:
    def _publish_chain(self, pool, toks):
        blocks = pool.acquire(pool.blocks_for(len(toks)))
        k = pool.k
        for i, b in enumerate(blocks):
            bs = pool.block_size
            k = k.at[:, b * bs:(b + 1) * bs].set(i + 1)
        pool.update(k, pool.v, *(() if not pool.policy.scaled
                                 else (pool.k_scale, pool.v_scale)))
        pool.publish(toks, blocks, len(toks))
        pool.release(blocks)
        return blocks

    def test_missing_chain_exports_none(self):
        pool = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                      block_size=4, num_blocks=8)
        assert pool.export_chain(np.arange(6, dtype=np.int32)) is None

    def test_round_trip_is_byte_exact_and_hits(self):
        toks = np.arange(10, dtype=np.int32)
        src = KVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        self._publish_chain(src, toks)
        chain = src.export_chain(toks)
        dst = KVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        assert dst.import_chain(chain) == 10
        back = dst.export_chain(toks)
        assert back["n_tokens"] == 10
        for a, b in zip(chain["blocks"], back["blocks"]):
            np.testing.assert_array_equal(a["k"], b["k"])
            np.testing.assert_array_equal(a["v"], b["v"])

    def test_full_pool_import_returns_zero_not_raises(self):
        toks = np.arange(10, dtype=np.int32)
        src = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        self._publish_chain(src, toks)
        chain = src.export_chain(toks)
        dst = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=4)
        held = dst.acquire(3)            # pool fully referenced
        assert held is not None
        assert dst.import_chain(chain) == 0   # fallback, not failure

    def test_cache_off_import_returns_zero(self):
        toks = np.arange(8, dtype=np.int32)
        src = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        self._publish_chain(src, toks)
        chain = src.export_chain(toks)
        dst = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8, prefix_cache=False)
        assert dst.import_chain(chain) == 0

    def test_incumbent_chain_survives_duplicate_import(self):
        """A racing local prefill published first: the import must not
        replace the incumbent blocks (publish keeps incumbents), and
        the duplicate's blocks return to the free list."""
        toks = np.arange(8, dtype=np.int32)
        src = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        self._publish_chain(src, toks)
        chain = src.export_chain(toks)
        dst = KVPool(n_layers=1, n_kv_heads=2, head_dim=4,
                     block_size=4, num_blocks=8)
        incumbent = self._publish_chain(dst, toks)
        free0 = dst.num_free
        dst.import_chain(chain)
        plan = dst.lookup(toks, max_tokens=8)
        assert plan.shared_blocks == incumbent[:len(plan.shared_blocks)]
        assert dst.num_free == free0     # duplicate blocks freed


# ---------------------------------------------------------------------
# engine layer: prefill_only + the disagg golden
# ---------------------------------------------------------------------


class TestPrefillOnly:
    def test_hands_off_with_real_last_flag(self, params, rng):
        eng = _engine(params)
        prompt = np.asarray(rng.integers(0, CFG.vocab_size, (6,)),
                            np.int32)
        seen = []
        rid = eng.submit(prompt, 8, key=jax.random.key(1),
                         on_token=lambda r, t, l: seen.append((t, l)),
                         prefill_only=True)
        eng.run(max_steps=20)
        req = eng.request(rid)
        assert req.handed_off is True
        assert len(req.generated) == 1
        assert seen == [(req.generated[0], False)]   # NOT last: 7 left
        # the chain was published — the handoff payload exists
        assert eng.export_kv_chain(prompt)["n_tokens"] == len(prompt)

    def test_one_token_budget_finishes_normally(self, params, rng):
        eng = _engine(params)
        prompt = np.asarray(rng.integers(0, CFG.vocab_size, (5,)),
                            np.int32)
        seen = []
        rid = eng.submit(prompt, 1, key=jax.random.key(2),
                         on_token=lambda r, t, l: seen.append((t, l)),
                         prefill_only=True)
        eng.run(max_steps=20)
        req = eng.request(rid)
        assert req.handed_off is False    # complete, nothing to move
        assert seen[0][1] is True         # real last flag

    def test_eos_on_first_token_finishes_normally(self, params, rng):
        prompt = np.asarray(rng.integers(0, CFG.vocab_size, (5,)),
                            np.int32)
        greedy = _engine(params, temperature=0.0, top_k=0)
        rid = greedy.submit(prompt, 8, prefill_only=True)
        greedy.run(max_steps=20)
        t0 = greedy.request(rid).generated[0]
        eng = _engine(params, temperature=0.0, top_k=0,
                      eos_token_id=int(t0))
        seen = []
        rid = eng.submit(prompt, 8, prefill_only=True,
                         on_token=lambda r, t, l: seen.append((t, l)))
        eng.run(max_steps=20)
        req = eng.request(rid)
        assert req.handed_off is False    # EOS = genuinely done
        assert seen == [(int(t0), True)]


BLOCK = 4


@functools.partial(jax.jit, static_argnames="kv")
def _verify_run(params, caches, ids, start, table, *, kv):
    """One run of ``ids`` [1, n] at ``start`` through the family's
    verify contract: logits at every run position + the updated pool."""
    policy = make_policy(kv)
    return gpt2_family(CFG).verify(
        params, caches[0], caches[1], ids, start,
        jnp.full((1,), ids.shape[1], jnp.int32), table, BLOCK,
        kv_scales=caches[2:] if policy.scaled else None, policy=policy)


def _logprobs_by_grouping(params, kv, tokens, first_run):
    """Teacher-forced next-token log-probabilities read THROUGH a paged
    pool that is filled the way an engine fills it: ``tokens
    [:first_run]`` in one prefill run (one quantization pass a block),
    every later token appended in a run of its own (each append
    requantizes its block). Returns ``{n: log-probabilities of the
    token after tokens[:n]}`` for every n from ``first_run``."""
    pool = KVPool(n_layers=CFG.n_layer, n_kv_heads=CFG.n_head,
                  head_dim=CFG.n_embd // CFG.n_head, block_size=BLOCK,
                  num_blocks=8, policy=kv)
    table = jnp.asarray([pool.acquire(pool.blocks_for(len(tokens)))])
    out = {}
    for start, n in [(0, first_run)] + [(i, 1) for i in
                                        range(first_run, len(tokens))]:
        logits, *bufs = _verify_run(
            params, pool.caches(),
            jnp.asarray(tokens[None, start:start + n]),
            jnp.asarray([start], jnp.int32), table, kv=kv)
        pool.update(*bufs)
        out[start + n] = np.asarray(jax.nn.log_softmax(logits[0, -1]))
    return out


class TestDisaggGolden:
    """Disaggregated output against colocated — greedy AND sampled,
    prefix-cache-on, f32 AND int8 KV — through the in-process engine
    pair (prefill engine -> exported chain -> decode engine), both with
    the chain transferred (warm) and without (cold: the local
    re-prefill fallback).

    Warm is BIT-identical under every policy: the chain carries the
    exporter's pool bytes and scales. Cold is bit-identical to
    colocated under f32 only. An int8 pool's bytes depend on how the
    tokens were GROUPED when they were written: the colocated engine
    prefilled the prompt and appended each generated token (every
    append requantizes its block under the grown absmax), the cold
    engine prefills ``prompt + generated`` in one pass, and a run
    attends to its own keys and values before they are rounded. Both
    are the int8 policy's arithmetic; they differ by its rounding (at
    the first position the two engines score differently the logits
    moved 4.6e-4 on a std of 0.115, measured PR 29), and on a tiny
    random model whose five best logits lie within 0.01 of each other
    that flips a sampled token (``[int8-True]`` left the colocated
    tokens at position 7 of 13 on every tree since the seed). So the
    int8 cold path is held to what it IS — token-identical to a fresh
    engine given ``prompt + generated`` as its prompt and the advanced
    key — and the two groupings to the policy's stated quality gate
    (tests/test_kv_quant.py: 0.05 nats), here on every next-token
    log-probability instead of on their mean (they differ by at most
    1.3e-3 nats in these four cases; an f32 pool's by 1e-6)."""

    @pytest.mark.parametrize("kv,sample", [
        ("f32", False), ("f32", True), ("int8", True), ("int8", False),
    ])
    def test_warm_and_cold_match_colocated(self, params, rng, kv,
                                           sample):
        kw = (dict(kv_dtype=kv) if sample
              else dict(kv_dtype=kv, temperature=0.0, top_k=0))
        prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)),
                              np.int32) for n in (5, 7)]
        keys = [jax.random.key(40 + i) for i in range(2)]
        colocated = _colocated_outputs(params, prompts, keys, **kw)

        for prompt, key, want in zip(prompts, keys, colocated):
            A = _engine(params, **kw)          # prefill replica
            ra = A.submit(prompt, 8, key=key, prefill_only=True)
            A.run(max_steps=50)
            gen = list(A.request(ra).generated)
            chain = A.export_kv_chain(prompt)
            assert chain is not None

            prog = RequestProgress(
                rid=0, prompt=prompt, generated=gen,
                key_data=np.asarray(jax.random.key_data(
                    _advance(key, len(gen)))),
                max_new_tokens=8)

            B = _engine(params, **kw)          # decode replica, warm
            assert B.import_kv_chain(chain) == len(prompt)
            rb = B.restore_progress(prog)
            B.run(max_steps=200)
            np.testing.assert_array_equal(B.result(rb), want)
            assert B.metrics.summary()["prefill_tokens_saved"] > 0

            C = _engine(params, **kw)          # decode replica, cold
            rc = C.restore_progress(RequestProgress(
                rid=0, prompt=prompt, generated=gen,
                key_data=np.asarray(jax.random.key_data(
                    _advance(key, len(gen)))),
                max_new_tokens=8))
            C.run(max_steps=200)
            if kv == "int8":
                whole = _engine(params, **kw)
                rw = whole.submit(
                    np.concatenate([prompt, np.asarray(gen, np.int32)]),
                    8 - len(gen), key=_advance(key, len(gen)))
                whole.run(max_steps=200)
                np.testing.assert_array_equal(C.result(rc),
                                              whole.result(rw))
                # every position the cold engine sampled, teacher-
                # forced on the colocated tokens
                appended = _logprobs_by_grouping(params, kv, want[:-1],
                                                 len(prompt))
                regrouped = _logprobs_by_grouping(
                    params, kv, want[:-1], len(prompt) + len(gen))
                assert len(regrouped) == 8 - len(gen)
                for n, lp in regrouped.items():
                    assert np.abs(lp - appended[n]).max() < 0.05, n
            else:
                np.testing.assert_array_equal(C.result(rc), want)


# ---------------------------------------------------------------------
# routing / shedding / health units (no processes)
# ---------------------------------------------------------------------


class _StubReplica:
    def __init__(self, name, pool=ANY_POOL, state="healthy",
                 in_flight=0):
        self.name = name
        self.pool = pool
        self.state = state
        self.paused = False
        self.in_flight = in_flight
        self.max_dispatch = 4
        self.outstanding_tokens = 0

    def adapter_resident(self, adapter_id):
        return False


class TestPoolEligibility:
    def test_pool_filter_matches_pool_and_any(self):
        reps = [_StubReplica("prefill0", "prefill"),
                _StubReplica("decode0", "decode"),
                _StubReplica("c0")]      # colocated, pool "any"
        assert [r.name for r in eligible(reps, pool="prefill")] == \
            ["prefill0", "c0"]
        assert [r.name for r in eligible(reps, pool="decode")] == \
            ["decode0", "c0"]
        # pool=None is the colocated predicate, byte-identical
        assert [r.name for r in eligible(reps)] == \
            ["prefill0", "decode0", "c0"]

    def test_state_and_window_still_apply(self):
        reps = [_StubReplica("prefill0", "prefill", state="dead"),
                _StubReplica("prefill1", "prefill", in_flight=4)]
        assert eligible(reps, pool="prefill") == []

    def test_thread_replicas_without_pool_attr_match_any_pool(self):
        class Bare:
            name = "t0"
            state = "healthy"
            paused = False
            in_flight = 0
            max_dispatch = 2

        bare = Bare()
        assert eligible([bare], pool="decode") == [bare]


class TestTypedSurface:
    def test_pool_down_is_a_known_shed_reason(self):
        assert "pool_down" in SHED_REASONS
        e = Overloaded("pool_down", "decode pool is gone")
        assert e.reason == "pool_down"

    def test_frontdoor_maps_pool_down_to_503_with_retry_after(self):
        fd = FrontDoor(fleet=None)
        status, body, headers = fd._error_response(
            Overloaded("pool_down", "nope"))
        assert status == 503
        assert body["reason"] == "pool_down"
        assert "Retry-After" in headers

    def test_handoff_event_kinds_registered(self):
        assert {"handoff", "handoff_retry", "handoff_fallback",
                "pool_degraded", "pool_recovered"} <= EVENT_KINDS

    def test_pools_spec_validated(self):
        with pytest.raises(ValueError, match="exactly"):
            ProcessFleet({"file": "x", "func": "f"},
                         pools={"prefill": 1})
        with pytest.raises(ValueError, match=">= 1 replica"):
            ProcessFleet({"file": "x", "func": "f"},
                         pools={"prefill": 1, "decode": 0})


class _StubHealthFleet:
    """Just enough fleet for FrontDoor's /healthz."""

    def __init__(self, pools, draining=False):
        self._pools = pools
        self._draining = draining

    def health(self):
        replicas = {}
        for pool, states in self._pools.items():
            for i, st in enumerate(states):
                replicas[f"{pool}{i}"] = {"state": st, "pool": pool}
        return {
            "replicas": replicas,
            "pools": {
                pool: {"replicas": [f"{pool}{i}"
                                    for i in range(len(states))],
                       "healthy": sum(s == "healthy" for s in states),
                       "starting": 0,
                       "state": ("up" if any(s == "healthy"
                                             for s in states)
                                 else "down")}
                for pool, states in self._pools.items()},
            "disaggregated": len(self._pools) > 1,
            "queue_depth": 0, "open_requests": 0,
            "draining": self._draining,
        }


def _get_healthz(fleet):
    with FrontDoor(fleet) as fd:
        conn = http.client.HTTPConnection(fd.host, fd.port, timeout=10)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        body = json.loads(resp.read().decode())
        headers = dict(resp.getheaders())
        conn.close()
    return resp.status, body, headers


class TestHealthzPoolMapping:
    """The satellite contract: 200 + status=degraded when one pool is
    down but the ladder still serves; 503 + Retry-After only when
    nothing can serve."""

    def test_all_pools_up_is_200_ok(self):
        status, body, _h = _get_healthz(_StubHealthFleet(
            {"prefill": ["healthy"], "decode": ["healthy", "healthy"]}))
        assert status == 200 and body["status"] == "ok"

    @pytest.mark.parametrize("down_pool", ["prefill", "decode"])
    def test_one_pool_down_is_200_degraded(self, down_pool):
        pools = {"prefill": ["healthy"], "decode": ["healthy"]}
        pools[down_pool] = ["dead"]
        status, body, _h = _get_healthz(_StubHealthFleet(pools))
        assert status == 200
        assert body["status"] == "degraded"
        assert body["pools"][down_pool]["state"] == "down"

    def test_both_pools_down_is_503_with_retry_after(self):
        status, body, headers = _get_healthz(_StubHealthFleet(
            {"prefill": ["dead"], "decode": ["dead", "stalled"]}))
        assert status == 503
        assert body["status"] == "unavailable"
        assert "Retry-After" in headers

    def test_draining_is_503_even_with_pools_up(self):
        status, body, _h = _get_healthz(_StubHealthFleet(
            {"prefill": ["healthy"], "decode": ["healthy"]},
            draining=True))
        assert status == 503 and body["status"] == "unavailable"

    def test_colocated_single_pool_keeps_binary_mapping(self):
        status, body, _h = _get_healthz(_StubHealthFleet(
            {"any": ["healthy", "dead"]}))
        assert status == 200 and body["status"] == "ok"
        status, body, _h = _get_healthz(_StubHealthFleet(
            {"any": ["dead", "dead"]}))
        assert status == 503 and body["status"] == "unavailable"


# ---------------------------------------------------------------------
# the real two-pool process fleet
# ---------------------------------------------------------------------


def test_disagg_process_fleet_token_identical_smoke(params, rng):
    """FAST-tier end-to-end: 1 prefill + 1 decode replica processes,
    int8 KV, sampled traffic — every output BIT-identical to a
    colocated engine of the same spec, every request handed off with
    its chain transferred, the decode replica serving warm hits, and
    /healthz reporting both pools up."""
    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)),
                          np.int32) for n in (5, 7, 6)]
    keys = [jax.random.key(200 + i) for i in range(3)]
    want = _colocated_outputs(params, prompts, keys, kv_dtype="int8")

    fleet = ProcessFleet(_spec(kv_dtype="int8"),
                         pools={"prefill": 1, "decode": 1},
                         platform="cpu", heartbeat_s=0.05)
    try:
        outs = fleet.generate(prompts, max_new_tokens=8, keys=keys,
                              timeout=300)
        for o, w in zip(outs, want):
            np.testing.assert_array_equal(o, w)
        s = fleet.summary()
        assert s["handoffs"] == 3
        assert s["handoff_transfers"] == 3
        assert s["handoff_fallbacks"] == 0
        assert s["finished"] == s["accepted"] == 3
        # the decode replica really served from the transferred chains
        assert s["engines"]["decode0"]["prefill_tokens_saved"] > 0
        # where the prefill compute ran: every prompt on the prefill
        # pool, warm-hit tails only (the handed-off token and at most a
        # copy-on-write slot a request) on the decode pool
        # (engine counters ride the heartbeat: the last prefill's may
        # still be on the wire when generate() returns)
        _wait_until(
            lambda: fleet.summary()["engines"]["prefill0"][
                "prefill_tokens"] >= sum(len(p) for p in prompts),
            timeout=10, msg="the prefill pool's prefill tokens")
        assert (fleet.summary()["engines"]["decode0"]["prefill_tokens"]
                <= 2 * len(prompts))
        assert s["replicas"]["prefill0"]["pool"] == "prefill"
        h = fleet.health()
        assert h["disaggregated"] is True
        assert h["pools"]["prefill"]["state"] == "up"
        assert h["pools"]["decode"]["state"] == "up"
        fleet.assert_compile_count()
        with FrontDoor(fleet) as fd:
            conn = http.client.HTTPConnection(fd.host, fd.port,
                                              timeout=10)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = json.loads(resp.read().decode())
            conn.close()
        assert resp.status == 200 and body["status"] == "ok"
    finally:
        fleet.close()


# ---------------------------------------------------------------------
# chaos + degradation ladder (slow tier: multi-process, multi-fleet)
# ---------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("fault,target", [
    ("kill", "prefill0"),      # exporter SIGKILL'd mid-transfer
    ("corrupt", "prefill0"),   # frame damaged after its checksum
    ("stall", "decode0"),      # receiver sits on the frame
])
def test_handoff_chaos_token_identical(params, rng, fault, target):
    """Chaos goldens: whatever the handoff fault, EVERY request
    finishes token-identical to an undisturbed colocated run — via
    retry or the local re-prefill fallback — and the failure is
    visible in the typed event log (and, for the kill, in the crash
    machinery: replica death + restart + pool events)."""
    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)),
                          np.int32) for n in (5, 7)]
    keys = [jax.random.key(300 + i) for i in range(2)]
    want = _colocated_outputs(params, prompts, keys)

    chaos = {"target": target, "handoff": fault, "rearm": True,
             "handoff_stall_s": 3.0}
    fleet = ProcessFleet(
        _spec(), pools={"prefill": 1, "decode": 2}, platform="cpu",
        heartbeat_s=0.05, chaos=[chaos], obs=True,
        handoff_retry=RetryPolicy(base_s=0.02, cap_s=0.1,
                                  max_attempts=2),
        handoff_timeout_s=1.0)
    try:
        outs = fleet.generate(prompts, max_new_tokens=8, keys=keys,
                              timeout=300)
        for o, w in zip(outs, want):
            np.testing.assert_array_equal(o, w)
        s = fleet.summary()
        assert s["finished"] == s["accepted"] == 2   # nothing lost
        assert s["handoffs"] == 2
        assert s["handoff_fallbacks"] >= 1           # fault engaged
        kinds = {e["kind"] for e in fleet.events.snapshot()}
        assert "handoff_fallback" in kinds
        if fault == "kill":
            assert s["replica_deaths"] >= 1
            assert {"replica_death", "pool_degraded"} <= kinds
    finally:
        fleet.close()


@pytest.mark.slow
def test_prefill_pool_down_decode_absorbs(params, rng):
    """Degradation ladder, first rung: the prefill pool dies
    repeatedly (rearmed kill, breaker tripped) — the decode pool
    absorbs prefill work colocated-style, every request still
    finishes token-identical, /healthz reports 200 degraded, and the
    event log shows the pool transition."""
    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)),
                          np.int32) for n in (5, 6)]
    keys = [jax.random.key(400 + i) for i in range(2)]
    want = _colocated_outputs(params, prompts, keys)

    fleet = ProcessFleet(
        _spec(), pools={"prefill": 1, "decode": 1}, platform="cpu",
        heartbeat_s=0.05, trip_after=1, breaker_reset_s=300.0,
        obs=True,
        chaos=[{"target": "prefill0", "kill_at_step": 1,
                "mode": "hard", "rearm": True}])
    try:
        outs = fleet.generate(prompts, max_new_tokens=8, keys=keys,
                              timeout=300)
        for o, w in zip(outs, want):
            np.testing.assert_array_equal(o, w)
        s = fleet.summary()
        assert s["finished"] == s["accepted"] == 2
        assert s["replica_deaths"] >= 1
        _wait_until(lambda: fleet.health()["pools"]["prefill"]["state"]
                    == "down", timeout=30,
                    msg="prefill pool marked down")
        kinds = {e["kind"] for e in fleet.events.snapshot()}
        assert "pool_degraded" in kinds
        with FrontDoor(fleet) as fd:
            conn = http.client.HTTPConnection(fd.host, fd.port,
                                              timeout=10)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = json.loads(resp.read().decode())
            conn.close()
        assert resp.status == 200
        assert body["status"] == "degraded"
    finally:
        fleet.close()


@pytest.mark.slow
def test_cache_off_engines_rejected_at_fleet_startup():
    """A disaggregated fleet built from prefix_cache=False engines
    would fall back on EVERY handoff (nothing is ever published to
    export) — fail fast at construction instead of degrading to
    worse-than-colocated with only per-request events as a clue."""
    with pytest.raises(ValueError, match="prefix_cache=True"):
        ProcessFleet(_spec(prefix_cache=False),
                     pools={"prefill": 1, "decode": 1},
                     platform="cpu", heartbeat_s=0.05)


@pytest.mark.slow
def test_decode_pool_hard_down_sheds_typed(params, rng):
    """Degradation ladder, last rung: the decode pool dies repeatedly
    until its breaker is OPEN — admitted work requeues behind the
    breaker (it is NOT errored), and NEW submits shed with typed
    ``Overloaded('pool_down')``."""
    prompt = np.asarray(rng.integers(0, CFG.vocab_size, (5,)),
                        np.int32)
    fleet = ProcessFleet(
        _spec(), pools={"prefill": 1, "decode": 1}, platform="cpu",
        heartbeat_s=0.05, trip_after=1, breaker_reset_s=300.0,
        handoff_retry=RetryPolicy(base_s=0.02, cap_s=0.1,
                                  max_attempts=2),
        handoff_timeout_s=1.0,
        chaos=[{"target": "decode0", "kill_at_step": 1,
                "mode": "hard", "rearm": True}])
    try:
        fid = fleet.submit(prompt, 8, key=jax.random.key(9))
        _wait_until(lambda: fleet.metrics.replica_deaths >= 1
                    and fleet.breaker("decode0").state == "open",
                    timeout=120, msg="decode breaker tripped")
        # the admitted request is requeued, not failed
        freq = fleet.request(fid)
        assert not freq.event.is_set() or freq.error is None
        with pytest.raises(Overloaded) as ei:
            fleet.submit(prompt, 8)
        assert ei.value.reason == "pool_down"
        assert fleet.metrics.shed_pool_down == 1
    finally:
        fleet.close()
