"""Observability goldens (quintnet_tpu/obs/ + the threaded hooks).

THE contract is inertness: arming the flight recorder — per-request
Tracer spans, per-step StepRecorder ring — changes NOTHING about what
the engine computes. Tracing on is token-BIT-identical to tracing off
(greedy and sampled) with prefix cache, speculation, chunked prefill
and int8 KV composed, and the compiled-program census is unchanged.
On top of that: the fleet's black box — a replica death produces a
crash dump carrying the corpse's last-known step ring and the affected
requests' spans, and those spans CONTINUE on the destination replica
under the same trace id (thread fleet in-process; process fleet across
a real SIGKILL with zero cooperation from the corpse). The Prometheus
exposition and Chrome trace-event exports are gated by actual parsers,
not shape squints. Satellites ride along: reservoir-bounded percentile
sources, zero-traffic aggregation without NaN, the per-logger
log_once fix, and trace-id round-trip over the wire.
"""

import json
import logging
import os
import signal
import time
import warnings

import jax
import numpy as np
import pytest

from quintnet_tpu.fleet import ProcessFleet, ServeFleet, Backoff, FrontDoor
from quintnet_tpu.fleet import wire
from quintnet_tpu.fleet.fleet import FleetMetrics
from quintnet_tpu.ft.chaos import ChaosMonkey
from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.obs import (SPAN_NAMES, EventLog, StepRecorder,
                              Tracer, load_crash_dump,
                              parse_exposition, render_exposition,
                              write_crash_dump)
from quintnet_tpu.obs.prom import sample
from quintnet_tpu.serve import ServeEngine, gpt2_family
from quintnet_tpu.serve import metrics as serve_metrics
from quintnet_tpu.serve.metrics import Reservoir, ServeMetrics
from quintnet_tpu.serve.scheduler import RequestProgress

CFG = GPT2Config.tiny(n_layer=2)
FACTORY_FILE = os.path.join(os.path.dirname(__file__),
                            "_proc_factories.py")


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _engine(params, *, obs=False, **kw):
    kwargs = dict(max_slots=2, block_size=4, num_blocks=32,
                  max_seq_len=48)
    kwargs.update(kw)
    eng = ServeEngine(gpt2_family(CFG), params, **kwargs)
    if obs:
        eng.tracer = Tracer(clock=eng.clock)
        eng.recorder = StepRecorder(capacity=64, clock=eng.clock)
    return eng


def _wait_until(pred, *, timeout=120.0, msg=""):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for: {msg}")
        time.sleep(0.01)


# ---------------------------------------------------------------------
# THE inertness golden: observed == unobserved, bit for bit
# ---------------------------------------------------------------------

@pytest.mark.parametrize("combo", [
    dict(spec=True, kv_dtype="int8", temperature=0.8, top_k=5),
    dict(chunked_prefill=True, prefill_len=16, temperature=0.8,
         top_k=5),
    dict(lora=True, kv_dtype="int8", temperature=0.8, top_k=5),
], ids=["spec+int8+sampled", "chunked+sampled", "lora+int8+sampled"])
def test_tracing_is_token_bit_identical(params, rng, combo):
    """Same params, same trace, same keys — one engine with the full
    flight recorder armed, one without. Every output array must be
    bit-identical and the compile census unchanged (observation adds
    zero programs). Sampled, with prefix cache on and the combo's
    feature stack composed — the inertness acceptance gate."""
    from quintnet_tpu.models.lora import LoRAConfig, lora_init
    from quintnet_tpu.serve import AdapterRegistry

    combo = dict(combo)
    lora = combo.pop("lora", False)
    lens = (5, 9, 3, 7, 30 if combo.get("chunked_prefill") else 12)
    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (t,)),
                          np.int32) for t in lens]
    keys = [jax.random.key(100 + i) for i in range(len(prompts))]
    adapter_ids = [None] * len(prompts)

    outs = {}
    stats = {}
    obs_engine = None
    for obs in (False, True):
        kw = dict(combo)
        if lora:
            lcfg = LoRAConfig(rank=4, alpha=8.0)
            tree = lora_init(jax.random.key(77), params["blocks"],
                             lcfg)
            reg = AdapterRegistry()
            reg.register("tenantA", tree=tree, cfg=lcfg)
            kw["adapters"] = reg
            adapter_ids = ["tenantA" if i % 2 == 0 else None
                           for i in range(len(prompts))]
        eng = _engine(params, obs=obs, prefix_cache=True, **kw)
        rids = [eng.submit(p, 8, key=k, adapter_id=a)
                for p, k, a in zip(prompts, keys, adapter_ids)]
        eng.run()
        outs[obs] = [eng.result(r) for r in rids]
        stats[obs] = eng.compile_stats()
        if obs:
            obs_engine = eng
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)
    assert stats[False] == stats[True]
    # and the observer actually observed
    assert len(obs_engine.recorder) > 0
    tids = obs_engine.tracer.trace_ids()
    assert len(tids) == len(prompts)
    names = {s.name for t in tids for s in obs_engine.tracer.spans(t)}
    assert {"submit", "queue", "admit", "finish"} <= names
    if combo.get("chunked_prefill"):
        assert "prefill_chunk" in names
    if combo.get("spec"):
        assert "verify" in names or "decode" in names
    # every emitted name is in the SPAN_NAMES registry (obs/trace.py)
    # — the registry is advisory at runtime, but it must not drift
    # from what the engine actually records
    assert names <= SPAN_NAMES, names - SPAN_NAMES


def test_tracing_inert_across_preemption(params, rng):
    """Preemption pressure (tiny pool) with tracing on vs off: same
    outputs, and the traced side recorded the preempt/resume arc."""
    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (t,)),
                          np.int32) for t in (6, 7, 6)]
    keys = [jax.random.key(7 + i) for i in range(3)]
    outs = {}
    traced = None
    for obs in (False, True):
        eng = _engine(params, obs=obs, num_blocks=8, max_seq_len=20,
                      temperature=0.7, top_k=4)
        rids = [eng.submit(p, 10, key=k)
                for p, k in zip(prompts, keys)]
        eng.run()
        outs[obs] = [eng.result(r) for r in rids]
        if obs:
            traced = eng
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)
    assert traced.metrics.preempted > 0      # pressure actually hit
    names = [s.name for t in traced.tracer.trace_ids()
             for s in traced.tracer.spans(t)]
    assert "preempt" in names


def test_fleet_tracing_inert(params, rng):
    """Thread fleet with obs on vs off, chaos kill included: outputs
    identical (the migration path is also observation-inert)."""
    def factory():
        return ServeEngine(gpt2_family(CFG), params, max_slots=2,
                           block_size=4, num_blocks=24, max_seq_len=40,
                           temperature=0.8, top_k=5)

    prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (5,)),
                          np.int32) for _ in range(4)]
    keys = [jax.random.key(40 + i) for i in range(4)]
    outs = {}
    for obs in (False, True):
        fleet = ServeFleet(
            factory, n_replicas=2, obs=obs,
            chaos=ChaosMonkey(kill_at_step=3, mode="raise",
                              target="r0"))
        try:
            fids = [fleet.submit(p, 12, key=k)
                    for p, k in zip(prompts, keys)]
            outs[obs] = [fleet.result(f, timeout=300) for f in fids]
            assert fleet.metrics.replica_deaths == 1
        finally:
            fleet.close()
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------
# crash-dump forensics
# ---------------------------------------------------------------------

def test_thread_fleet_crash_dump(params, rng, tmp_path):
    """A chaos-killed thread replica leaves a black box: the dump file
    carries its step ring and the migrated requests' spans, and those
    requests' timelines CONTINUE (restore -> finish) under the same
    trace id after migration."""
    def factory():
        return ServeEngine(gpt2_family(CFG), params, max_slots=2,
                           block_size=4, num_blocks=24, max_seq_len=40)

    fleet = ServeFleet(
        factory, n_replicas=2, obs=True, crash_dir=str(tmp_path),
        chaos=ChaosMonkey(kill_at_step=3, mode="raise", target="r0"))
    try:
        prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (5,)),
                              np.int32) for _ in range(4)]
        fids = [fleet.submit(p, 12) for p in prompts]
        [fleet.result(f, timeout=300) for f in fids]
        assert fleet.metrics.replica_deaths == 1
        # the dump file is written by the dispatcher OUTSIDE the fleet
        # lock — wait for the flush, don't race it
        _wait_until(lambda: len(fleet.crash_dumps) == 1,
                    msg="crash dump flushed")
        dump = load_crash_dump(fleet.crash_dumps[0])
        assert dump["replica"] == "r0"
        assert dump["reason"] == "death"
        assert len(dump["ring"]) >= 1            # the corpse's steps
        assert dump["requests"], "affected requests recorded"
        for r in dump["requests"]:
            assert r["trace_id"] in dump["traces"]
            assert dump["traces"][r["trace_id"]]
            # continuation: the SAME id later carries the restore on
            # the survivor and the finish
            names = [s.name
                     for s in fleet.tracer.spans(r["trace_id"])]
            assert "migration" in names
            assert "restore" in names
            assert names.index("restore") > names.index("migration")
            assert "finish" in names
        kinds = [e["kind"] for e in fleet.events.snapshot()]
        assert "replica_death" in kinds
        assert "migration" in kinds
        assert "crash_dump" in kinds
        assert "replica_restart" in kinds or "breaker" in kinds
    finally:
        fleet.close()


def test_process_fleet_sigkill_crash_dump(params, rng, tmp_path):
    """THE acceptance golden on the PR 8 harness: a real
    ``os.kill(pid, SIGKILL)`` mid-stream produces a crash dump
    containing the dead replica's (heartbeat-mirrored) step ring and
    the migrated requests' spans — assembled with zero cooperation
    from the corpse — and the migrated requests' spans CONTINUE on the
    destination replica under the same trace id, while every output
    stays token-identical to the undisturbed oracle."""
    from quintnet_tpu.models.gpt2_generate import gpt2_generate

    max_new = 64       # a tiny model decodes in a burst; the stream
    #                    must outlive a few heartbeats so the mirror
    #                    is non-empty when the kill lands mid-flight
    spec = {"file": FACTORY_FILE, "func": "build_tiny_gpt2",
            "kwargs": {"max_seq_len": 110, "n_positions": 128,
                       "num_blocks": 64}}
    # heartbeat_budget_s generous on purpose: the default (1s) lets a
    # freshly-RESTARTED child on a loaded CI box false-trip the stall
    # detector and write a SECOND dump, which is not what this golden
    # probes (the stall path has its own test in test_fleet_proc.py)
    fleet = ProcessFleet(spec, n_replicas=2, policy="round_robin",
                         platform="cpu", heartbeat_s=0.005,
                         heartbeat_budget_s=5.0,
                         backoff=Backoff(base_s=0.01, cap_s=0.1),
                         obs=True, crash_dir=str(tmp_path))
    try:
        prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (t,)),
                              np.int32) for t in (5, 7, 3, 6)]
        keys = [jax.random.key(500 + i) for i in range(4)]
        streamed = []
        fids = []
        for i, (p, k) in enumerate(zip(prompts, keys)):
            cb = ((lambda fid, tok, last:
                   streamed.append(tok)) if i == 1 else None)
            fids.append(fleet.submit(p, max_new, key=k, on_token=cb))
        victim = fleet.replica("p1")     # round_robin: i=1 -> p1
        # kill mid-stream AND after at least one heartbeat shipped
        # step records — the mirror is "last-known", and last-known
        # must be non-empty for the dump to mean anything
        _wait_until(lambda: len(streamed) >= 2 and len(victim.ring) > 0,
                    msg="victim streaming with a mirrored ring")
        assert len(streamed) < max_new
        os.kill(victim.pid, signal.SIGKILL)

        outs = [fleet.result(f, timeout=300) for f in fids]
        cfg_128 = GPT2Config.tiny(n_layer=2, n_positions=128)
        params_128 = gpt2_init(jax.random.key(0), cfg_128)
        for p, k, o in zip(prompts, keys, outs):
            np.testing.assert_array_equal(
                o, np.asarray(gpt2_generate(
                    params_128, p[None], cfg_128,
                    max_new_tokens=max_new,
                    temperature=0.0, key=k)[0]))
        assert fleet.metrics.replica_deaths == 1
        assert fleet.metrics.migrations >= 1

        # >= 1, first dump: a later incidental event (e.g. a
        # load-starved restarted child) must not deadlock the wait —
        # the DEATH dump this golden is about is always the first
        _wait_until(lambda: len(fleet.crash_dumps) >= 1,
                    msg="crash dump flushed")
        dump = load_crash_dump(fleet.crash_dumps[0])
        assert dump["replica"] == "p1"
        assert dump["reason"] == "death"
        assert len(dump["ring"]) >= 1        # the corpse's last-known
        assert all("step" in r and "t0" in r and "t1" in r
                   for r in dump["ring"])
        assert dump["requests"]
        migrated_tids = [r["trace_id"] for r in dump["requests"]]
        for tid in migrated_tids:
            assert dump["traces"].get(tid), \
                f"no spans for migrated {tid} in the dump"

        # continuation on the DESTINATION replica, same trace id: the
        # survivor's engine recorded restore -> decode -> finish under
        # the id the journal carried over the wire
        dest = fleet.replica_traces("p0", migrated_tids)
        for tid in migrated_tids:
            names = [s["name"] for s in dest.get(tid, [])]
            assert "restore" in names, (tid, names)
            assert "finish" in names, (tid, names)
        kinds = [e["kind"] for e in fleet.events.snapshot()]
        assert "replica_death" in kinds
        assert "migration" in kinds
        assert "crash_dump" in kinds
    finally:
        fleet.drain(timeout=180)


def test_crash_dump_file_roundtrip(tmp_path):
    path = write_crash_dump(
        str(tmp_path), replica="rX", reason="stall", error="wedged",
        ring=[{"step": 1, "t0": 0.0, "t1": 0.1}],
        traces={"f0": [{"trace_id": "f0", "name": "queue",
                        "t0": 0.0, "t1": 0.2, "attrs": {}}]},
        events=[{"ts": 0.0, "seq": 1, "kind": "replica_stall"}],
        requests=[{"fid": 0, "trace_id": "f0", "committed": 3}])
    dump = load_crash_dump(path)
    assert dump["replica"] == "rX" and dump["reason"] == "stall"
    assert dump["ring"] and dump["traces"]["f0"]
    # two dumps in the same second must not collide
    path2 = write_crash_dump(str(tmp_path), replica="rX",
                             reason="death")
    assert path2 != path
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"kind": "crash_dump", "v": 999}))
    with pytest.raises(ValueError, match="version"):
        load_crash_dump(str(bad))


# ---------------------------------------------------------------------
# obs primitives
# ---------------------------------------------------------------------

def test_tracer_bounds_and_merge():
    clk = [0.0]
    tr = Tracer(clock=lambda: clk[0], max_traces=2,
                max_spans_per_trace=8)
    for i in range(20):
        clk[0] = float(i)
        tr.add("a", f"s{i}")
    spans = tr.spans("a")
    assert len(spans) == 8                      # bounded
    assert spans[0].name == "s0"                # first kept (anchor)
    assert spans[-1].name == "s19"              # latest kept
    assert tr.dropped("a") == 12
    tr.add("b", "x")
    tr.add("c", "y")                            # evicts oldest trace
    assert "a" not in tr.trace_ids()
    # merge: another tracer's snapshot folds in under the same ids
    other = Tracer()
    other.add("b", "remote", t0=1.0, t1=2.0, replica="p1")
    tr.merge(other.snapshot())
    assert [s.name for s in tr.spans("b")] == ["x", "remote"]
    # None trace_id is a no-op, not an error
    tr.add(None, "ignored")


def test_recorder_ring_and_drain():
    from quintnet_tpu.obs.recorder import StepRecord

    rec = StepRecorder(capacity=4)
    for i in range(3):
        rec.record(StepRecord(step=i + 1, t0=float(i),
                              t1=float(i) + 0.5))
    assert [r["step"] for r in rec.drain_new()] == [1, 2, 3]
    assert rec.drain_new() == []                # cursor advanced
    for i in range(3, 10):                      # overflow the ring
        rec.record(StepRecord(step=i + 1, t0=float(i),
                              t1=float(i) + 0.5))
    assert len(rec) == 4 and rec.total == 10
    # records that scrolled off before a drain are lost, not
    # re-shipped: only the surviving window arrives, exactly once
    drained = rec.drain_new()
    assert [r["step"] for r in drained] == [7, 8, 9, 10]
    assert rec.drain_new() == []
    # max_records caps one drain; the rest comes next call
    for i in range(10, 14):
        rec.record(StepRecord(step=i + 1, t0=float(i),
                              t1=float(i) + 0.5))
    assert len(rec.drain_new(max_records=3)) == 3
    assert [r["step"] for r in rec.drain_new()] == [14]


def test_event_log_typed_and_jsonl(tmp_path):
    path = tmp_path / "events.jsonl"
    log = EventLog(path=str(path), capacity=4)
    log.emit("replica_death", replica="p0", error="boom")
    log.emit("migration", fid=3)
    with pytest.raises(ValueError, match="unknown event kind"):
        log.emit("oops")
    assert [e["kind"] for e in log.snapshot()] == ["replica_death",
                                                   "migration"]
    assert log.snapshot(kind="migration")[0]["fid"] == 3
    log.close()
    lines = [json.loads(ln) for ln in
             path.read_text().strip().splitlines()]
    assert [ln["kind"] for ln in lines] == ["replica_death",
                                            "migration"]
    assert lines[0]["seq"] == 1 and lines[1]["seq"] == 2


def test_prometheus_render_and_parse(params, rng):
    """render_exposition over REAL ledgers parses with the strict
    parser; samples are addressable by name + labels; malformed text
    is rejected."""
    eng = _engine(params)
    rids = [eng.submit(np.asarray(rng.integers(0, CFG.vocab_size, (5,)),
                                  np.int32), 6) for _ in range(2)]
    eng.run()
    fm = FleetMetrics()
    fm.submitted = 2
    fm.finished = 2
    text = render_exposition(
        fm.summary(), {"r0": eng.metrics.summary()},
        health={"replicas": {"r0": {"state": "healthy"}},
                "queue_depth": 0, "open_requests": 0})
    parsed = parse_exposition(text)
    assert sample(parsed, "quintnet_fleet_finished") == 2.0
    assert sample(parsed, "quintnet_engine_finished",
                  replica="r0") == 2.0
    assert sample(parsed, "quintnet_engine_ttft_s", replica="r0",
                  quantile="p50") >= 0.0
    assert sample(parsed, "quintnet_engine_ttft_s_count",
                  replica="r0") == 2.0
    assert sample(parsed, "quintnet_replica_up", replica="r0") == 1.0
    # one TYPE header per metric name (the format's requirement)
    types = [ln for ln in text.splitlines()
             if ln.startswith("# TYPE")]
    assert len(types) == len({ln.split()[2] for ln in types})
    with pytest.raises(ValueError):
        parse_exposition("this is not { exposition\n")
    assert rids


def test_trace_view_chrome_export(params, rng, tmp_path):
    """The Perfetto export validates as Chrome trace-event JSON (the
    acceptance parser, not a shape squint), covers steps AND request
    spans, and the CLI round-trips a crash dump."""
    from tools.trace_view import chrome_trace, validate_chrome_trace
    import tools.trace_view as trace_view

    eng = _engine(params, obs=True, chunked_prefill=True,
                  prefill_len=16)
    rid = eng.submit(np.asarray(rng.integers(0, CFG.vocab_size, (30,)),
                                np.int32), 6)
    eng.run()
    trace = chrome_trace(eng.recorder.snapshot(),
                         eng.tracer.snapshot())
    n = validate_chrome_trace(trace)
    assert n > 0
    # json-serializable end to end
    reparsed = json.loads(json.dumps(trace))
    assert validate_chrome_trace(reparsed) == n
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert {"M", "X", "i"} <= phases             # steps + instants
    assert "b" in phases and "e" in phases       # async request spans
    x = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in x)
    assert any(e["args"].get("prefill_chunks", 0) > 0 for e in x)
    # unbalanced async must be rejected
    bad = {"traceEvents": [
        {"name": "q", "ph": "e", "ts": 0, "pid": 1, "cat": "r",
         "id": "f0"}]}
    with pytest.raises(ValueError, match="without begin"):
        validate_chrome_trace(bad)
    # the CLI path over a crash-dump-shaped file
    dump_path = tmp_path / "dump.json"
    dump_path.write_text(json.dumps(
        {"ring": eng.recorder.snapshot(),
         "traces": eng.tracer.snapshot()}))
    out_path = tmp_path / "trace.json"
    assert trace_view.main([str(dump_path), "-o", str(out_path)]) == 0
    validate_chrome_trace(json.loads(out_path.read_text()))
    assert rid == 0


def test_frontdoor_metrics_endpoints(params, rng):
    """GET /metrics parses as Prometheus text exposition (acceptance)
    and GET /v1/metrics is explicit application/json carrying the
    per-replica engine_summary."""
    import http.client

    def factory():
        return ServeEngine(gpt2_family(CFG), params, max_slots=2,
                           block_size=4, num_blocks=24, max_seq_len=24)

    fleet = ServeFleet(factory, n_replicas=2, obs=True)
    try:
        fleet.generate([np.asarray(rng.integers(0, CFG.vocab_size,
                                                (5,)), np.int32)],
                       max_new_tokens=6, timeout=300)
        with FrontDoor(fleet) as fd:
            conn = http.client.HTTPConnection(fd.host, fd.port,
                                              timeout=60)
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            assert r.status == 200
            assert r.getheader("Content-Type").startswith(
                "text/plain; version=0.0.4")
            parsed = parse_exposition(r.read().decode())
            assert sample(parsed, "quintnet_fleet_finished") == 1.0
            ups = [v for (name, _l), v in parsed.items()
                   if name == "quintnet_replica_up"]
            assert len(ups) == 2 and all(v == 1.0 for v in ups)
            assert any(name == "quintnet_engine_gen_tokens"
                       for name, _l in parsed)

            conn2 = http.client.HTTPConnection(fd.host, fd.port,
                                               timeout=60)
            conn2.request("GET", "/v1/metrics")
            r2 = conn2.getresponse()
            assert r2.status == 200
            assert r2.getheader("Content-Type") == "application/json"
            body = json.loads(r2.read())
            assert body["frontdoor"]["finished"] == 1
            assert set(body["engine_summary"]) == {"r0", "r1"}
            assert all("gen_tokens" in s
                       for s in body["engine_summary"].values())
    finally:
        fleet.close()


# ---------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------

def test_reservoir_bounds_percentile_sources():
    r = Reservoir(cap=8, seed=1)
    for x in range(5):
        r.append(float(x))
    assert r.n == 5 and len(r) == 5             # exact below the cap
    assert sorted(r) == [0.0, 1.0, 2.0, 3.0, 4.0]
    for x in range(5, 1000):
        r.append(float(x))
    assert r.n == 1000 and len(r) == 8          # bounded above it
    assert all(0.0 <= x < 1000.0 for x in r)
    # deterministic: same seed, same stream -> same retained sample
    r2 = Reservoir(cap=8, seed=1)
    r2.extend(float(x) for x in range(1000))
    assert r.to_list() == r2.to_list()


def test_serve_metrics_reservoir_and_count_surfaced():
    clk = [0.0]
    m = ServeMetrics(clock=lambda: clk[0])
    m.ttfts = Reservoir(cap=16)
    for i in range(100):
        m.record_first_token(i / 100.0, adapter_id="t0")
        m.record_finish(i / 10.0, adapter_id="t0")
        m.record_itl(0.01)
    s = m.summary()
    assert s["ttft_s"]["n"] == 100              # TRUE count surfaced
    assert s["latency_s"]["n"] == 100
    assert s["itl_s"]["n"] == 100
    assert len(m.ttfts) == 16                   # storage bounded
    assert s["adapters"]["t0"]["ttft_s"]["n"] == 100
    assert len(m.per_adapter["t0"]["ttfts"]) <= \
        serve_metrics.RESERVOIR_CAP
    # aggregate pools retained samples and SUMS true counts
    m2 = ServeMetrics(clock=lambda: clk[0])
    m2.record_first_token(0.5, adapter_id="t0")
    agg = serve_metrics.aggregate([m, m2])
    assert agg["ttft_s"]["n"] == 101
    assert agg["adapters"]["t0"]["ttft_s"]["n"] == 101


def test_aggregate_weights_capped_reservoirs_by_true_count():
    """A busy replica whose reservoir hit its cap must not be
    out-voted by a quiet one: pooling weights each retained sample by
    the observations it represents, so fleet percentiles track the
    TRUE traffic mix (naive equal-weight pooling would report the
    quiet replica's tail as the fleet median)."""
    busy = ServeMetrics()
    busy.ttfts = Reservoir(cap=64)
    for _ in range(10000):
        busy.ttfts.append(0.01)          # 10k fast requests, sampled
    quiet = ServeMetrics()
    for _ in range(100):
        quiet.ttfts.append(1.0)          # 100 slow requests, exact
    agg = serve_metrics.aggregate([busy, quiet])
    assert agg["ttft_s"]["n"] == 10100
    # true mix is ~99% fast: every reported percentile up to p99 must
    # be the fast value (equal-weight pooling of 64 vs 100 samples
    # would have said 1.0 at p50)
    assert agg["ttft_s"]["p50"] == 0.01
    assert agg["ttft_s"]["p95"] == 0.01
    # below every cap the pooled result stays the plain exact pooling
    a, b = ServeMetrics(), ServeMetrics()
    a.ttfts.extend([0.1, 0.2, 0.3])
    b.ttfts.extend([0.4])
    exact = serve_metrics.aggregate([a, b])
    assert exact["ttft_s"]["n"] == 4
    assert exact["ttft_s"]["p50"] == float(
        np.percentile([0.1, 0.2, 0.3, 0.4], 50))


def test_zero_traffic_aggregation_no_nan():
    """aggregate() and FleetMetrics.summary() over zero-step engines:
    zeroed dicts, finite floats, NO RuntimeWarning (PR 4's empty-
    summary fix, applied one layer up)."""
    def _all_finite(obj):
        if isinstance(obj, dict):
            return all(_all_finite(v) for v in obj.values())
        if isinstance(obj, (int, float)):
            return np.isfinite(obj)
        return True

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        empty = serve_metrics.aggregate([])
        assert empty["replicas"] == 0
        assert empty["tokens_per_sec"] == 0.0
        assert empty["ttft_s"] == {"p50": 0.0, "p95": 0.0,
                                   "p99": 0.0, "n": 0}
        assert _all_finite(empty)

        fresh = serve_metrics.aggregate([ServeMetrics(),
                                         ServeMetrics()])
        assert fresh["replicas"] == 2
        assert fresh["steps"] == 0
        assert fresh["prefix_hit_rate"] == 0.0
        assert fresh["tokens_per_decode_step"] == 0.0
        assert _all_finite(fresh)

        fm = FleetMetrics().summary()
        assert fm["finished"] == 0 and fm["shed_rate"] == 0.0
        assert fm["ttft_s"]["n"] == 0
        assert _all_finite(fm)

        one = ServeMetrics().summary()
        assert one["tokens_per_sec"] == 0.0
        assert _all_finite(one)


def test_log_once_keyed_by_logger(capsys):
    from quintnet_tpu.utils.logger import log_once, setup_logging

    a = setup_logging(name="qt-test-a")
    b = setup_logging(name="qt-test-b")
    msg = "unique-warning-xyz"
    log_once(a, msg)
    log_once(b, msg)       # a DIFFERENT logger must not be deduped
    log_once(a, msg)       # the same one must
    log_once(b, msg)
    out = capsys.readouterr().out
    assert out.count(msg) == 2


def test_exposition_label_escaping_round_trips():
    """Label values carrying the format's three special characters —
    backslash, double quote, newline — render as ONE well-formed line
    each and parse back to the ORIGINAL value (backslash first in the
    escaper, or it would re-escape the others)."""
    nasty = {
        "q": 'say "hi"',
        "b": "back\\slash",
        "n": "two\nlines",
        "all": 'a\\b"c\nd',
    }
    fm = FleetMetrics()
    fm.finished = 1
    text = render_exposition(
        fm.summary(),
        {name: {"finished": 1} for name in nasty.values()})
    for line in text.splitlines():
        assert "\n" not in line                  # one line per sample
    parsed = parse_exposition(text)
    for raw in nasty.values():
        assert sample(parsed, "quintnet_engine_finished",
                      replica=raw) == 1.0        # round-tripped exact


def test_exposition_parser_rejects_invalid_escape():
    with pytest.raises(ValueError, match="invalid escape"):
        parse_exposition('m{l="bad\\t"} 1\n')
    # the three legal escapes parse
    parsed = parse_exposition('m{l="a\\\\b\\"c\\nd"} 1\n')
    assert sample(parsed, "m", l='a\\b"c\nd') == 1.0


def test_exposition_drops_non_finite_and_parser_rejects_them():
    """The renderer NEVER serves NaN/Inf (an absent sample is honest;
    a NaN poisons every rate() downstream) — and the strict parser
    treats a non-finite sample in OUR exposition as proof a second,
    unguarded accounting path leaked in."""
    fm = FleetMetrics()
    fm.finished = 3
    text = render_exposition(
        fm.summary(),
        {"r0": {"finished": 2.0, "bad_nan": float("nan"),
                "bad_inf": float("inf"),
                "bad_ninf": float("-inf")}})
    parsed = parse_exposition(text)              # strict gate passes
    assert sample(parsed, "quintnet_engine_finished", replica="r0") == 2.0
    for name, _labels in parsed:
        assert "bad_nan" not in name and "bad_inf" not in name
    # the format ALLOWS NaN/Inf tokens; our parser rejects each form
    for tok in ("NaN", "nan", "+Inf", "-Inf", "inf"):
        with pytest.raises(ValueError, match="non-finite"):
            parse_exposition(f"leaked_metric {tok}\n")


def test_exposition_single_series_per_queue_gauge():
    """summary() and health() both know the queue gauges since the
    signal plane landed; the renderer must emit each series ONCE
    (duplicate name+labels lines are off the format — Prometheus
    rejects the whole scrape) and the strict parser is the gate that
    catches a second accounting path leaking in."""
    fm = FleetMetrics()
    fm._queue_probe = lambda: (3, 1.5)
    health = {"replicas": {}, "queue_depth": 4,
              "queue_oldest_wait_s": 9.9, "open_requests": 2}
    text = render_exposition(fm.summary(), health=health)
    parsed = parse_exposition(text)              # raises on duplicates
    # summary won: one series, the summary's value
    assert sample(parsed, "quintnet_fleet_queue_depth") == 3.0
    assert sample(parsed, "quintnet_fleet_queue_oldest_wait_s") == 1.5
    # keys only health carries still render (the fallback)
    assert sample(parsed, "quintnet_fleet_open_requests") == 2.0
    # and the parser really does reject a duplicate series
    with pytest.raises(ValueError, match="duplicate sample"):
        parse_exposition("m 1\nm 2\n")
    parse_exposition('m{a="x"} 1\nm{a="y"} 2\n')  # labels differ: fine


def test_crash_dir_bounded_keeps_newest(tmp_path):
    """A flapping replica must not grow crash_dir without limit: after
    each write only the newest ``keep`` dumps survive (and keep=None
    disables pruning)."""
    paths = []
    for i in range(7):
        paths.append(write_crash_dump(
            str(tmp_path), replica=f"p{i}", reason="death", keep=4))
        os.utime(paths[-1], (i + 1.0, i + 1.0))  # monotone mtimes
    names = sorted(os.listdir(tmp_path))
    assert len(names) == 4
    kept = {os.path.basename(p) for p in paths[-4:]}
    assert set(names) == kept
    # the newest dumps are the ones still loadable
    for p in paths[-4:]:
        assert load_crash_dump(p)["replica"] in {"p3", "p4", "p5", "p6"}
    # keep=None: no pruning
    for i in range(3):
        write_crash_dump(str(tmp_path), replica="x", reason="stall",
                         keep=None)
    assert len(os.listdir(tmp_path)) == 7
    # an invalid keep is rejected BEFORE the dump is written — a
    # post-write raise would leave the dir growing un-pruned forever
    with pytest.raises(ValueError, match="keep"):
        write_crash_dump(str(tmp_path), replica="x", reason="stall",
                         keep=0)
    assert len(os.listdir(tmp_path)) == 7        # nothing landed


def test_trace_view_renders_slo_events_as_global_markers(tmp_path):
    """slo_breach / slo_recovered / rebalance_recommended lifecycle
    events become instant markers on the "fleet events" track —
    SLO-judgment kinds FULL-HEIGHT (scope "g") so they line up against
    every other track, ordinary kinds thread-local ticks — and the CLI
    renders a dump whose only payload is events."""
    from tools.trace_view import chrome_trace, validate_chrome_trace
    import tools.trace_view as trace_view

    events = [
        {"ts": 10.0, "seq": 1, "kind": "slo_breach",
         "objective": "ttft_p99", "pool": "prefill",
         "burn_fast": 4.2, "burn_slow": 3.0},
        {"ts": 10.5, "seq": 2, "kind": "rebalance_recommended",
         "direction": "decode_to_prefill", "revert": False},
        {"ts": 12.0, "seq": 3, "kind": "slo_recovered",
         "objective": "ttft_p99", "pool": "prefill"},
        {"ts": 12.5, "seq": 4, "kind": "rebalance_recommended",
         "direction": "prefill_to_decode", "revert": True},
        {"ts": 11.0, "seq": 5, "kind": "replica_death",
         "replica": "p1"},
        {"not_an_event": True},                  # skipped, not guessed
    ]
    trace = chrome_trace(fleet_events=events)
    assert validate_chrome_trace(trace) > 0
    inst = {e["name"]: e for e in trace["traceEvents"]
            if e["ph"] == "i"}
    breach = inst["slo_breach ttft_p99 [prefill] 4.2x"]
    assert breach["s"] == "g"                    # full-height marker
    assert breach["args"]["burn_fast"] == 4.2
    assert inst["rebalance decode_to_prefill"]["s"] == "g"
    assert inst["rebalance prefill_to_decode (revert)"]["s"] == "g"
    assert inst["slo_recovered"]["s"] == "g"
    assert inst["replica_death"]["s"] == "t"     # ordinary tick
    # timestamps re-based to the earliest event (t=10.0 -> 0us)
    assert breach["ts"] == 0.0
    assert inst["replica_death"]["ts"] == pytest.approx(1e6)
    # the CLI path over an events-only dump (crash dumps embed the
    # ring+traces too; a bare event ring must still render)
    dump = tmp_path / "events.json"
    dump.write_text(json.dumps({"events": events}))
    out = tmp_path / "trace.json"
    assert trace_view.main([str(dump), "-o", str(out)]) == 0
    rendered = json.loads(out.read_text())
    assert validate_chrome_trace(rendered) > 0
    assert any(e.get("name", "").startswith("slo_breach")
               for e in rendered["traceEvents"])


def test_trace_id_rides_the_wire():
    p = RequestProgress(
        rid=1, prompt=np.arange(3, dtype=np.int32), generated=[7],
        key_data=np.zeros((4,), np.uint32), max_new_tokens=4,
        trace_id="f42")
    back = wire.progress_from_wire(wire.progress_to_wire(p))
    assert back.trace_id == "f42"
    # pre-obs payloads (no field) decode to None, not KeyError
    payload = wire.progress_to_wire(p)
    del payload["trace_id"]
    assert wire.progress_from_wire(payload).trace_id is None


# ---------------------------------------------------------------------
# names on the device trace, phases of a step, the default ring (PR 25)
# ---------------------------------------------------------------------

# greedy tokens of _golden_prompts() from the tree BEFORE the programs
# were named and scoped (commit bf72991): names and scopes are metadata
GOLDEN_GREEDY = [
    [95, 95, 64, 64, 95, 95, 15, 64], [95, 95, 95, 95, 15, 15, 15, 64],
    [115, 115, 115, 117, 64, 64, 64, 64], [64, 64, 127, 14, 14, 14, 14, 95],
    [95, 95, 95, 15, 15, 15, 15, 15]]
GOLDEN_COMPILE_STATS = {"prefill": 1, "decode": 1}
PROGRAM_NAME = r"serve_(prefill_b\d+|decode(_r\d+)?|verify_b\d+|pack_update)"


def _golden_prompts():
    g = np.random.default_rng(25)
    return [np.asarray(g.integers(0, CFG.vocab_size, (t,)), np.int32)
            for t in (5, 9, 3, 7, 12)]


class _TickClock:
    """A clock that moves by one tick at every reading: phase times are
    then exact counts of readings, whatever the machine is doing."""

    def __init__(self, tick=1e-3):
        self.t, self.tick = 0.0, tick

    def __call__(self):
        self.t += self.tick
        return self.t


def test_named_and_scoped_programs_keep_the_golden_tokens(params):
    """The default engine — ring on, programs named, scopes in the
    model — gives the parent tree's greedy tokens and compiles what the
    parent compiled."""
    eng = _engine(params)
    prompts = _golden_prompts()
    rids = [eng.submit(p, 8) for p in prompts]
    eng.run()
    got = [[int(t) for t in eng.result(r)[len(p):]]
           for r, p in zip(rids, prompts)]
    assert got == GOLDEN_GREEDY
    assert eng.compile_stats() == GOLDEN_COMPILE_STATS
    assert len(eng.recorder) > 0          # the ring is on by default
    assert eng.recorder.capacity == 4096


def test_step_phases_are_exclusive_and_sum_to_the_wall(params):
    from quintnet_tpu.obs.spans import PHASES

    eng = _engine(params, clock=_TickClock())
    for p in _golden_prompts():
        eng.submit(p, 6)
    eng.run()
    recs = eng.recorder.snapshot()
    assert len(recs) > 4
    for r in recs:
        assert set(r["phases"]) <= set(PHASES), r["phases"]
        assert all(v >= 0.0 for v in r["phases"].values())
        wall = r["t1"] - r["t0"]
        assert abs(sum(r["phases"].values()) - wall) <= max(
            0.02 * wall, 50e-6), (r["phases"], wall)
    # a step that admitted was in every phase but none twice over
    admitted = next(r for r in recs if r["admitted"])
    assert {"schedule", "prefill", "upload", "dispatch", "wait",
            "commit"} <= set(admitted["phases"])


def test_a_pure_decode_step_counts_two_syncs_and_four_uploads(params):
    eng = _engine(params)
    for p in _golden_prompts()[:2]:
        eng.submit(p, 6)
    eng.step()                            # admits both: not pure
    first = eng.recorder.last()
    assert first["admitted"] == 2
    # one read a step (ServeEngine._fetch): both first tokens with the
    # decode's tokens and keys, both rows joined to the decode on the
    # device
    assert first["host_syncs"] == 1
    assert first["attrs"]["joined_rows"] == 2
    four = sum(a.nbytes for a in (eng._tok, eng._pos, eng._tables,
                                  eng._key_data))
    eng.step()
    pure = eng.recorder.last()
    assert pure["admitted"] == 0 and pure["decoding"] == 2
    assert pure["host_syncs"] == 1
    assert pure["attrs"]["joined_rows"] == 0
    assert pure["h2d_bytes"] == four
    assert "prefill" not in pure["phases"]


def test_context_tokens_is_the_sum_of_positions(params):
    eng = _engine(params, max_slots=3)
    lens = (5, 9, 3)
    for p in _golden_prompts()[:3]:
        eng.submit(p, 6)
    eng.step()
    # after the admitting step each row holds its prompt; that step's
    # decode read exactly those positions
    assert eng.recorder.last()["context_tokens"] == sum(lens)
    eng.step()
    assert eng.recorder.last()["context_tokens"] == sum(lens) + 3
    assert [int(x) for x in eng._pos[:3]] == [n + 2 for n in lens]


@pytest.mark.parametrize("kv_dtype,spec", (("bf16", False), ("f32", False),
                                           ("bf16", True)))
def test_attended_rows_counts_what_the_paged_layers_read(params, kv_dtype,
                                                         spec, monkeypatch):
    """A decode or verify step records ``attended_rows``, pool
    positions x layers its paged layers' attention READ: on a bf16 pool
    every row of the program — the rows that sat out too, at position 0
    — rounded up to the walk's key block; on an f32 pool, which still
    gathers a view, rows x the table's width. ``paged_layers`` is on
    the ring, so a reader divides by what the rows held."""
    import quintnet_tpu.nn.attention as attention
    from quintnet_tpu.serve import SpecConfig
    from tools.trace_view import chrome_trace, read_amplification

    kb, slots, width = 8, 3, 48                  # max_seq_len 48
    monkeypatch.setattr(attention, "WALK_KEY_BLOCK", kb)
    eng = _engine(params, max_slots=slots, kv_dtype=kv_dtype,
                  spec=SpecConfig(max_draft=4) if spec else None)
    layers = eng.recorder.static["paged_layers"]
    assert layers == CFG.n_layer == eng.pool.n_layers
    lens = (5, 9)                                # the third slot sits out
    for p in _golden_prompts()[:2]:
        eng.submit(p, 12)
    run = 1
    for step in range(4):
        before = np.array(eng._pos)
        eng.step()
        rec = eng.recorder.last()
        if spec and rec["spec_step"]:
            # a verify run reads up to its bucket's last column
            run = 1 + max(b for b in eng.spec.buckets
                          if f"serve_verify_b{b}" in eng._read_granule)
        last = before + (run - 1 if rec["spec_step"] else 0)
        if step == 0:
            last = np.array(lens + (0,))         # admitted this step
        assert rec["decoding"] == 2
        if kv_dtype == "f32":
            want = slots * width * layers
        else:
            want = int(((last // kb + 1) * kb).sum()) * layers
        assert rec["attrs"]["attended_rows"] == want, (step, rec)
    ring = eng.recorder.snapshot()
    ratio, steps = read_amplification(ring, layers)
    assert steps == 4
    if kv_dtype == "f32":
        assert ratio > 3.0                       # 144 read of 14-20 held
    else:
        assert 1.0 < ratio < 2.5                 # a block a row, and the
        #                                          idle row's one
    # a step that decoded nothing carries no counter
    idle = _engine(params)
    idle.step()
    assert "attended_rows" not in idle.recorder.last()["attrs"]
    shown = [e["args"] for e in chrome_trace(
        ring, paged_layers=layers)["traceEvents"] if e["ph"] == "X"]
    assert all(a["read_amplification"] == a["attended_rows"] / (
        a["context_tokens"] * layers) for a in shown)


def _lowered_names(eng):
    import re

    return [re.search(r"module @(\S+)",
                      s.fn.lower(*args).as_text()).group(1)
            for s, args in eng._warmup_calls()]


@pytest.mark.parametrize("tp", [False, True], ids=["mesh-less", "tp2"])
def test_every_engine_program_has_a_stable_name(params, tp):
    """What reaches jax.jit is named: the lowered modules read
    jit_serve_*, never jit_body, with no id or counter in the name —
    on the mesh-less path and under the tp shard_map alike, spec and
    adapters armed."""
    import re

    from quintnet_tpu.serve import AdapterRegistry

    kw = dict(spec=True, adapters=AdapterRegistry())
    p = params
    if tp:
        from quintnet_tpu.core.mesh import mesh_from_sizes
        from quintnet_tpu.models.gpt2 import gpt2_to_tp_layout

        p = gpt2_to_tp_layout(params, CFG, 2)
        kw["mesh"] = mesh_from_sizes(tp=2)
    eng = _engine(p, **kw)
    names = _lowered_names(eng)
    assert len(names) == (len(eng.prefill_buckets)
                          + len(eng.lora_rank_buckets)
                          + len(eng.spec.buckets))
    for n in names:
        assert re.fullmatch("jit_" + PROGRAM_NAME, n), n
    assert len(set(names)) == len(names)
    assert eng._pack_update.__name__ == "serve_pack_update"
    assert sorted(n[len("jit_"):] for n in names) \
        == eng.recorder.static["programs"]
    # the same names from a second engine: nothing counts up
    assert _lowered_names(_engine(p, **kw)) == names


def test_train_step_text_carries_the_scope_vocabulary():
    """The train step's lowered text names the layers, the loss, the
    gradient reduction and the optimizer; the scope map reads them back
    per instruction from the compiled text."""
    import jax.numpy as jnp

    from quintnet_tpu.core.config import Config
    from quintnet_tpu.models.gpt2 import gpt2_model_spec
    from quintnet_tpu.obs.scopes import module_name, scope_map
    from quintnet_tpu.parallel.strategy import get_strategy
    from quintnet_tpu.train.trainer import Trainer

    cfg = Config.from_dict({
        "mesh_dim": [2, 2], "mesh_name": ["dp", "tp"],
        "training": {"batch_size": 4, "epochs": 1, "optimizer": "adamw",
                     "grad_clip_norm": 1.0, "log_every": 0}})
    model = gpt2_model_spec(CFG)
    strategy = get_strategy("auto", cfg, devices=jax.devices()[:4])
    trainer = Trainer(cfg, model, strategy=strategy, task_type="clm",
                      log_fn=lambda _m: None)
    params = strategy.shard_params(model, model.init(jax.random.key(0)))
    opt = strategy.init_opt_state(model, trainer.optimizer, params)
    ids = np.zeros((4, 16), np.int32)
    batch = strategy.shard_batch((jnp.asarray(ids), jnp.asarray(ids)),
                                 model)
    import re

    lowered = trainer.step_fn.fn.lower(params, opt, batch)
    assert "module @jit_local_step" in lowered.as_text()
    compiled = lowered.compile().as_text()
    assert module_name(compiled) == "jit_local_step"
    parts = {c for name in re.findall(r'op_name="([^"]+)"', compiled)
             for c in re.split(r"[/()]", name)}
    assert {"attn", "mlp", "lm_head", "loss", "grad_reduce", "optimizer",
            "embed", "final_norm", "grad_clip", "blocks", "qkv", "sdpa",
            "proj", "grads", "all_reduce_tp"} <= parts
    paths = set(scope_map(compiled).values())
    assert any(p.endswith("attn/qkv") for p in paths), sorted(paths)[:20]
    assert any(p.startswith("grads/transpose(jvp(blocks))")
               for p in paths)
    assert "optimizer" in paths and "grad_reduce" in paths
    # the eval program has a name of its own
    trainer._build_eval()
    assert trainer._eval_fn.fn.__name__ == "eval_step"


def test_scope_path_keeps_only_the_vocabulary():
    from quintnet_tpu.obs.scopes import scope_path

    assert scope_path(
        "jit(local_step)/shard_map/grads/transpose(jvp(blocks))/while/"
        "body/closed_call/checkpoint/rematted_computation/attn/proj/"
        "all_reduce_tp/psum") == (
        "grads/transpose(jvp(blocks))/rematted_computation/attn/proj/"
        "all_reduce_tp")
    assert scope_path("jit(serve_decode)/blocks/while/body/closed_call/"
                      "attn/kv_write/jit(floor_divide)/div") \
        == "blocks/attn/kv_write"
    assert scope_path("jit(f)/jit(_where)/select_n") == ""
    assert scope_path("mul;jit(s)/optimizer/add") == "optimizer"


def test_obs_package_import_pulls_in_no_jax():
    """``import quintnet_tpu.obs`` must stay jax-free: every module
    obs/__init__ imports is scanned for an import of jax. The one
    module that needs the profiler's annotations (obs/spans.py) is
    not among them."""
    import ast

    obs_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "quintnet_tpu", "obs")
    with open(os.path.join(obs_dir, "__init__.py")) as f:
        tree = ast.parse(f.read())
    mods = sorted({n.module.rsplit(".", 1)[1] for n in ast.walk(tree)
                   if isinstance(n, ast.ImportFrom)
                   and n.module.startswith("quintnet_tpu.obs.")})
    assert "recorder" in mods and "spans" not in mods
    for mod in mods + ["scopes"]:
        with open(os.path.join(obs_dir, mod + ".py")) as f:
            for node in ast.walk(ast.parse(f.read())):
                names = ([a.name for a in node.names]
                         if isinstance(node, ast.Import) else
                         [node.module or ""]
                         if isinstance(node, ast.ImportFrom) else [])
                assert not any(n == "jax" or n.startswith("jax.")
                               for n in names), (mod, names)


def test_live_finds_an_engines_ring_and_forgets_it(params):
    import gc

    from quintnet_tpu.obs.recorder import live

    gc.collect()
    before = set(map(id, live()))
    eng = _engine(params)
    ring = eng.recorder
    assert ring in live()
    assert ring.static["max_slots"] == 2
    assert ring.static["kv_bytes_per_token"] == eng.pool.bytes_per_token
    assert ring.static["param_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(eng.params))
    # a ring attached later (the fleets do) is found and filled too,
    # in place of the one it replaced
    mine = StepRecorder(capacity=8, clock=eng.clock)
    eng.recorder = mine
    assert mine in live() and mine.static["max_slots"] == 2
    assert ring not in live()
    # an engine built LATER and dropped is never listed beside one
    # that lives (a reader takes a number only from the one ring)
    later = _engine(params)
    assert later.recorder in live() and mine in live()
    del later
    gc.collect()
    assert mine in live()
    assert set(map(id, live())) <= before | {id(mine)}


def test_live_falls_back_to_the_newest_ring_when_no_engine_is_left(
        params, monkeypatch):
    """A reader that holds no engine may come after the last engine's
    last reference is gone (a benchmark reads its per-layer metrics
    after its driver returned): it finds the ring registered last, with
    its records and statics, whatever the garbage collector has done —
    and only while NO engine lives."""
    import gc
    import weakref

    from quintnet_tpu.obs import recorder
    from quintnet_tpu.obs.recorder import StepRecord, live

    # (engines of other tests of this process may live: a registry of
    # this test's own)
    monkeypatch.setattr(recorder, "_LIVE", weakref.WeakKeyDictionary())
    monkeypatch.setattr(recorder, "_NEWEST", None)
    assert live() == []
    older, eng = _engine(params), _engine(params)
    eng.recorder.record(StepRecord(step=1, t0=0.0, t1=1.0,
                                   context_tokens=7))
    kept = id(eng.recorder)
    assert len(live()) == 2
    del older, eng
    gc.collect()
    (left,) = live()
    assert id(left) == kept
    assert left.last()["context_tokens"] == 7
    assert left.static["max_slots"] == 2
    # until an engine lives again: then its ring alone
    del left
    other = _engine(params)
    gc.collect()
    assert live() == [other.recorder]


def test_trace_view_xplane_on_the_recorded_v5e_trace(tmp_path):
    """``--xplane`` on artifacts/trace_r04 (ten 40.7 ms steps of
    jit_local_step on a v5e): programs by name, and device own-time by
    scope through a qn_scopes.json beside the xplane."""
    import shutil

    from benchmarks.lib import trace_reduce as tr

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run = tmp_path / "trace"
    shutil.copytree(os.path.join(root, "artifacts", "trace_r04"), run)
    import tools.trace_view as tv

    bare = tv.xplane_tables(str(run))
    assert bare["programs"]["jit_local_step"]["count"] == 10
    assert abs(bare["programs"]["jit_local_step"]["ms"] - 406.977) < 0.01
    assert list(bare["device_ms_by_scope"]) == [tv.NO_MAP]
    # name half of the trace's operations and see them come back
    ops = tr.read_trace(tr.find_xplane(str(run)))["devices"][
        "/device:TPU:0"]["ops"]
    names = sorted({tr.short_name(n) for n, _s, _e in ops})
    scoped = {n: "grads/blocks/attn" for n in names[::2]}
    with open(run / "qn_scopes.json", "w") as f:
        json.dump({"jit_local_step": scoped}, f)
    out = tv.xplane_tables(str(run))
    by = out["device_ms_by_scope"]
    assert set(by) == {"grads/blocks/attn", tv.NO_SCOPE}
    assert abs(sum(by.values()) - sum(
        bare["device_ms_by_scope"].values())) < 1e-6
    assert 0.0 < out["device_named_share_pct"] < 100.0
    assert out["chips"] == 1 and "collective_ms_by_scope" not in out


def test_trace_view_xplane_idle_by_span_and_exposed_collectives(
        tmp_path, monkeypatch):
    """The interval arithmetic of ``--xplane`` on a hand-made two-chip
    trace: idle goes to the innermost qn.* span, a collective's exposed
    part is what no other operation overlaps."""
    from benchmarks.lib import trace_reduce as tr

    ar = "%psum.1 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}"
    mm = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"
    wh = "%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b"
    us = 1e3                               # the trace's times are ns
    chip = {"modules": [("jit_step(1)", 0.0, 100 * us)],
            # a while that encloses a matmul and an all-reduce, then a
            # second all-reduce that a (pretend) overlapping op half hides
            "ops": [(wh, 0.0, 60 * us), (mm, 0.0, 30 * us),
                    (ar, 30 * us, 60 * us), (ar, 70 * us, 90 * us),
                    (mm, 80 * us, 100 * us)]}
    trace = {"devices": {"/device:TPU:0": chip, "/device:TPU:1": chip},
             "host_spans": [("qn.serve.step", 55 * us, 75 * us),
                            ("qn.serve.wait", 62 * us, 68 * us)]}
    monkeypatch.setattr(tr, "read_trace", lambda *_a, **_k: trace)
    monkeypatch.setattr(tr, "find_xplane", lambda d: d + "/x.xplane.pb")
    with open(tmp_path / "qn_scopes.json", "w") as f:
        json.dump({"jit_step": {"psum.1": "grad_reduce",
                                "fusion.1": "blocks/mlp"}}, f)
    import tools.trace_view as tv

    assert tv.opcode(ar) == "all-reduce" and tv.opcode(wh) == "while"
    out = tv.xplane_tables(str(tmp_path))
    assert out["chips"] == 2
    coll = out["collective_ms_by_scope"]["grad_reduce"]
    assert coll["ms"] == pytest.approx(50e-3)          # 30 + 20 us
    assert coll["exposed_ms"] == pytest.approx(40e-3)  # 30 + 10 us
    # idle 60..70: 60-62 and 68-70 under the step, 62-68 under wait
    assert out["idle_ms_by_span"] == pytest.approx(
        {"qn.serve.wait": 6e-3, "qn.serve.step": 4e-3})
    assert out["idle_named_share_pct"] == 100.0
    by = out["device_ms_by_scope"]
    assert by["blocks/mlp"] > 0 and by["grad_reduce"] > 0
    assert by[tv.NO_SCOPE] == 0.0          # the while: no time of its own
    # chip 0's operations by program: calls and own time (the while's
    # own time is what its body leaves: nothing; the pretend overlap
    # is clipped to the all-reduce it starts in: 30 + 10 us each)
    ops = out["ops_by_program"]["jit_step"]
    assert ops["psum.1"] == {"count": 2, "ms": pytest.approx(40e-3)}
    assert ops["fusion.1"] == {"count": 2, "ms": pytest.approx(40e-3)}
    assert ops["while.1"] == {"count": 1, "ms": 0.0}


def test_scope_map_gives_a_bare_instruction_its_producers_scope():
    """A convert the compiler split off carries no metadata: it takes
    the scope of the nearest producer that has one; a named instruction
    outside every scope stays out."""
    from quintnet_tpu.obs.scopes import module_name, scope_map

    text = """HloModule jit_serve_decode, is_scheduled=true

ENTRY %main (p: bf16[8]) -> f32[8] {
  %p = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kCustom, calls=%fc, metadata={op_name="jit(serve_decode)/blocks/while/body/attn/kv_gather/gather"}
  %custom-call.2 = bf16[8]{0} custom-call(%fusion.1), custom_call_target="ConcatBitcast"
  %convert.3 = f32[8]{0} convert(%custom-call.2), backend_config={"x":[]}
  %select.4 = f32[8]{0} select(%p, %p, %p), metadata={op_name="jit(serve_decode)/jit(_where)/select_n"}
  ROOT %add.5 = f32[8]{0} add(%convert.3, %select.4), metadata={op_name="jit(serve_decode)/sample/add"}
}
"""
    assert module_name(text) == "jit_serve_decode"
    assert scope_map(text) == {
        "fusion.1": "blocks/attn/kv_gather",
        "custom-call.2": "blocks/attn/kv_gather",
        "convert.3": "blocks/attn/kv_gather", "add.5": "sample"}


def test_scope_map_gives_a_rewritten_ragged_dot_its_operands_scope():
    """The TPU compiler rewrites ``lax.ragged_dot`` into custom calls
    under ITS OWN name (``op_name="ragged-dot-none"``), dropping the
    ``experts`` scope nn/moe.py opened around it: most of an MoE
    program's device time read ``(no scope)``. Lines captured from the
    window cell's ``jit_serve_prefill_b16`` compiled for a described
    v5e (backend configs cut): the grouped matmul takes the scope of
    the rows and weights it contracts, NOT of the group sizes that
    reach it through the bookkeeping call; the copy of its result goes
    with it."""
    from quintnet_tpu.obs.scopes import scope_map

    text = """HloModule jit_serve_prefill_b16, is_scheduled=true

ENTRY %main () -> f32[8] {
  %fusion.604 = bf16[128,2048]{1,0:T(8,128)(2,1)S(1)} fusion(%bitcast_convert_fusion.3, %pad_clamp_fusion.8), kind=kCustom, calls=%fused_computation.6.clone.clone.clone, metadata={op_name="jit(serve_prefill_b16)/blocks/while/body/closed_call/moe/experts/gather" stack_frame_id=309}, backend_config={}
  %get-tuple-element.1678 = s32[1024]{0:T(1024)S(1)} get-tuple-element(%fusion.603), index=3, metadata={op_name="jit(serve_prefill_b16)/blocks/while/body/closed_call/moe/sort/reduce_sum" stack_frame_id=352}
  %ragged-dot-metadata.1 = (s32[1025]{0:T(1024)S(1)}, s32[1024]{0:T(1024)S(1)}, s32[1024]{0:T(1024)S(1)}, s32[1]{0:T(128)}) custom-call(%get-tuple-element.1678), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1024]{0}}, metadata={op_name="ragged-dot-metadata"}, backend_config={}
  %get-tuple-element.1679 = s32[1]{0:T(128)} get-tuple-element(%ragged-dot-metadata.1), index=3
  %get-tuple-element.1680 = s32[1025]{0:T(1024)S(1)} get-tuple-element(%ragged-dot-metadata.1), index=0
  %bitcast.769 = bf16[1024,2048,512]{2,1,0:T(8,128)(2,1)} bitcast(%get-tuple-element.1845), metadata={op_name="jit(serve_prefill_b16)/blocks/while/body/closed_call/moe/experts/reshape" stack_frame_id=315}
  %ragged-dot-none.4 = f32[128,512]{1,0:T(8,128)} custom-call(%get-tuple-element.1679, %get-tuple-element.1680, %get-tuple-element.1679, /*index=3*/%fusion.604, %bitcast.769), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}, s32[1025]{0}, s32[1]{0}, bf16[128,2048]{1,0}, bf16[1024,2048,512]{2,1,0}}, frontend_attributes={mosaic_fusion_entry_point="true",ragged_dot_tiling="128,512,512"}, metadata={op_name="ragged-dot-none"}, backend_config={}
  %copy-start.7 = (f32[128,512]{1,0:T(8,128)S(1)}, f32[128,512]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%ragged-dot-none.4)
  %copy-done.7 = f32[128,512]{1,0:T(8,128)S(1)} copy-done(%copy-start.7)
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %copy.9 = f32[8]{0} copy(%x.1)
}
"""
    got = scope_map(text)
    assert got["ragged-dot-none.4"] == "blocks/moe/experts"
    assert got["copy-start.7"] == got["copy-done.7"] == "blocks/moe/experts"
    # the bookkeeping call and its results belong where the sizes were
    # summed; they cost nothing
    assert got["ragged-dot-metadata.1"] == "blocks/moe/sort"
    assert got["get-tuple-element.1679"] == "blocks/moe/sort"
    # an argument's own name has no path either: nothing to inherit,
    # and nothing for the copy of it
    assert "x.1" not in got and "copy.9" not in got
    assert got["fusion.604"] == got["bitcast.769"] == "blocks/moe/experts"
