"""The six per-layer readers PR 25 added for the saturated serving
cell, on hand-made run contexts and a stand-in ring; the byte formulas
of lib/serve_bytes.py against hand arithmetic; the manifest's new
entries. CPU only: nothing here is a device number."""

import json
import os

import pytest

from benchmarks.lib import harness, serve_bytes, step_ring

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ("decode_device_ms.sat", "prefill_device_share_pct.sat",
       "engine_host_ms.sat", "host_syncs_per_step.sat",
       "h2d_kb_per_step.sat", "decode_hbm_roofline_pct.sat")


class _Ring:
    """What a reader needs of obs.recorder.StepRecorder."""

    def __init__(self, records, static):
        self._records, self.static = records, static

    def snapshot(self):
        return [dict(r) for r in self._records]


def _record(t0, t1, *, wait, syncs=2, h2d=3400, context=4000, decoding=12):
    return {"t0": t0, "t1": t1, "phases": {"wait": wait, "commit": 1e-4},
            "host_syncs": syncs, "h2d_bytes": h2d,
            "context_tokens": context, "decoding": decoding}


@pytest.fixture
def bench():
    return harness.Bench(ROOT)


@pytest.fixture
def run(monkeypatch):
    """A context of 3 window steps and 2 traced ones, each driver span
    a little wider than the engine's record inside it, and the ring
    that holds the records (plus two of the fill, before the window)."""
    spans = [(10.0 + 0.2 * i, 10.0 + 0.2 * i + 0.14) for i in range(5)]
    records = [_record(8.0, 8.1, wait=0.09), _record(9.0, 9.1, wait=0.09)]
    waits = (0.120, 0.124, 0.128, 0.125, 0.125)
    for i, ((s, e), w) in enumerate(zip(spans, waits)):
        records.append(_record(
            s + 0.001, e - 0.001, wait=w, syncs=2 + 2 * (i == 1),
            h2d=3400 + 600 * (i == 1), context=4000 + 100 * i))
    ring = _Ring(records, {"param_bytes": 3.3e9,
                           "kv_bytes_per_token": 307200.0,
                           "max_slots": 12})
    rings = [ring]
    from quintnet_tpu.obs import recorder

    monkeypatch.setattr(recorder, "live", lambda: list(rings),
                        raising=False)
    ctx = {
        "engine_steps": [(s, e, 12) for s, e in spans[:3]],
        "traced_steps": 2, "device_kind": "TPU v5 lite",
        "trace": {"modules": {
            "jit_serve_decode": [2, 0.250],
            "jit_serve_prefill_b256": [1, 0.040],
            "jit_serve_prefill_b512": [1, 0.060]}}}
    return ctx, rings, records


def _read(bench, name, ctx):
    return bench.reader(name)(ctx)


def test_the_six_readers_on_a_hand_made_run(bench, run):
    ctx, _rings, _records = run
    assert _read(bench, "decode_device_ms.sat", ctx) == pytest.approx(125.0)
    assert _read(bench, "prefill_device_share_pct.sat", ctx) \
        == pytest.approx(100.0 * 0.100 / 0.350)
    # walls are 0.138 s; less the waits 0.120 / 0.124 / 0.128: median 14 ms
    assert _read(bench, "engine_host_ms.sat", ctx) == pytest.approx(14.0)
    assert _read(bench, "host_syncs_per_step.sat", ctx) \
        == pytest.approx((2 + 4 + 2) / 3)
    assert _read(bench, "h2d_kb_per_step.sat", ctx) \
        == pytest.approx((3400 + 4000 + 3400) / 3 / 1e3)
    # the traced stretch is records 4 and 5: 4300 and 4400 tokens
    least_ms = 1e3 * (3.3e9 + 4350 * 307200.0) / 819e9
    assert _read(bench, "decode_hbm_roofline_pct.sat", ctx) \
        == pytest.approx(100.0 * least_ms / 125.0)
    assert 0 < _read(bench, "decode_hbm_roofline_pct.sat", ctx) < 100


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("fault", ["no ring", "two rings", "unpaired",
                                   "no decode program", "no live()"])
def test_a_reader_that_finds_nothing_returns_none(bench, run, monkeypatch,
                                                  name, fault):
    """None, never a guess: where the program keeps no ring (the parent
    commit), where two engines live, where a step has no record of its
    own, where the trace names no decode program."""
    ctx, rings, records = run
    from_ring = name not in ("decode_device_ms.sat",
                             "prefill_device_share_pct.sat")
    from_trace = not name.startswith(("engine_host", "host_syncs", "h2d"))
    if fault == "no ring":
        rings.clear()
        hit = from_ring
    elif fault == "two rings":
        rings.append(rings[0])
        hit = from_ring
    elif fault == "unpaired":
        del records[3]                  # the window's second step
        hit = from_ring
    elif fault == "no live()":
        from quintnet_tpu.obs import recorder

        monkeypatch.delattr(recorder, "live")
        hit = from_ring
    else:
        ctx["trace"]["modules"] = {"jit_body": [4, 0.5]}
        hit = from_trace
    value = _read(bench, name, ctx)
    assert (value is None) if hit else (value is not None)


def test_two_records_inside_one_step_do_not_pair(run):
    ctx, _rings, records = run
    s, _e, _r = ctx["engine_steps"][0]
    records.insert(2, _record(s + 0.0001, s + 0.0005, wait=0.0))
    assert step_ring.window_records(ctx) is None


def test_serve_bytes_against_hand_arithmetic_at_gpt2_xl():
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "gpt2-xl.json")))
    L, d = cfg["n_layer"], cfg["n_embd"]
    assert (L, d) == (48, 1600)
    assert serve_bytes.kv_bytes_per_token(L, d, 2) == 307_200
    assert serve_bytes.kv_bytes_per_token(L, d, 4) == 614_400
    got = serve_bytes.gpt2_param_bytes(
        L, d, cfg["vocab_size"], cfg["n_positions"], weight_itemsize=2)
    weights = 48 * 12 * 1600 * 1600 * 2                   # 2,949,120,000
    rest = (48 * 9 * 1600 + 48 * 4 * 1600 + 2 * 1600
            + (50257 + 1024) * 1600) * 4
    assert weights == 2_949_120_000 and got == weights + rest
    assert 3.2e9 < got < 3.4e9
    step = serve_bytes.decode_step_bytes(got, 4200, 307_200)
    assert step == got + 4200 * 307_200
    assert 5.0 < 1e3 * step / 819e9 < 6.0                  # ms, by hand


def test_the_engines_own_counts_agree_with_the_formulas():
    """The ring's static facts are what the reader divides by: at a
    tiny size they equal the benchmark's shape formulas."""
    import gc

    import jax

    from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
    from quintnet_tpu.serve import ServeEngine, gpt2_family

    gc.collect()                # engines of earlier tests: not alive
    cfg = GPT2Config.tiny(n_layer=2)
    eng = ServeEngine(gpt2_family(cfg), gpt2_init(jax.random.key(0), cfg),
                      max_slots=2, block_size=4, num_blocks=16,
                      max_seq_len=32, kv_dtype="bf16",
                      weights_dtype="bf16")
    static = eng.recorder.static
    assert static["kv_bytes_per_token"] == serve_bytes.kv_bytes_per_token(
        cfg.n_layer, cfg.n_embd, 2)
    assert static["param_bytes"] == serve_bytes.gpt2_param_bytes(
        cfg.n_layer, cfg.n_embd, cfg.vocab_size, cfg.n_positions,
        weight_itemsize=2)
    assert step_ring.find_ring() is eng.recorder


def test_the_manifest_lists_the_six_after_what_was_there(bench):
    names = [m["name"] for m in bench.manifest["per_layer"]]
    assert tuple(names[-6:]) == NEW
    cell = bench.cell("gpt2-xl.serve-chat-sat")
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    for m in bench.manifest["per_layer"][-6:]:
        assert m["workloads"] == ["gpt2-xl.serve-chat-sat"]
        assert m["moves"] == "serve_tok_s"
        bench.reader(m["name"])          # its file is there
    for other in ("gpt2-124m.train-packed-s1024",
                  "gpt2-large.train-dp2tp2-s1024"):
        assert not set(NEW) & {m["name"]
                               for m in bench.cell(other).per_layer}
