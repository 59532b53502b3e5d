"""The benchmark's own tests: `pytest benchmarks/tests` (CPU, under a
minute). The reduction is held to a recorded v5e trace, the generators
to their seeds and clips, the arithmetic to hand-made logs, the
manifest to its files, and each driver is rehearsed at a tiny size as a
function — no metrics line ever comes out of a CPU run of run.py."""

import itertools
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.lib import harness, stats, trace_reduce, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE = os.path.join(ROOT, "artifacts", "trace_r04", "plugins", "profile",
                     "2026_07_30_04_47_12", "vm.xplane.pb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_reduction_on_the_recorded_v5e_trace():
    trace = trace_reduce.read_trace(TRACE)
    r = trace_reduce.reduce_trace(trace)
    count, total = r["modules"]["jit_local_step"]
    assert count == 10 and total / count == pytest.approx(40.70e-3, abs=2e-5)
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    assert len(ops) == 34730
    # operations nest under the loops that hold them: the plain sum
    # counts those nanoseconds twice, the union does not
    assert sum(e - s for _n, s, e in ops) / 1e6 == pytest.approx(659.7,
                                                                 abs=0.1)
    assert r["busy_s"] * 1e3 == pytest.approx(406.86, abs=0.01)
    assert r["window_s"] * 1e3 == pytest.approx(407.04, abs=0.01)
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        0.05, abs=0.01)
    # own times of the operations add up to the busy time
    assert len(r["device_ops"]) == 10
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1] > 0
    assert sum(v for _k, v in r["device_ops"]) < r["busy_s"]
    assert not trace["host_spans"]          # recorded before bench.* spans
    assert {k for k, _v in r["idle_gaps"]} <= {"unannotated", "between_ops"}
    assert sum(v for _k, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_interval_arithmetic_on_hand_made_events():
    ev = [("loop", 0, 100), ("a", 10, 40), ("b", 40, 90), ("c", 50, 60),
          ("solo", 150, 170)]
    assert trace_reduce.union_ns([(s, e) for _n, s, e in ev]) == 120
    own = trace_reduce.self_times(ev)
    assert own == {"loop": 20, "a": 30, "b": 40, "c": 10, "solo": 20}
    assert sum(own.values()) == 120
    busy = trace_reduce.merge([(s, e) for _n, s, e in ev])
    assert trace_reduce.gaps(busy) == [(100, 150)]
    spans = [("bench.engine_step", 90, 120), ("bench.submit", 125, 140),
             ("bench.inner", 100, 110)]
    got = trace_reduce.attribute_gaps([(100, 150)], spans, floor_ns=0)
    assert got == {"bench.inner": 10, "bench.engine_step": 10,
                   "bench.submit": 15, "unannotated": 15}
    assert trace_reduce.attribute_gaps(
        [(100, 150), (2000, 4000)], spans + [("bench.drain", 1900, 3000)]
    ) == {"between_ops": 50, "bench.drain": 1000, "unannotated": 1000}
    assert trace_reduce.short_name(
        "%fusion.12 = bf16[8,128]{1,0} fusion(%p0)") == "fusion.12"
    assert trace_reduce.reduce_trace({"devices": {}, "host_spans": []}) \
        is None


def _mix(name):
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json")) \
            as f:
        return json.load(f)


def test_chat_is_a_function_of_the_seed_and_keeps_its_clips():
    spec = _mix("serve-chat-r80")

    def take(seed, n=64):
        return list(itertools.islice(traffic.requests(spec, 50257, seed), n))

    a, b, c = take(2**31 + 7), take(2**31 + 7), take(11)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.due_s == y.due_s for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))
    for r in a + c:
        assert 16 <= len(r.prompt) <= 768 and 16 <= r.max_new <= 256
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 50257
    # stratified: every seed offers the same lengths and the same gaps
    # in every block of 32, in another order
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a[:32])) == sorted(map(key, c[:32]))
        assert sorted(map(key, a[:32])) == sorted(map(key, a[32:]))
        assert list(map(key, a[:32])) != list(map(key, c[:32]))
    med = sorted(len(r.prompt) for r in a[:32])[16]
    assert 220 <= med <= 290
    dues = [r.due_s for r in a]
    assert all(y > x for x, y in zip(dues, dues[1:]))
    rate = spec["arrivals"]["rate_rps"]
    assert dues[31] * rate == pytest.approx(32, rel=0.02)   # mean gap 1/rate
    # a backlog has no due times; a bursty mix (cv 2) keeps the mean
    assert take(3, 4)[0].due_s is not None
    sat = list(itertools.islice(
        traffic.requests(_mix("serve-chat-sat"), 50257, 3), 4))
    assert all(r.due_s is None for r in sat)
    bursty = {**spec, "arrivals": {"kind": "gamma", "cv": 2.0,
                                   "rate_rps": 1.0}}
    d = [r.due_s for r in itertools.islice(
        traffic.requests(bursty, 50257, 5), 32)]
    assert d[-1] == pytest.approx(32, rel=0.1)
    shared = {**spec, "shared_prefix": {"len": 32, "pools": 1}}
    s = list(itertools.islice(traffic.requests(shared, 50257, 5), 3))
    assert np.array_equal(s[0].prompt[:16], s[1].prompt[:16])


def test_packed_is_a_function_of_the_seed_and_fills_every_row():
    spec = {**_mix("train-packed-s1024"), "seq_len": 64,
            "doc_len": {"dist": "uniform", "low": 8, "high": 64},
            "rows_per_chunk": 8}

    def take(seed, n=5):
        return list(itertools.islice(
            traffic.document_batches(spec, 128, 4, seed), n))

    a, b, c = take(2**31 + 9), take(2**31 + 9), take(4)
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    for x, y in a:
        assert x.shape == (4, 64) and x.dtype == np.int32
        assert np.array_equal(x, y)                 # labels are the inputs
        assert (x == 127).any() and x.max() <= 127  # EOS between documents
    assert not np.array_equal(a[0][0], a[3][0])     # a fresh batch a step


def test_percentiles_gaps_and_lateness_on_a_hand_made_log():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(1, 102)), 90) == pytest.approx(91)
    assert stats.percentile([10, 20], 95) == pytest.approx(19.5)
    assert stats.percentile(list(np.arange(50.0)), 95) == pytest.approx(
        float(np.percentile(np.arange(50.0), 95)))
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    # request 0 was due at 1.0 but only sent at 1.4 (a stall): its time
    # to first token counts from 1.0. Request 2 never got a token.
    due = {0: 1.0, 1: 2.0, 2: 3.0}
    tokens = {0: [1.5, 1.6, 1.8], 1: [2.25, 2.35], 2: [], 9: [0.1, 0.2]}
    got = stats.request_latencies(due, tokens)
    assert got["ttft"] == pytest.approx([0.5, 0.25])
    assert got["gaps"] == pytest.approx([0.1, 0.2, 0.1])
    assert got["no_token"] == 1
    assert stats.iqr_share([10, 10, 10, 10, 11, 9]) == pytest.approx(
        0.05, abs=0.03)


def test_every_name_in_the_manifest_resolves_and_keeps_to_its_characters():
    bench = harness.Bench(ROOT)
    m = bench.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and all(x["bound"] <= 0.1 for x in e2e.values())
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in m["workloads"]]:
        assert NAME.match(n), n
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    for w in m["workloads"]:
        assert len(w["why"]) <= 200
        cell = bench.cell(w["name"])               # cell, config, mix files
        assert cell.config["reduced"] == [] and cell.config["assumed"]
        bench.driver(cell.spec["driver"])
        reported = {x["name"] for x in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for x in cell.per_layer:
            assert callable(bench.reader(x["name"]))
            assert x["moves"] in reported, (w["name"], x["name"])
    with pytest.raises(harness.MissingFile, match="no_such_reader.py"):
        bench.reader("no_such_reader.x")


def test_a_cell_a_mix_and_a_reader_added_as_files_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    m = harness.Bench(ROOT).manifest
    m["workloads"].append({"name": "gpt2-xl.serve-long-sat",
                           "config": "gpt2-xl", "traffic": "serve-long-sat",
                           "chips": 1, "why": "long prompts"})
    m["per_layer"].append({"name": "steps_in_window.sat", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine + scheduler",
                           "moves": "serve_tok_s",
                           "workloads": ["gpt2-xl.serve-long-sat"]})
    m["end_to_end"][1]["workloads"].append("gpt2-xl.serve-long-sat")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    b = root / "benchmarks"
    shutil.copy(b / "workloads" / "gpt2-xl.serve-chat-sat.json",
                b / "workloads" / "gpt2-xl.serve-long-sat.json")
    (b / "traffic" / "serve-long-sat.json").write_text(json.dumps({
        "kind": "requests", "stratify": 8, "arrivals": {"kind": "backlog"},
        "prompt_len": {"dist": "uniform", "low": 512, "high": 896},
        "output_len": {"dist": "uniform", "low": 16, "high": 64}}))
    (b / "layer_metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return ctx.get('steps')\n")
    bench = harness.Bench(str(root))
    cell = bench.cell("gpt2-xl.serve-long-sat")
    first = next(traffic.requests(cell.traffic, 50257, 1))
    assert 512 <= len(first.prompt) <= 896 and first.due_s is None
    got = harness.per_layer_values(bench, cell, {"steps": 7})
    assert got == {"steps_in_window.sat": {"value": 7.0, "unit": "steps"}}
    assert harness.per_layer_values(bench, cell, {}) == {}   # nothing read
    assert all(p.read_bytes() == data for p, data in before.items())


TINY = {"n_layer": 2, "n_embd": 32, "n_head": 4, "n_positions": 64,
        "vocab_size": 128, "layer_norm_epsilon": 1e-5, "dropout": 0.0}


def _ctx(tmp_path, spec, mix, seconds, trace):
    import jax

    from benchmarks.lib.device import CompileMeter

    cell = harness.Cell(name="tiny", chips=1, spec=spec, config=TINY,
                        traffic=mix, end_to_end=[], per_layer=[])
    lines = []
    return harness.RunContext(
        cell=cell, seed=2**31 + 3, seconds=seconds, trace=trace,
        devices=jax.devices()[:1], meter=CompileMeter(),
        t_process_start=time.perf_counter(), scratch=str(tmp_path),
        info=lines.append), lines


def test_train_driver_rehearsal_at_a_tiny_size(tmp_path):
    bench = harness.Bench(ROOT)
    spec = json.loads(json.dumps(
        bench.cell("gpt2-124m.train-packed-s1024").spec))
    spec["trainer"].update(batch=4, dtype="float32")
    spec["correctness"]["loss_tolerance"] = 1e-4
    mix = {**_mix("train-packed-s1024"), "seq_len": 32, "rows_per_chunk": 8,
           "doc_len": {"dist": "uniform", "low": 4, "high": 32}}
    ctx, lines = _ctx(tmp_path, spec, mix, 1.0, False)
    rec = bench.driver("train").run(ctx)
    assert all(c["ok"] for c in rec["checks"].values()), rec["checks"]
    c = rec["context"]
    assert rec["attempted"] == c["steps"] + 3 and rec["failed"] == 0
    assert c["steps"] >= 2 and 1.0 <= c["window_s"] < 5.0
    assert c["tokens_per_step"] == 4 * 32
    assert rec["end_to_end"]["train_tok_s"] == pytest.approx(
        c["steps"] * 128 / c["window_s"])
    assert rec["setup_s"] > 0 and lines and "train" in lines[0]
    assert bench.reader("train_step_ms")(c) == pytest.approx(
        1e3 * c["window_s"] / c["steps"])
    assert bench.reader("step_device_ms.train")(c) is None    # no trace
    with pytest.raises(KeyError, match="peaks.py"):
        bench.reader("mfu_pct")(c)                  # a CPU has no peak


@pytest.mark.parametrize("arrivals,trace", [
    ({"kind": "backlog"}, False),
    ({"kind": "gamma", "cv": 1.0, "rate_rps": 20.0, "lead_in_s": 0.3,
      "drain_s": 10.0}, True)])
def test_serve_driver_rehearsal_at_a_tiny_size(tmp_path, arrivals, trace):
    bench = harness.Bench(ROOT)
    spec = json.loads(json.dumps(bench.cell("gpt2-xl.serve-chat-sat").spec))
    spec["engine"].update(max_slots=2, num_blocks=24, block_size=4,
                          max_seq_len=64, kv_dtype="f32",
                          weights_dtype="f32")
    spec["correctness"].update(prompt_lens=[9, 16], half_width=8,
                               logits_tolerance=1e-4)
    spec["trace_seconds"] = 0.5
    mix = {"kind": "requests", "stratify": 4, "arrivals": arrivals,
           "prompt_len": {"dist": "uniform", "low": 4, "high": 24},
           "output_len": {"dist": "uniform", "low": 2, "high": 8}}
    ctx, lines = _ctx(tmp_path, spec, mix, 1.5, trace)
    rec = bench.driver("serve").run(ctx)
    assert all(c["ok"] for c in rec["checks"].values()), rec["checks"]
    assert rec["attempted"] > 0 and rec["failed"] == 0
    c = rec["context"]
    assert c["steps"] > 0 and c["max_slots"] == 2
    assert 0 < bench.reader("batch_occupancy_pct")(c) <= 100
    assert bench.reader("engine_step_ms.sat")(c) > 0
    assert c["trace"] is None           # a CPU trace has no TPU plane
    if arrivals["kind"] == "backlog":
        assert rec["end_to_end"]["serve_tok_s"] > 0
        assert bench.reader("gen_late_p95_ms")(c) is None
    else:
        assert c["window_s"] == pytest.approx(1.0)   # the untraced part
        assert rec["end_to_end"]["ttft_p90_ms"] > 0
        assert rec["end_to_end"]["gap_p95_ms"] > 0
        assert bench.reader("gen_late_p95_ms")(c) >= 0
        assert len(c["latencies"]["ttft"]) == rec["attempted"]


def test_run_py_on_a_cpu_exits_non_zero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "gpt2-124m.train-packed-s1024", "--seed",
         str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout
    assert "TPU" in p.stderr
