"""The cell ``laguna-xs.2.serve-code-sat`` on the CPU: every new name
resolves, the configuration keeps every published key, the traffic file
is a function of the seed and keeps its clips, the byte and FLOP counts
against hand arithmetic at the PUBLISHED widths and against the
engine's own statics (by ``jax.eval_shape``: nothing that large is
built), the four new readers on a hand-made run and ``None`` where
there is nothing to read, the driver rehearsed at a tiny size and its
check held to each control. Nothing here is a device number."""

import gc
import json
import os
import time

import numpy as np
import pytest

from benchmarks.lib import harness, step_ring, traffic, window_moe_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "laguna-xs.2.serve-code-sat"
CONFIG = "laguna-xs.2"
NEW = ("decode_window_hbm_roofline_pct.sat",
       "prefill_window_moe_flops_roofline_pct.sat",
       "serve_window_moe_mfu_pct.sat",
       "window_share_of_decode_kv_bytes_pct.sat")
SHARED = ("engine_step_ms.sat", "batch_occupancy_pct", "step_device_ms.sat",
          "device_idle_pct.sat", "hbm_peak_gb.serve", "decode_device_ms.sat",
          "prefill_device_share_pct.sat", "engine_host_ms.sat",
          "host_syncs_per_step.sat", "h2d_kb_per_step.sat",
          "expert_load_max_over_mean.sat")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


@pytest.fixture(scope="module")
def published(bench):
    return bench.cell(CELL).config


def test_every_new_name_resolves(bench):
    m = bench.manifest
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"].startswith("https://huggingface.co/poolside/")
    w = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG,
                                                       "serve-code-sat", 1)
    assert all(len(x["why"]) <= 200 for x in (entry, w))
    cell = bench.cell(CELL)
    assert cell.spec["driver"] == "serve_window_moe"
    assert bench.driver("serve_window_moe").run
    e = cell.spec["engine"]
    assert (e["prefix_cache"], e["chunked_prefill"], e["prefill_len"],
            e["max_seq_len"], e["block_size"], e["attn_kernel"],
            e["kv_dtype"], e["weights_dtype"]) == (
                False, True, 1024, 17408, 16, "xla", "bf16", "bf16")
    assert e["max_slots"] in (64, 48, 32, 24, 16)
    # rows longer than two windows, a chunk call past 0, then decode
    c = cell.spec["correctness"]
    assert c["chunk_calls"] == [1024, 128] and min(c["prompt_lens"]) > (
        sum(c["chunk_calls"]) > 2 * cell.config["sliding_window"])
    assert {x["name"] for x in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert {x["name"] for x in cell.per_layer} == set(SHARED) | set(NEW)
    names = [x["name"] for x in m["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)
    for name in NEW:
        x = next(x for x in m["per_layer"] if x["name"] == name)
        assert x["workloads"] == [CELL] and x["moves"] == "serve_tok_s"
        assert callable(bench.reader(name))
    for x in m["per_layer"]:
        if x["name"] in SHARED:
            assert CELL in x["workloads"]
    # the older cells report none of the new metrics
    for other in m["workloads"]:
        if other["name"] != CELL:
            names = {x["name"] for x in bench.cell(other["name"]).per_layer}
            assert not names & set(NEW)


def test_the_configuration_keeps_every_published_key(published):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    assert published["source"] == row["source_url"]
    assert published["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert published["published"][key] == value == 40
        else:
            assert published[key] == value, key      # lists copied whole
    assert published["num_hidden_layers"] == 5
    n = published["num_hidden_layers"]
    assert published["layer_types"][:n] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert published["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4
    assert published["num_attention_heads_per_layer"][:n] == [
        48, 64, 64, 64, 48]
    assert "8-stage pipeline" in published["deployment"]
    assert {"gate", "router", "qk_norm", "window"} <= set(
        published["assumed"])
    assert "Laguna-S-2.1" in published["assumed"]["gate"]


def test_the_traffic_is_a_function_of_the_seed_and_keeps_its_clips(bench):
    mix = bench.cell(CELL).traffic
    assert {k: mix[k] for k in ("kind", "prompt_len", "output_len",
                                "stratify", "arrivals")} == {
        "kind": "requests",
        "prompt_len": {"dist": "lognormal", "median": 4096, "sigma": 0.7,
                       "low": 1024, "high": 16384},
        "output_len": {"dist": "lognormal", "median": 384, "sigma": 0.6,
                       "low": 64, "high": 1024},
        "stratify": 32, "arrivals": {"kind": "backlog"}}
    assert "shared_prefix" not in mix

    def take(seed, n=64):
        stream = traffic.requests(mix, 100352, seed)
        return [next(stream) for _ in range(n)]

    a, b, c = take(2**31 + 7), take(2**31 + 7), take(11)
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))
    assert any(len(x.prompt) != len(y.prompt) for x, y in zip(a, c))
    # stratified: every seed offers the same multiset of lengths a block
    assert sorted(len(x.prompt) for x in a[:32]) == sorted(
        len(x.prompt) for x in c[:32])
    lens = [len(x.prompt) for x in a]
    outs = [x.max_new for x in a]
    assert min(lens) >= 1024 and max(lens) == 16384
    assert min(outs) >= 64 and max(outs) <= 1024
    assert 3700 < float(np.median(lens)) < 4500
    assert 5000 < float(np.mean(lens)) < 5300          # about 5.1k
    assert 340 < float(np.median(outs)) < 430
    assert 80000 < max(int(x.prompt.max()) for x in a) < 100352
    assert all(x.due_s is None for x in a)
    # the longest request fits the served context; every context is at
    # least two windows long
    assert max(lens) + max(outs) <= bench.cell(CELL).spec["engine"][
        "max_seq_len"]
    assert min(lens) >= 2 * 512


def test_window_moe_bytes_by_hand_at_the_published_widths(published):
    """The table under the issue's configuration, recomputed."""
    full = 2048 * 6144 * 2 + 2 * 2048 * 1024 + 2048 * 48
    sliding = 2048 * 8192 * 2 + 2 * 2048 * 1024 + 2048 * 64
    assert (full, sliding) == (29_458_432, 37_879_808)     # 29.46M, 37.88M
    expert = 3 * 2048 * 512
    dense = 3 * 2048 * 8192
    assert (expert, dense) == (3_145_728, 50_331_648)
    n = window_moe_bytes.param_counts(published)
    assert n["experts"] == 4 * 256 * expert == 3_221_225_472
    assert n["matmul"] == (2 * full + 3 * sliding + dense + 4 * expert
                           + 4 * 256 * expert)
    assert n["other"] == (2 * 100352 * 2048 + 2048 + 5 * 2 * 2048
                          + 4 * 2048 * 256)
    assert 3.86e9 < n["total"] < 3.88e9                    # 3.87B
    served = window_moe_bytes.param_bytes(published, weight_itemsize=2)
    assert served == 2 * n["matmul"] + 4 * n["other"]
    assert 8.56e9 < served < 8.58e9                        # 8.57 GB
    assert window_moe_bytes.expert_param_bytes(published, 2) == 6_442_450_944
    assert window_moe_bytes.row_bytes(published, 2) == 4096
    assert window_moe_bytes.kv_bytes_per_token(published, 2) == 8192
    assert window_moe_bytes.window_bytes_per_slot(
        published, 2, 528) == 3 * 528 * 4096 == 6_488_064   # 6.5 MB
    assert window_moe_bytes.token_table_bytes(published) == 100352 * 2048 * 4
    # a decode step of 32 rows at 5,400 positions, 650 of the 1,024
    # (layer, expert) pairs touched
    t = window_moe_bytes.decode_step_bytes(
        published, served, 6_442_450_944, 650.0, 32 * 5400 * 2,
        32 * 512 * 3, 4096.0, 32)
    assert t["experts"] == 6_442_450_944 * 650 / 1024
    assert t["weights"] == (served - 6_442_450_944 - 822_083_584
                            + 32 * 2048 * 4)
    assert t["global_kv"] == 32 * 5400 * 2 * 4096
    assert t["window_kv"] == 32 * 512 * 3 * 4096
    assert 8.0 < 1e3 * t["total"] / 819e9 < 9.0            # ms, at least
    # a token's FLOPs: two a parameter it passes through, the counted
    # routings, the head where read, 4 x heads x 128 a key
    f = window_moe_bytes.flops_per_token(
        published, context=1000.0, window_context=400.0, routings=32.0,
        head=1.0)
    by_hand = 2 * (2 * full + 3 * sliding + dense
                   + 4 * (expert + 2048 * 256) + 32 * expert
                   + 100352 * 2048
                   + 2 * 2 * 48 * 128 * 1000 + 3 * 2 * 64 * 128 * 400)
    assert f == by_hand
    assert window_moe_bytes.windowed_context(512, 512) == 256.5
    assert window_moe_bytes.windowed_context(1024, 512) == 384.25
    assert window_moe_bytes.windowed_context(100, 512) == 50.5


def test_the_engines_own_counts_agree_with_the_formulas(published):
    """The ring's statics are what the readers divide by: at the
    published widths (shapes only) they equal the shape formulas, and a
    live engine at a tiny size says the same of itself."""
    import jax

    from quintnet_tpu.models.laguna import LagunaConfig, laguna_init
    from quintnet_tpu.serve import ServeEngine, laguna_family
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    cfg = LagunaConfig.from_dict(published)
    fam = laguna_family(cfg)
    shapes = jax.eval_shape(
        lambda k: (lambda p: quantize_params(
            p, present_targets(p, fam.weight_targets),
            make_weight_policy("bf16")))(laguna_init(k, cfg)),
        jax.random.key(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves) == window_moe_bytes.param_counts(
        published)["total"]
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        window_moe_bytes.param_bytes(published, weight_itemsize=2)
    experts = jax.tree.leaves(shapes["blocks"]["experts"])
    assert all(x.dtype == "bfloat16" for x in experts)
    assert sum(x.size * 2 for x in experts) == \
        window_moe_bytes.expert_param_bytes(published, 2)
    assert (fam.n_layers, fam.window.n_layers, fam.window.ring) == (2, 3,
                                                                    528)
    tiny = LagunaConfig.tiny()
    tiny_d = tiny.to_dict()
    gc.collect()
    eng = ServeEngine(laguna_family(tiny, block_size=4),
                      laguna_init(jax.random.key(0), tiny),
                      max_slots=2, block_size=4, num_blocks=16,
                      max_seq_len=32, kv_dtype="bf16", weights_dtype="bf16",
                      prefix_cache=False)
    static = eng.recorder.static
    assert static["param_bytes"] == window_moe_bytes.param_bytes(
        tiny_d, weight_itemsize=2)
    assert static["expert_param_bytes"] == \
        window_moe_bytes.expert_param_bytes(tiny_d, 2)
    assert static["kv_bytes_per_token"] == \
        window_moe_bytes.kv_bytes_per_token(tiny_d, 2)
    assert static["window_bytes_per_slot"] == \
        window_moe_bytes.window_bytes_per_slot(tiny_d, 2, 12)
    assert step_ring.find_ring() is eng.recorder


class _Ring:
    def __init__(self, records, static):
        self._records, self.static = records, static

    def snapshot(self):
        return list(self._records)


def test_the_four_readers_on_a_hand_made_run(bench, published, monkeypatch):
    """Ten window steps and four traced ones of 32 decoding rows at
    5,400 positions each with 650 of the 1,024 (layer, expert) pairs
    touched; one 1,024-token chunk in window step 3 and in traced steps
    11 and 12, its 32,768 routings counted."""
    served = window_moe_bytes.param_bytes(published, weight_itemsize=2)
    static = {"param_bytes": served, "expert_param_bytes": 6_442_450_944,
              "kv_bytes_per_token": 8192.0}
    steps, records = [], []
    for i in range(14):
        s = 100.0 + i
        if i < 10:
            steps.append((s, s + 0.9, 32))
        fed = i in (3, 11, 12)
        records.append({
            "t0": s + 0.1, "t1": s + 0.8, "decoding": 32,
            "decode_tokens": 32, "context_tokens": 32 * 5400,
            "prefill_tokens": 1024 if fed else 0,
            "prefill_chunks": 1 if fed else 0, "admitted": 0,
            "attrs": {"global_rows": 32 * 5400 * 2,
                      "window_rows": 32 * 512 * 3,
                      "expert_rows": 1024.0 + (32768.0 if fed else 0.0),
                      "decode_expert_rows": 1024.0,
                      "decode_experts_touched": 650.0}})
    monkeypatch.setattr(step_ring, "find_ring",
                        lambda: _Ring(records, static))
    ctx = {"engine_steps": steps, "traced_steps": 4, "model": published,
           "device_kind": "TPU v5 lite", "window_s": 10.0,
           "trace": {"modules": {"jit_serve_decode(1)": (4, 4 * 0.025),
                                 "jit_serve_prefill_b1024(2)": (2, 0.100)}}}
    least = window_moe_bytes.decode_step_bytes(
        published, served, 6_442_450_944, 650.0, 32 * 5400 * 2,
        32 * 512 * 3, 4096.0, 32)
    got = bench.reader(NEW[0])(ctx)
    assert got == pytest.approx(100 * least["total"] / 819e9 / 0.025)
    assert 32 < got < 36                            # 8.5 of 25 ms
    chunk = 1024 * window_moe_bytes.flops_per_token(
        published, context=512.0, window_context=384.25, routings=32.0,
        head=1 / 1024)
    got = bench.reader(NEW[1])(ctx)
    assert got == pytest.approx(100 * 2 * chunk / 197e12 / 0.100)
    assert 0 < got < 100
    per_step = 32 * window_moe_bytes.flops_per_token(
        published, context=5401.0, window_context=512.0, routings=32.0,
        head=1.0)
    got = bench.reader(NEW[2])(ctx)
    assert got == pytest.approx(
        100 * (10 * per_step + chunk) / (10.0 * 197e12))
    assert 0 < got < 1
    assert bench.reader(NEW[3])(ctx) == pytest.approx(
        100 * 512 * 3 / (512 * 3 + 5400 * 2))       # 12.4%
    # nothing to read: no trace, another family's model, a ring without
    # the window counters (the parent's), no ring at all
    for broken in ({**ctx, "trace": None}, {**ctx, "traced_steps": 0}):
        assert bench.reader(NEW[0])(broken) is None
        assert bench.reader(NEW[1])(broken) is None
    for other in (None, {"n_layer": 48}, {"kv_lora_rank": 512}):
        assert all(bench.reader(n)({**ctx, "model": other}) is None
                   for n in NEW[:3])
    static.pop("expert_param_bytes")
    assert bench.reader(NEW[0])(ctx) is None
    for r in records:
        r["attrs"] = {"expert_rows": 1.0, "decode_expert_rows": 1.0,
                      "decode_experts_touched": 1.0}
    assert bench.reader(NEW[0])(ctx) is None        # no window counters
    assert bench.reader(NEW[2])(ctx) is None
    assert bench.reader(NEW[3])(ctx) is None
    for r in records:
        r["attrs"] = {}
    assert all(bench.reader(n)(ctx) is None for n in NEW)
    for r in records:
        del r["attrs"]
    assert all(bench.reader(n)(ctx) is None for n in NEW)
    monkeypatch.setattr(step_ring, "find_ring", lambda: None)
    assert all(bench.reader(n)(ctx) is None for n in NEW)


def _tiny_cell(bench):
    from quintnet_tpu.models.laguna import LagunaConfig

    spec = json.loads(json.dumps(bench.cell(CELL).spec))
    spec["engine"].update(max_slots=3, num_blocks=96, block_size=4,
                          max_seq_len=64, prefill_len=16, kv_dtype="f32",
                          weights_dtype="f32")
    # past two windows of 8: 16 through the widest bucket, 4 through a
    # second chunk call of another bucket, the rest decoded
    spec["correctness"].update(prompt_lens=[27, 31], chunk_calls=[16, 4],
                               logits_tolerance=5e-5, expert_tolerance=1e-5,
                               routing_floor=1.0)
    mix = {"kind": "requests", "stratify": 4,
           "arrivals": {"kind": "backlog"},
           "prompt_len": {"dist": "uniform", "low": 4, "high": 40},
           "output_len": {"dist": "uniform", "low": 2, "high": 8}}
    return harness.Cell(name="tiny", chips=1, spec=spec,
                        config=LagunaConfig.tiny().to_dict(), traffic=mix,
                        end_to_end=[], per_layer=[])


def test_serve_window_moe_driver_rehearsal_at_a_tiny_size(bench, tmp_path):
    import jax

    from benchmarks.lib.device import CompileMeter

    lines = []
    gc.collect()
    cell = _tiny_cell(bench)
    ctx = harness.RunContext(
        cell=cell, seed=2**31 + 5, seconds=1.5, trace=False,
        devices=jax.devices()[:1], meter=CompileMeter(),
        t_process_start=time.perf_counter(), scratch=str(tmp_path),
        info=lines.append)
    rec = bench.driver("serve_window_moe").run(ctx)
    assert all(c["ok"] for c in rec["checks"].values()), rec["checks"]
    check = rec["checks"]["logits_vs_reference"]
    assert check["decode_steps"] == 31 - 20 and check["ref_std"] > 0
    assert check["token_rms_max"] < 5e-5
    assert check["expert_rel_err_median"] < 1e-5
    assert check["expert_tokens"] == 256
    # in f32 no near-tie is decided the other way
    assert check["routings_agreeing_share"] == 1.0
    assert check["routings_compared"] == (7 * 2 + 4) * 4 * 4
    assert rec["checks"]["no_dropped_routing"]["routed"] > 0
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert rec["end_to_end"]["serve_tok_s"] > 0 and rec["setup_s"] > 0
    c = rec["context"]
    assert c["steps"] > 0 and c["max_slots"] == 3
    assert c["model"] is cell.config
    assert c["trace"] is None           # a CPU trace has no TPU plane
    assert 0 < bench.reader("batch_occupancy_pct")(c) <= 100
    assert bench.reader("engine_step_ms.sat")(c) > 0
    assert bench.reader("expert_load_max_over_mean.sat")(c) >= 1.0
    assert bench.reader(NEW[0])(c) is None and bench.reader(
        NEW[1])(c) is None                          # no device trace
    with pytest.raises(KeyError, match="no published peaks"):
        bench.reader(NEW[2])(c)                     # a CPU has no peak
    share = bench.reader(NEW[3])(c)
    assert 0 < share < 60                           # under 3 of 5 layers
    serve = lines[0]["serve"]
    assert serve["prefill_chunks"] > 0          # prompts past 16 tokens
    assert serve["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert serve["window_bytes_per_slot"] == 3 * 12 * 2 * 2 * 16 * 4
    assert 0 < serve["decode_means"]["experts_touched"] <= 4 * 16
    assert 0 < serve["decode_means"]["window_rows"] <= 3 * 8 * 3
    assert serve["preempted"] == 0


@pytest.mark.parametrize("control", [
    "window_off", "rope_swapped", "no_gate", "no_attention_factor"])
def test_the_check_refuses_each_control(bench, control):
    """The controls the cell's limits are set against, at the tiny
    size: the same engine held to a reference that ignores the window,
    swaps the rotary settings, or leaves the gate or the attention
    factor out fails the limits it passes otherwise."""
    from benchmarks.drivers import serve_window_moe as driver
    from quintnet_tpu.models.laguna import LagunaConfig

    cell = _tiny_cell(bench)
    cfg = LagunaConfig.from_dict(cell.config)
    gc.collect()
    engine = driver.build_engine(cell.spec, cfg,
                                 driver.make_params(cfg, "f32", 3))
    ok = driver.check_logits(engine, cell.config, cell.spec, 3)
    assert ok["ok"], ok
    cut = driver.check_logits(
        engine, cell.config, cell.spec, 3,
        reference_out=driver.reference_side(
            engine.params, cell.config, cell.spec, 3, controls=(control,)))
    assert not cut["ok"]
    assert cut["token_rms_median"] > 50 * ok["token_rms_median"]
