"""The cell ``ling-3.0-flash.serve-reason-sat`` on the CPU: every new
name resolves (asked of the manifest by NAME: nothing here pins the
manifest's full lists, which a later PR appends to), the configuration
keeps every published key, the traffic file is a function of the seed,
keeps its clips and staggers the first rows, the byte and FLOP counts
against hand arithmetic at the PUBLISHED widths and against the
engine's own statics (by ``jax.eval_shape``: nothing that large is
built), the three new readers on a hand-made run and ``None`` where
there is nothing to read, the driver rehearsed at a tiny size and its
check held to each control. Nothing here is a device number."""

import gc
import itertools
import json
import os
import time

import numpy as np
import pytest

from benchmarks.lib import harness, kda_moe_bytes, step_ring, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ling-3.0-flash.serve-reason-sat"
CONFIG = "ling-3.0-flash"
NEW = ("decode_kda_hbm_roofline_pct.sat",
       "prefill_kda_flops_roofline_pct.sat", "serve_kda_moe_mfu_pct.sat")
SHARED = ("engine_step_ms.sat", "batch_occupancy_pct", "step_device_ms.sat",
          "device_idle_pct.sat", "hbm_peak_gb.serve", "decode_device_ms.sat",
          "prefill_device_share_pct.sat", "engine_host_ms.sat",
          "host_syncs_per_step.sat", "h2d_kb_per_step.sat",
          "expert_load_max_over_mean.sat",
          "state_share_of_decode_bytes_pct.sat", "setup_import_s",
          "setup_build_s.serve", "setup_warmup_s.serve",
          "setup_trace_lower_s", "setup_compile_or_load_s",
          "setup_cache_misses")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers"]
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
    assert entry["reduced"] == REDUCED
    assert entry["source"].startswith("https://huggingface.co/inclusionAI/")
    w = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "serve-reason-sat", 1)
    assert all(len(x["why"]) <= 200 for x in (entry, w))
    assert "4x its share" in w["why"] and "6 of 42 layers" in w["why"]
    cell = bench.cell(CELL)
    assert cell.spec["driver"] == "serve_kda_moe"
    assert bench.driver("serve_kda_moe").run
    e = cell.spec["engine"]
    assert (e["prefix_cache"], e["chunked_prefill"], e["prefill_len"],
            e["max_seq_len"], e["block_size"], e["attn_kernel"],
            e["kv_dtype"], e["weights_dtype"]) == (
                False, True, 1024, 12288, 16, "xla", "bf16", "bf16")
    assert e["max_slots"] in (256, 192, 128, 96, 64)
    # 256 positions through the bucket, 32 through a chunk call that
    # starts past 0, then decode
    c = cell.spec["correctness"]
    assert c["chunk_calls"] == [256, 32] and c["prompt_lens"] == [320, 352]
    assert {"logits_tolerance", "state_tolerance", "expert_tolerance",
            "latent_tolerance", "latent_in_run_tolerance",
            "engine_tokens_floor", "routing_floor", "why"} <= set(c)
    # tens of live rows, from the first slot to the last
    assert 24 <= c["live_rows"] <= e["max_slots"]
    assert {x["name"] for x in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert {x["name"] for x in cell.per_layer} == set(SHARED) | set(NEW)
    names = [x["name"] for x in m["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)
    for name in NEW:
        x = next(x for x in m["per_layer"] if x["name"] == name)
        assert x["workloads"] == [CELL] and x["moves"] == "serve_tok_s"
        assert x["unit"] == "%" and callable(bench.reader(name))
    # the cells that were there report none of the new metrics
    for other in m["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {x["name"] for x in bench.metrics_for(
                other["name"], "per_layer")}


def test_the_configuration_keeps_every_published_key(published):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    assert published["source"] == row["source_url"]
    assert published["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in REDUCED:
            assert published["published"][key] == value
        else:
            assert published[key] == value, key      # lists copied whole
    assert [published[k] for k in REDUCED] == [6, 128, 39296, 0]
    assert (published["num_experts_published"], published["experts_first"],
            published["vocab_size_published"]) == (512, 0, 157184)
    assert published["first_k_dense_replace"] == 2
    # the floors: a whole period, four sparse layers after the dense
    # ones, at least 8 experts a layer, at least an eighth of the rows
    assert published["num_hidden_layers"] == published["layer_group_size"]
    assert published["num_hidden_layers"] - 2 >= 4
    assert published["vocab_size"] * 8 >= 157184
    # the kept layers clamp nothing
    assert not any(published["expert_swiglu_limit_list"][:6])
    assert not any(published["share_expert_swiglu_limit_list"][:6])
    assert "one of 4 chips" in published["deployment"]
    assert "7 pipeline stages" in published["deployment"]
    assert {"layer_pattern", "kda_gate", "qk_norm", "output_gate",
            "output_norm", "kv_heads", "rope", "router", "weights",
            "mtp"} <= set(published["assumed"])


def test_the_traffic_is_a_function_of_the_seed_and_staggers_the_first_rows(
        bench):
    mix = bench.cell(CELL).traffic
    assert {k: mix[k] for k in ("kind", "prompt_len", "output_len",
                                "stratify", "arrivals",
                                "initial_remaining")} == {
        "kind": "requests",
        "prompt_len": {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                       "low": 128, "high": 4096},
        "output_len": {"dist": "lognormal", "median": 2048, "sigma": 0.6,
                       "low": 512, "high": 8192},
        "stratify": 32, "arrivals": {"kind": "backlog"},
        "initial_remaining": "uniform"}
    assert "shared_prefix" not in mix
    from benchmarks.lib import backlog

    def take(seed, n=256, slots=128):
        return list(itertools.islice(backlog.staggered(
            traffic.requests(mix, 39296, seed), mix, slots, seed), n))

    a, b, c = take(2**31 + 7), take(2**31 + 7), take(11)
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))
    assert any(len(x.prompt) != len(y.prompt) for x, y in zip(a, c))
    # stratified: every seed offers the same multiset of lengths a block
    assert sorted(len(x.prompt) for x in a[:32]) == sorted(
        len(x.prompt) for x in c[:32])
    lens = [len(x.prompt) for x in a]
    assert min(lens) >= 128 and max(lens) == 4096
    assert 900 < float(np.median(lens)) < 1150
    assert 30000 < max(int(x.prompt.max()) for x in a) < 39296
    assert all(x.due_s is None for x in a)
    # every request after the first max_slots runs its whole length
    later = [x.max_new for x in a[128:]]
    assert min(later) >= 512 and max(later) <= 8192
    assert 1800 < float(np.median(later)) < 2300
    assert sorted(later[:32]) == sorted(x.max_new for x in c[128:160])
    # the first max_slots keep a SEEDED uniform share: the 128 equal
    # strata of (0, 1), dealt over the requests by the seed — about
    # half on average, at every stage, never nothing
    def shares(seed, got):
        plain = itertools.islice(traffic.requests(mix, 39296, seed), 128)
        return np.asarray([x.max_new / y.max_new
                           for x, y in zip(got[:128], plain)])

    share, other = shares(2**31 + 7, a), shares(11, c)
    assert np.allclose(np.sort(share), (np.arange(128) + 0.5) / 128,
                       atol=1e-3)
    assert np.allclose(np.sort(other), np.sort(share), atol=1e-3)
    # which request keeps which share is the seed's: no fixed pairing
    # of a length's rank with a share
    rank = np.argsort(np.argsort(share))
    assert (rank != np.argsort(np.argsort(other))).mean() > 0.9
    assert sorted(x.max_new for x in a[:128]) != sorted(
        x.max_new for x in c[:128])
    assert min(x.max_new for x in a[:128]) >= 1
    plain = list(itertools.islice(traffic.requests(mix, 39296, 11), 4))
    # the longest request fits the served context
    assert 4096 + 8192 <= bench.cell(CELL).spec["engine"]["max_seq_len"]
    with pytest.raises(ValueError, match="initial_remaining"):
        next(backlog.staggered(iter(plain), {"initial_remaining": "ramp"},
                              4, 0))


def test_kda_moe_bytes_by_hand_at_the_published_widths(published):
    """The issue's own arithmetic, term by term."""
    s = kda_moe_bytes._shape(published)
    assert s["kda"] == 5 * 2560 * 4096 + 2 * 2560 * 32 == 52_592_640
    assert s["mla"] == (2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32
                        + 4096 * 2560) == 31_965_184
    assert s["dense_mlp"] == 3 * 2560 * 6144 == 47_185_920
    assert s["expert"] == s["shared"] == 3 * 2560 * 768 == 5_898_240
    assert s["router"] == 2561 * 512
    assert (s["n_kda"], s["n_mla"], s["n_dense"], s["n_moe"]) == (5, 1, 2, 4)
    n = kda_moe_bytes.param_counts(published)
    assert n["experts"] == 4 * 128 * 5_898_240 == 3_019_898_880
    assert 3.63e9 < n["total"] < 3.65e9                     # 3.64B
    served = kda_moe_bytes.param_bytes(published, weight_itemsize=2)
    assert 7.68e9 < served < 7.70e9                         # 7.69 GB
    assert kda_moe_bytes.expert_param_bytes(published, 2) == 6_039_797_760
    assert kda_moe_bytes.token_table_bytes(published) == 39296 * 2560 * 4
    assert kda_moe_bytes.kv_bytes_per_token(published, 2) == 1152
    assert kda_moe_bytes.state_bytes_per_slot(published, 2) == 5 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2) == 10_854_400
    # a decode step of 128 rows at 2,500 positions each with 440 of the
    # 512 (layer, expert) pairs touched
    b = kda_moe_bytes.decode_step_bytes(
        published, served, 6_039_797_760, 440.0, 128 * 2500, 1152.0, 128,
        10_854_400)
    assert b["weights"] == served - 6_039_797_760 - 39296 * 2560 * 4 \
        + 128 * 2560 * 4
    assert b["experts"] == pytest.approx(6_039_797_760 * 440 / 512)
    assert b["state"] == 2 * 10_854_400 * 128
    assert b["latent"] == 128 * 2500 * 1152
    assert 9.4e9 < b["total"] < 9.8e9                       # the issue's 9.6
    # the delta rule: 3 dk x dv products a head a step; a chunk of 64
    assert kda_moe_bytes.delta_rule_products(published, None) == \
        32 * 3 * 128 * 128
    assert kda_moe_bytes.delta_rule_products(published, 64) == 32 * (
        2 * 32 * 128 + 32 * 256 + 3 * 128 * 128 + 32 * 128)
    f = kda_moe_bytes.flops_per_token(published, context=2000.0,
                                      held_routings=8.0, head=1.0)
    by_hand = 2.0 * (
        5 * (52_592_640 + 4 * 12288 + 32 * 3 * 16384)
        + 31_965_184 + 32 * (192 + 128) * 2000.0 + 2 * 47_185_920
        + 4 * (5_898_240 + 2561 * 512) + 8.0 * 5_898_240 + 39296 * 2560)
    assert f == pytest.approx(by_hand)
    chunked = kda_moe_bytes.flops_per_token(
        published, context=2000.0, held_routings=8.0, head=1.0, chunk=64)
    assert chunked - f == pytest.approx(2.0 * 5 * 32 * (
        2 * 32 * 128 + 32 * 256 + 32 * 128))


def test_the_engines_own_counts_agree_with_the_formulas(published):
    """The ring's statics are what the readers divide by: at the
    published widths (shapes only) they equal the shape formulas, and a
    live engine at a tiny size says the same of itself."""
    import dataclasses

    import jax

    from quintnet_tpu.models.ling_hybrid import (LingHybridConfig,
                                                 ling_hybrid_init)
    from quintnet_tpu.serve import ServeEngine, ling_hybrid_family
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    cfg = LingHybridConfig.from_dict(published)
    fam = ling_hybrid_family(cfg)
    shapes = jax.eval_shape(
        lambda k: (lambda p: quantize_params(
            p, present_targets(p, fam.weight_targets),
            make_weight_policy("bf16")))(ling_hybrid_init(k, cfg)),
        jax.random.key(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves) == kda_moe_bytes.param_counts(
        published)["total"]
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        kda_moe_bytes.param_bytes(published, weight_itemsize=2)
    experts = jax.tree.leaves(shapes["blocks"]["moe"]["moe"]["experts"])
    assert all(x.dtype == "bfloat16" for x in experts)
    assert sum(x.size * 2 for x in experts) == \
        kda_moe_bytes.expert_param_bytes(published, 2)
    assert (fam.n_layers, fam.latent, fam.state.n_layers) == (1, 576, 5)
    tiny = LingHybridConfig.tiny()
    tiny_d = dataclasses.asdict(tiny)
    gc.collect()
    eng = ServeEngine(ling_hybrid_family(tiny),
                      ling_hybrid_init(jax.random.key(0), tiny),
                      max_slots=2, block_size=4, num_blocks=16,
                      max_seq_len=32, kv_dtype="bf16", weights_dtype="bf16",
                      prefix_cache=False)
    static = eng.recorder.static
    assert static["param_bytes"] == kda_moe_bytes.param_bytes(
        tiny_d, weight_itemsize=2)
    assert static["expert_param_bytes"] == \
        kda_moe_bytes.expert_param_bytes(tiny_d, 2)
    assert static["kv_bytes_per_token"] == \
        kda_moe_bytes.kv_bytes_per_token(tiny_d, 2)
    assert static["state_bytes_per_slot"] == \
        kda_moe_bytes.state_bytes_per_slot(tiny_d, 2)
    assert static["paged_layers"] == tiny.periods
    assert step_ring.find_ring() is eng.recorder


class _Ring:
    def __init__(self, records, static):
        self._records, self.static = records, static

    def snapshot(self):
        return list(self._records)


def test_the_three_readers_on_a_hand_made_run(bench, published,
                                              monkeypatch):
    """Ten window steps and four traced ones of 128 decoding rows at
    2,500 positions each with 440 of the 512 (layer, expert) pairs
    touched; one 1,024-token chunk in window step 3 and in traced steps
    11 and 12, a quarter of its 32,768 routings on held experts."""
    served = kda_moe_bytes.param_bytes(published, weight_itemsize=2)
    static = {"param_bytes": served, "expert_param_bytes": 6_039_797_760,
              "kv_bytes_per_token": 1152.0,
              "state_bytes_per_slot": 10_854_400}
    steps, records = [], []
    for i in range(14):
        s = 100.0 + i
        if i < 10:
            steps.append((s, s + 0.9, 128))
        fed = i in (3, 11, 12)
        records.append({
            "t0": s + 0.1, "t1": s + 0.8, "decoding": 128,
            "decode_tokens": 128, "context_tokens": 128 * 2500,
            "prefill_tokens": 1024 if fed else 0,
            "prefill_chunks": 1 if fed else 0, "admitted": 0,
            "state_bytes": 2 * 10_854_400 * (128 + fed),
            "attrs": {"expert_rows": 1024.0 + (8192.0 if fed else 0.0),
                      "decode_expert_rows": 1024.0,
                      "decode_experts_touched": 440.0}})
    monkeypatch.setattr(step_ring, "find_ring",
                        lambda: _Ring(records, static))
    ctx = {"engine_steps": steps, "traced_steps": 4, "model": published,
           "device_kind": "TPU v5 lite", "window_s": 10.0,
           "trace": {"modules": {"jit_serve_decode(1)": (4, 4 * 0.025),
                                 "jit_serve_prefill_b1024(2)": (2, 0.200)}}}
    least = kda_moe_bytes.decode_step_bytes(
        published, served, 6_039_797_760, 440.0, 128 * 2500, 1152.0, 128,
        10_854_400)
    got = bench.reader(NEW[0])(ctx)
    assert got == pytest.approx(100 * least["total"] / 819e9 / 0.025)
    assert 45 < got < 49                            # 11.7 of 25 ms
    chunk = 1024 * kda_moe_bytes.flops_per_token(
        published, context=512.0, held_routings=8.0, head=1 / 1024,
        chunk=64)
    got = bench.reader(NEW[1])(ctx)
    assert got == pytest.approx(100 * 2 * chunk / 197e12 / 0.200)
    assert 0 < got < 100
    per_step = 128 * kda_moe_bytes.flops_per_token(
        published, context=2501.0, held_routings=8.0, head=1.0)
    got = bench.reader(NEW[2])(ctx)
    assert got == pytest.approx(
        100 * (10 * per_step + chunk) / (10.0 * 197e12))
    assert 0 < got < 1
    # the shared reader of the state's share: the mechanism's part of a
    # decode step's bytes, the steps that also prefilled left out
    share = bench.reader("state_share_of_decode_bytes_pct.sat")(ctx)
    assert share == pytest.approx(100 * 2 * 10_854_400 * 128 / (
        served + 128 * 2500 * 1152 + 2 * 10_854_400 * 128))
    assert 25 < share < 28
    # nothing to read: no trace, another family's model, a ring without
    # the state's or the experts' facts (the parent's), no ring at all
    for broken in ({**ctx, "trace": None}, {**ctx, "traced_steps": 0}):
        assert bench.reader(NEW[0])(broken) is None
        assert bench.reader(NEW[1])(broken) is None
    for other in (None, {"n_layer": 48}, {"kv_lora_rank": 512}):
        assert all(bench.reader(n)({**ctx, "model": other}) is None
                   for n in NEW)
    for gone in ("state_bytes_per_slot", "expert_param_bytes"):
        kept = static.pop(gone)
        assert bench.reader(NEW[0])(ctx) is None
        static[gone] = kept
    for r in records:
        r["attrs"] = {}
    assert all(bench.reader(n)(ctx) is None for n in NEW)
    for r in records:
        del r["attrs"]
    assert all(bench.reader(n)(ctx) is None for n in NEW)
    monkeypatch.setattr(step_ring, "find_ring", lambda: None)
    assert all(bench.reader(n)(ctx) is None for n in NEW)


def _tiny_cell(bench):
    import dataclasses

    from quintnet_tpu.models.ling_hybrid import LingHybridConfig

    spec = json.loads(json.dumps(bench.cell(CELL).spec))
    spec["engine"].update(max_slots=5, num_blocks=96, block_size=4,
                          max_seq_len=64, prefill_len=16, kv_dtype="f32",
                          weights_dtype="f32")
    # 16 through the widest bucket, 4 through a second chunk call that
    # starts past 0, the rest decoded; two copies of each row, on slots
    # 0, 1, 3 and 4
    spec["correctness"].update(
        prompt_lens=[27, 31], chunk_calls=[16, 4], live_rows=4,
        logits_tolerance=5e-5, engine_tokens_floor=1.0,
        state_tolerance=1e-5, latent_in_run_tolerance=1e-5,
        latent_tolerance=1e-5, expert_tolerance=1e-5, routing_floor=1.0)
    mix = {"kind": "requests", "stratify": 4,
           "arrivals": {"kind": "backlog"}, "initial_remaining": "uniform",
           "prompt_len": {"dist": "uniform", "low": 4, "high": 40},
           "output_len": {"dist": "uniform", "low": 8, "high": 20}}
    return harness.Cell(
        name="tiny", chips=1, spec=spec,
        config=dataclasses.asdict(LingHybridConfig.tiny()), traffic=mix,
        end_to_end=[], per_layer=[])


def test_serve_kda_moe_driver_rehearsal_at_a_tiny_size(bench, tmp_path):
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
    rec = bench.driver("serve_kda_moe").run(ctx)
    assert all(c["ok"] for c in rec["checks"].values()), rec["checks"]
    check = rec["checks"]["logits_vs_reference"]
    assert check["decode_steps"] == 31 - 20 and check["ref_std"] > 0
    assert check["token_rms_max"] < 5e-5
    assert check["state_rel_err"] < 1e-5 and check["state_norm"] > 0
    assert check["live_slots"] == [0, 1, 3, 4]
    # the engine's own compiled programs gave the logits' argmax at
    # every step of every live row, and left the reference's latent rows
    assert check["engine_tokens_agreeing_share"] == 1.0
    assert 0 < check["latent_rows_in_run_rel_err"] < 1e-5
    assert 0 < check["latent_rel_err_median"] < 1e-5
    assert check["expert_rel_err_median"] < 1e-5
    # 8 of 16 experts held, 2 of 4 groups kept: a token meets a held
    # expert unless it kept groups 2 and 3, one choice of six
    assert 150 < check["expert_tokens"] <= 256
    # in f32 no near-tie is decided the other way
    assert check["routings_agreeing_share"] == 1.0
    assert check["routings_compared"] == 2 * (7 * 2 + 4) * 5 * 4
    assert rec["checks"]["no_dropped_routing"]["routed"] > 0
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert rec["end_to_end"]["serve_tok_s"] > 0 and rec["setup_s"] > 0
    c = rec["context"]
    assert c["steps"] > 0 and c["max_slots"] == 5
    assert c["model"] is cell.config
    assert c["trace"] is None           # a CPU trace has no TPU plane
    assert 0 < bench.reader("batch_occupancy_pct")(c) <= 100
    assert bench.reader("engine_step_ms.sat")(c) > 0
    assert bench.reader("expert_load_max_over_mean.sat")(c) >= 1.0
    assert 0 < bench.reader("state_share_of_decode_bytes_pct.sat")(c) < 100
    assert bench.reader(NEW[0])(c) is None and bench.reader(
        NEW[1])(c) is None                          # no device trace
    with pytest.raises(KeyError, match="no published peaks"):
        bench.reader(NEW[2])(c)                     # a CPU has no peak
    serve = lines[0]["serve"]
    assert serve["prefill_chunks"] > 0          # prompts past 16 tokens
    assert serve["kv_bytes_per_token"] == 2 * 24 * 4
    assert serve["state_bytes_per_slot"] == 4 * (4 * 16 * 16 * 4
                                                 + 3 * 192 * 4)
    assert 0 < serve["decode_means"]["experts_touched"] <= 5 * 8
    assert serve["decode_means"]["tokens_without_held_group"] >= 0
    assert serve["preempted"] == 0


@pytest.mark.parametrize("control", [
    "bf16_state", "alpha_1", "beta_1", "tail_zeroed", "fp8_latent",
    "no_group_limit", "bias_in_weights", "no_routed", "programs_differ",
    "last_slot_state_lost"])
def test_the_check_refuses_each_control(bench, control, monkeypatch):
    """The controls the cell's limits are set against
    (tools/kda_moe_probe.py), at the tiny size in f32: the same engine
    with the state rounded to bf16, a decay or a write strength forced
    to 1, the conv tail lost between the chunk calls or a latent row
    rounded to float8 — or held to a reference without the group limit,
    with the bias in the weights or without the routed experts — fails
    limits it passes otherwise. What alters the programs is read on a
    fresh engine built while the alteration holds (the engine's own
    programs are traced once); ``programs_differ`` keeps the standing
    engine, so only the logits' programs change, and the state of the
    LAST slot lost after its first chunk call is a fault no row on the
    first slots would show."""
    import jax.numpy as jnp
    from jax import lax

    from benchmarks.drivers import serve_kda_moe as driver
    from quintnet_tpu.models import pangu_moe
    from quintnet_tpu.models.ling_hybrid import LingHybridConfig
    from quintnet_tpu.nn import kda

    cell = _tiny_cell(bench)
    cfg = LingHybridConfig.from_dict(cell.config)
    gc.collect()
    engine = driver.build_engine(cell.spec, cfg,
                                 driver.make_params(cfg, "f32", 3))
    want = driver.reference_side(engine.params, cell.config, cell.spec, 3)
    ok = driver.check_logits(engine, cell.config, cell.spec, 3,
                             reference_out=want)
    assert ok["ok"], ok
    kw = {}
    rebuild = control in ("bf16_state", "alpha_1", "beta_1", "fp8_latent")
    if control == "bf16_state":
        def through_bf16(rule):
            def rounded(*a, **k):
                o, state = rule(*a, **k)
                return o, lax.reduce_precision(state, exponent_bits=8,
                                               mantissa_bits=7)
            return rounded
        monkeypatch.setattr(kda, "delta_step", through_bf16(kda.delta_step))
        monkeypatch.setattr(kda, "delta_chunked",
                            through_bf16(kda.delta_chunked))
    elif control in ("alpha_1", "beta_1", "programs_differ"):
        gates = kda._gates

        def forced(p, x, dims):
            g, beta = gates(p, x, dims)
            return ((g, jnp.ones_like(beta)) if control == "beta_1"
                    else (jnp.zeros_like(g), beta))
        monkeypatch.setattr(kda, "_gates", forced)
    elif control == "tail_zeroed":
        def zero_tail(pool, row, start):
            if start == 0:
                pool.conv = pool.conv.at[:, row].set(0)
        kw["after_call"] = zero_tail
    elif control == "last_slot_state_lost":
        def lose_state(pool, row, start):
            if row == engine.max_slots - 1 and start == 0:
                pool.ssm = pool.ssm.at[:, row].set(0)
        kw["after_call"] = lose_state
    elif control == "fp8_latent":
        write = pangu_moe.latent_write
        monkeypatch.setattr(
            pangu_moe, "latent_write",
            lambda pool, layer, rows, *a, **k: write(
                pool, layer, lax.reduce_precision(
                    rows, exponent_bits=4, mantissa_bits=3), *a, **k))
    else:
        config = ({**cell.config, "n_group": 1, "topk_group": 1}
                  if control == "no_group_limit" else cell.config)
        other = driver.reference_side(
            engine.params, config, cell.spec, 3,
            **({"bias_in_weights": True} if control == "bias_in_weights"
               else {"routed": False} if control == "no_routed" else {}))
        want = other
    if rebuild:
        engine = driver.build_engine(cell.spec, cfg, engine.params)
    cut = driver.check_logits(engine, cell.config, cell.spec, 3,
                              reference_out=want, **kw)
    assert not cut["ok"], cut
    if control in ("bf16_state", "last_slot_state_lost"):
        # the logits hardly see it; the state's own limit does
        assert cut["state_rel_err"] > 100 * ok["state_rel_err"]
    elif control == "programs_differ":
        # the engine's own programs are sound: its state still is
        assert cut["engine_tokens_agreeing_share"] < 0.5
        assert cut["state_rel_err"] < 1e-5
    elif control == "fp8_latent":
        assert cut["latent_rel_err_median"] > 100 * ok[
            "latent_rel_err_median"]
        assert cut["latent_rows_in_run_rel_err"] > 100 * ok[
            "latent_rows_in_run_rel_err"]
    elif control == "bias_in_weights":
        # a bias of 0.01 on scores of 0.5: the expert leg sees it
        assert cut["expert_rel_err_median"] > 100 * ok[
            "expert_rel_err_median"]
    else:
        assert cut["token_rms_median"] > 50 * ok["token_rms_median"]
