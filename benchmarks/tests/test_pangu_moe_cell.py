"""The cell ``openpangu-ultra-moe-718b.serve-doc-sat`` on the CPU: every
new name resolves, the traffic file is a function of the seed and keeps
its clips, the byte and FLOP counts against hand arithmetic at the
PUBLISHED widths and against the engine's own statics (by
``jax.eval_shape``: nothing that large is built), the four new readers
on a hand-made run and ``None`` where there is nothing to read, the
driver rehearsed at a tiny size. Nothing here is a device number."""

import gc
import json
import os
import time

import numpy as np
import pytest

from benchmarks.lib import harness, moe_mla_bytes, step_ring, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "openpangu-ultra-moe-718b.serve-doc-sat"
CONFIG = "openpangu-ultra-moe-718b"
NEW = ("decode_latent_hbm_roofline_pct.sat",
       "prefill_moe_flops_roofline_pct.sat", "serve_mfu_pct.sat",
       "expert_load_max_over_mean.sat")
SHARED = ("engine_step_ms.sat", "batch_occupancy_pct", "step_device_ms.sat",
          "device_idle_pct.sat", "hbm_peak_gb.serve", "decode_device_ms.sat",
          "prefill_device_share_pct.sat", "engine_host_ms.sat",
          "host_syncs_per_step.sat", "h2d_kb_per_step.sat")
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
    assert entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert entry["source"].startswith("https://huggingface.co/"
                                      "FreedomIntelligence/")
    w = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG,
                                                       "serve-doc-sat", 1)
    assert all(len(x["why"]) <= 200 for x in (entry, w))
    cell = bench.cell(CELL)
    assert cell.spec["driver"] == "serve_moe_mla"
    assert bench.driver("serve_moe_mla").run
    e = cell.spec["engine"]
    assert (e["prefix_cache"], e["chunked_prefill"], e["prefill_len"],
            e["max_seq_len"], e["block_size"], e["attn_kernel"]) == (
                False, True, 1024, 5120, 16, "xla")
    assert e["num_blocks"] % e["max_slots"] == 0
    assert {x["name"] for x in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert {x["name"] for x in cell.per_layer} == set(SHARED) | set(NEW)
    for name in NEW:
        x = next(x for x in m["per_layer"] if x["name"] == name)
        assert x["workloads"] == [CELL] and x["moves"] == "serve_tok_s"
        assert callable(bench.reader(name))
    for x in m["per_layer"]:
        if x["name"] in SHARED:
            assert x["workloads"][-1] == CELL
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
                   if r["name"] == "openPangu-Ultra-MoE-718B")
    assert published["source"] == row["source_url"]
    reduced = set(published["reduced"])
    for key, value in row["config"].items():
        if key in reduced:
            assert published["published"][key] == value
        else:
            assert published[key] == value, key
    assert {k: published[k] for k in published["reduced"]} == {
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "n_routed_experts": 16, "vocab_size": 19200,
        "num_nextn_predict_layers": 0}
    assert published["n_routed_experts_published"] == 256
    assert published["vocab_size_published"] == 153600
    assert "16 chips" in published["deployment"]
    assert {"router", "rope", "norms", "mtp", "serving", "weights"} <= set(
        published["assumed"])


def test_the_traffic_is_a_function_of_the_seed_and_keeps_its_clips(bench):
    mix = bench.cell(CELL).traffic
    assert mix["arrivals"]["kind"] == "backlog"

    def take(seed, n=64):
        stream = traffic.requests(mix, 19200, seed)
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
    assert min(lens) >= 128 and max(lens) <= 4096 and max(lens) > 1024
    assert min(outs) >= 64 and max(outs) <= 1024
    assert 900 < float(np.median(lens)) < 1150
    assert 340 < float(np.median(outs)) < 430
    assert max(int(x.prompt.max()) for x in a) < 19200
    assert all(x.due_s is None for x in a)
    # the longest request fits the served context
    assert max(lens) + max(outs) <= bench.cell(CELL).spec["engine"][
        "max_seq_len"]


def test_moe_mla_bytes_by_hand_at_the_published_widths(published):
    """The table under the issue's Motivation, recomputed."""
    mla = (7680 * 1536 + 1536 * 24576 + 7680 * 576 + 512 * 32768
           + 16384 * 7680)
    assert mla == 196_575_232                              # 196.6M
    expert = 3 * 7680 * 2048
    assert expert == 47_185_920                            # 47.19M
    dense = 3 * 7680 * 18432
    assert dense == 424_673_280                            # 424.7M
    n = moe_mla_bytes.param_counts(published)
    assert n["experts"] == 4 * 16 * expert
    assert n["matmul"] == 5 * mla + dense + 4 * expert + 4 * 16 * expert
    norms = 5 * (4 * 7680 + 1536 + 512) + 7680
    assert n["other"] == 2 * 19200 * 7680 + norms + 4 * 7680 * 256
    assert 4.91e9 < n["total"] < 4.93e9                    # 4.92B
    one_moe_layer = mla + expert + 7680 * 256 + 16 * expert
    assert 1000.6e6 < one_moe_layer < 1000.8e6             # 1,000.7M
    served = moe_mla_bytes.param_bytes(published, weight_itemsize=2)
    assert served == 2 * n["matmul"] + 4 * n["other"]
    assert 10.43e9 < served < 10.45e9                      # 10.44 GB
    assert moe_mla_bytes.expert_param_bytes(published, 2) == 6_039_797_760
    assert moe_mla_bytes.kv_bytes_per_token(published, 2) == 5 * 576 * 2
    assert moe_mla_bytes.token_table_bytes(published) == 19200 * 7680 * 4
    # a decode step of 64 rows at 1,700 positions, every held expert
    # touched: the weights less the table, the experts, the latent rows
    t = moe_mla_bytes.decode_step_bytes(
        published, served, 6_039_797_760, 64.0, 64 * 1700, 5760.0, 64)
    assert t["experts"] == 6_039_797_760
    assert t["weights"] == served - 6_039_797_760 - 589_824_000 + 64 * 30720
    assert t["latent"] == 64 * 1700 * 5760
    assert 10.4 < 1e3 * t["total"] / 819e9 < 12.9          # ms, at least
    half = moe_mla_bytes.decode_step_bytes(
        published, served, 6_039_797_760, 32.0, 64 * 1700, 5760.0, 64)
    assert half["experts"] == 6_039_797_760 / 2
    # a token's FLOPs: two a parameter it passes through, the counted
    # routings, the head where read, 128 heads x (192 + 128) a position
    f = moe_mla_bytes.flops_per_token(published, context=1000.0,
                                      held_routings=2.0, head=1.0)
    by_hand = 2 * (5 * mla + dense + 4 * (expert + 7680 * 256)
                   + 2 * expert + 19200 * 7680
                   + 5 * 128 * 320 * 1000)
    assert f == by_hand
    assert moe_mla_bytes.flops_per_token(
        published, context=1000.0, held_routings=0.0, head=0.0) == (
            by_hand - 2 * (2 * expert + 19200 * 7680))


def test_the_engines_own_counts_agree_with_the_formulas(published):
    """The ring's statics are what the readers divide by: at the
    published widths (shapes only) they equal the shape formulas, and a
    live engine at a tiny size says the same of itself."""
    import jax

    from quintnet_tpu.models.pangu_moe import PanguMoEConfig, pangu_moe_init
    from quintnet_tpu.serve import ServeEngine, pangu_moe_family
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    cfg = PanguMoEConfig.from_dict(published)
    fam = pangu_moe_family(cfg)
    shapes = jax.eval_shape(
        lambda k: (lambda p: quantize_params(
            p, present_targets(p, fam.weight_targets),
            make_weight_policy("bf16")))(pangu_moe_init(k, cfg)),
        jax.random.key(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves) == moe_mla_bytes.param_counts(
        published)["total"]
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        moe_mla_bytes.param_bytes(published, weight_itemsize=2)
    experts = jax.tree.leaves(shapes["blocks"]["moe"]["moe"]["experts"])
    assert all(x.dtype == "bfloat16" for x in experts)
    assert sum(x.size * 2 for x in experts) == \
        moe_mla_bytes.expert_param_bytes(published, 2)
    assert fam.n_layers * fam.latent * 2 == \
        moe_mla_bytes.kv_bytes_per_token(published, 2)
    tiny = PanguMoEConfig.tiny()
    tiny_d = {f: getattr(tiny, f) for f in tiny.__dataclass_fields__}
    gc.collect()
    eng = ServeEngine(pangu_moe_family(tiny),
                      pangu_moe_init(jax.random.key(0), tiny),
                      max_slots=2, block_size=4, num_blocks=16,
                      max_seq_len=32, kv_dtype="bf16", weights_dtype="bf16",
                      prefix_cache=False)
    static = eng.recorder.static
    assert static["param_bytes"] == moe_mla_bytes.param_bytes(
        tiny_d, weight_itemsize=2)
    assert static["expert_param_bytes"] == \
        moe_mla_bytes.expert_param_bytes(tiny_d, 2)
    assert static["kv_bytes_per_token"] == \
        moe_mla_bytes.kv_bytes_per_token(tiny_d, 2)
    assert step_ring.find_ring() is eng.recorder


class _Ring:
    def __init__(self, records, static):
        self._records, self.static = records, static

    def snapshot(self):
        return list(self._records)


def test_the_four_readers_on_a_hand_made_run(bench, published, monkeypatch):
    """Ten window steps and four traced ones of 64 decoding rows at
    1,700 positions each with 40 of the 64 (layer, expert) pairs
    touched; one 1,024-token chunk in window step 3 and in traced steps
    11 and 12, 520 of its routings on held experts."""
    served = moe_mla_bytes.param_bytes(published, weight_itemsize=2)
    static = {"param_bytes": served, "expert_param_bytes": 6_039_797_760,
              "kv_bytes_per_token": 5760.0}
    steps, records = [], []
    for i in range(14):
        s = 100.0 + i
        if i < 10:
            steps.append((s, s + 0.9, 64))
        fed = i in (3, 11, 12)
        records.append({
            "t0": s + 0.1, "t1": s + 0.8, "decoding": 64,
            "decode_tokens": 64, "context_tokens": 64 * 1700,
            "prefill_tokens": 1024 if fed else 0,
            "prefill_chunks": 1 if fed else 0, "admitted": 0,
            "attrs": {"expert_rows": 128.0 + (520.0 if fed else 0.0),
                      "decode_expert_rows": 128.0,
                      "decode_experts_touched": 40.0,
                      "decode_held_expert_tokens": [12.0] + [4.0] * 7
                      + [11.0] * 8}})
    monkeypatch.setattr(step_ring, "find_ring",
                        lambda: _Ring(records, static))
    ctx = {"engine_steps": steps, "traced_steps": 4, "model": published,
           "device_kind": "TPU v5 lite", "window_s": 10.0,
           "trace": {"modules": {"jit_serve_decode(1)": (4, 4 * 0.025),
                                 "jit_serve_prefill_b1024(2)": (2, 0.100)}}}
    least = moe_mla_bytes.decode_step_bytes(
        published, served, 6_039_797_760, 40.0, 64 * 1700, 5760.0, 64)
    got = bench.reader(NEW[0])(ctx)
    assert got == pytest.approx(100 * least["total"] / 819e9 / 0.025)
    assert 40 < got < 45                            # 10.6 of 25 ms
    flops = 2048 * moe_mla_bytes.flops_per_token(
        published, context=512.0, held_routings=520 / 1024, head=1 / 1024)
    got = bench.reader(NEW[1])(ctx)
    assert got == pytest.approx(100 * flops / 197e12 / 0.100)
    assert 35 < got < 37                            # 36 ms of 100 at peak
    per_step = 64 * moe_mla_bytes.flops_per_token(
        published, context=1701.0, held_routings=2.0, head=1.0)
    chunk = 1024 * moe_mla_bytes.flops_per_token(
        published, context=512.0, held_routings=520 / 1024, head=1 / 1024)
    got = bench.reader(NEW[2])(ctx)
    assert got == pytest.approx(
        100 * (10 * per_step + chunk) / (10.0 * 197e12))
    assert 0 < got < 1
    assert bench.reader(NEW[3])(ctx) == pytest.approx(12.0 * 16 / 128.0)
    # nothing to read: no trace, another family's model, a ring without
    # the expert counters (the parent's), no ring at all
    for broken in ({**ctx, "trace": None}, {**ctx, "traced_steps": 0}):
        assert bench.reader(NEW[0])(broken) is None
        assert bench.reader(NEW[1])(broken) is None
    for other in (None, {"n_layer": 48}, {"layer_types": ["mamba"]}):
        assert all(bench.reader(n)({**ctx, "model": other}) is None
                   for n in NEW[:3])
    static.pop("expert_param_bytes")
    assert bench.reader(NEW[0])(ctx) is None
    for r in records:
        r["attrs"] = {}
    assert all(bench.reader(n)(ctx) is None for n in NEW)
    for r in records:
        del r["attrs"]
    assert all(bench.reader(n)(ctx) is None for n in NEW)
    monkeypatch.setattr(step_ring, "find_ring", lambda: None)
    assert all(bench.reader(n)(ctx) is None for n in NEW)


TINY = {"vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 5,
        "first_k_dense_replace": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_theta": 10000.0, "n_routed_experts": 8,
        "n_routed_experts_published": 16, "experts_first": 4,
        "n_shared_experts": 1, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-5, "max_position_embeddings": 256}


def _tiny_cell(bench):
    spec = json.loads(json.dumps(bench.cell(CELL).spec))
    spec["engine"].update(max_slots=3, num_blocks=96, block_size=4,
                          max_seq_len=64, prefill_len=16, kv_dtype="f32",
                          weights_dtype="f32")
    spec["correctness"].update(prompt_lens=[27, 31], chunk_calls=[16, 4],
                               logits_tolerance=5e-5, expert_tolerance=1e-5,
                               routing_floor=1.0)
    mix = {"kind": "requests", "stratify": 4,
           "arrivals": {"kind": "backlog"},
           "prompt_len": {"dist": "uniform", "low": 4, "high": 40},
           "output_len": {"dist": "uniform", "low": 2, "high": 8}}
    return harness.Cell(name="tiny", chips=1, spec=spec, config=TINY,
                        traffic=mix, end_to_end=[], per_layer=[])


def test_serve_moe_mla_driver_rehearsal_at_a_tiny_size(bench, tmp_path):
    import jax

    from benchmarks.lib.device import CompileMeter

    lines = []
    gc.collect()
    ctx = harness.RunContext(
        cell=_tiny_cell(bench), seed=2**31 + 5, seconds=1.5, trace=False,
        devices=jax.devices()[:1], meter=CompileMeter(),
        t_process_start=time.perf_counter(), scratch=str(tmp_path),
        info=lines.append)
    rec = bench.driver("serve_moe_mla").run(ctx)
    assert all(c["ok"] for c in rec["checks"].values()), rec["checks"]
    check = rec["checks"]["logits_vs_reference"]
    assert check["decode_steps"] == 31 - 20 and check["ref_std"] > 0
    assert check["max_abs_diff"] < 2e-4
    assert check["token_rms_median"] < 5e-5
    assert check["expert_rel_err_median"] < 1e-5
    assert 64 < check["expert_tokens"] <= 256
    # in f32 no near-tie is decided the other way
    assert check["routings_agreeing_share"] == 1.0
    assert check["routings_compared"] == (7 * 2 + 4) * 3 * 4
    assert rec["checks"]["no_dropped_routing"]["routed"] > 0
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert rec["end_to_end"]["serve_tok_s"] > 0 and rec["setup_s"] > 0
    c = rec["context"]
    assert c["steps"] > 0 and c["max_slots"] == 3 and c["model"] is TINY
    assert c["trace"] is None           # a CPU trace has no TPU plane
    assert 0 < bench.reader("batch_occupancy_pct")(c) <= 100
    assert bench.reader("engine_step_ms.sat")(c) > 0
    assert bench.reader("expert_load_max_over_mean.sat")(c) >= 1.0
    assert bench.reader("decode_latent_hbm_roofline_pct.sat")(c) is None
    assert bench.reader("prefill_moe_flops_roofline_pct.sat")(c) is None
    serve = lines[0]["serve"]
    assert serve["prefill_chunks"] > 0          # prompts past 16 tokens
    assert serve["kv_bytes_per_token"] == 5 * 24 * 4
    assert 0 < serve["decode_means"]["experts_touched"] <= 3 * 8
    assert serve["preempted"] == 0


def test_the_check_refuses_a_reference_without_the_routed_experts(bench):
    """The control the cell's limits are set against, at the tiny size:
    the same engine held to a reference that leaves the routed experts
    out fails the limits it passes otherwise."""
    import jax

    from benchmarks.drivers import serve_moe_mla as driver
    from quintnet_tpu.models.pangu_moe import PanguMoEConfig

    cell = _tiny_cell(bench)
    cfg = PanguMoEConfig.from_dict(TINY)
    gc.collect()
    engine = driver.build_engine(cell.spec, cfg,
                                 driver.make_params(cfg, "f32", 3))
    ok = driver.check_logits(engine, TINY, cell.spec, 3)
    assert ok["ok"], ok
    cut = driver.check_logits(
        engine, TINY, cell.spec, 3, reference_out=driver.reference_side(
            engine.params, TINY, cell.spec, 3, routed=False))
    assert not cut["ok"]
    assert cut["token_rms_median"] > 50 * ok["token_rms_median"]
    assert jax.tree.leaves(engine.params)[0] is not None
