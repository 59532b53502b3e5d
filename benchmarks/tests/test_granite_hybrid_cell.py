"""The cell ``granite-4.0-h-micro.serve-chat-sat`` on the CPU: its
driver's rehearsal at a tiny size, its byte and FLOP counts against the
engine's own statics at the PUBLISHED sizes (by ``jax.eval_shape``:
nothing that large is built), its readers on a hand-made run, and the
manifest with four cells. Nothing here is a device number."""

import gc
import json
import math
import os
import time

import pytest

from benchmarks.lib import harness, hybrid_bytes, step_ring

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite-4.0-h-micro.serve-chat-sat"
XL = "gpt2-xl.serve-chat-sat"
NEW = ("decode_state_hbm_roofline_pct.sat",
       "state_share_of_decode_bytes_pct.sat",
       "prefill_flops_roofline_pct.sat")
SHARED = ("engine_step_ms.sat", "batch_occupancy_pct", "step_device_ms.sat",
          "device_idle_pct.sat", "hbm_peak_gb.serve", "decode_device_ms.sat",
          "prefill_device_share_pct.sat", "engine_host_ms.sat",
          "host_syncs_per_step.sat", "h2d_kb_per_step.sat")


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


@pytest.fixture(scope="module")
def published(bench):
    return bench.cell(CELL).config


def test_the_manifest_has_the_cell_its_ten_shared_and_three_new_metrics(
        bench, published):
    m = bench.manifest
    assert [w["name"] for w in m["workloads"]][-1] == CELL
    assert len(m["workloads"]) == 4
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    entry = m["configs"][-1]
    assert entry["name"] == "granite-4.0-h-micro" and entry["reduced"] == []
    assert len(entry["source"]) <= 200
    cell = bench.cell(CELL)
    assert cell.spec["driver"] == "serve_hybrid"
    assert cell.spec["engine"]["prefix_cache"] is False
    assert {x["name"] for x in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {x["name"] for x in cell.per_layer}
    assert names == set(SHARED) | set(NEW)
    assert tuple(x["name"] for x in m["per_layer"][-3:]) == NEW
    for x in m["per_layer"]:
        if x["name"] in SHARED:
            assert x["workloads"] == [XL, CELL]
        if x["name"] in NEW:
            assert x["workloads"] == [CELL] and x["moves"] == "serve_tok_s"
            bench.reader(x["name"])
    # its count has no state term and would read the new cell too low
    roof = next(x for x in m["per_layer"]
                if x["name"] == "decode_hbm_roofline_pct.sat")
    assert roof["workloads"] == [XL]
    # the configuration file: every key of the catalog's row, unchanged
    row = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if os.path.isfile(row):
        with open(row) as f:
            catalog = next(r for r in map(json.loads, f)
                           if r["name"] == "granite-4.0-h-micro")
        assert published["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            assert published[key] == value, key
    assert published["assumed"] and published["deployment"]


def test_hybrid_bytes_by_hand_at_the_published_sizes(published):
    n = hybrid_bytes.param_counts(published)
    mamba_mm = 2048 * (4096 + 4352 + 64) + 4096 * 2048 + 3 * 2048 * 8192
    attn_mm = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert (mamba_mm, attn_mm) == (76_152_832, 60_817_408)
    assert n["matmul"] == 36 * mamba_mm + 4 * attn_mm == 2_984_771_584
    assert n["total"] == 3_191_396_096                          # 3.19B
    assert hybrid_bytes.param_bytes(
        published, weight_itemsize=2) == 6_796_041_216          # 6.80 GB
    assert hybrid_bytes.kv_bytes_per_token(published, 2) == 8_192
    assert hybrid_bytes.state_bytes_per_slot(published, 2) == 36 * (
        64 * 64 * 128 * 4 + 3 * 4352 * 2) == 76_437_504         # 76.4 MB
    terms = hybrid_bytes.decode_step_bytes(6_796_041_216, 32 * 450, 8_192,
                                           32, 76_437_504)
    assert terms["state"] == 2 * 32 * 76_437_504
    assert 14.3 < 1e3 * terms["total"] / 819e9 < 14.5           # ms, by hand
    assert 0.41 < terms["state"] / terms["total"] < 0.42
    per_token = hybrid_bytes.prefill_flops_per_token(published, 300)
    matmuls = 2 * 2_984_771_584
    ssd = 36 * (2 * 256 * 128 + 2 * 256 * 4096 + 4 * 4096 * 128)
    attn = 4 * 2 * 32 * 64 * 300
    assert per_token == matmuls + ssd + attn
    assert ssd / per_token < 0.03                  # the scan is 2.5% of it
    short = hybrid_bytes.prefill_flops_per_token(published, 16)
    assert short < per_token                       # a chunk of 16, not 256


def test_the_engines_own_counts_agree_with_the_formulas(bench, published):
    """The ring's statics are what the readers divide by: at the
    published sizes (shapes only) they equal the shape formulas."""
    import jax
    import jax.numpy as jnp

    from quintnet_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                    granite_hybrid_init)
    from quintnet_tpu.serve import granite_hybrid_family
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    cfg = GraniteHybridConfig.from_dict(published)
    fam = granite_hybrid_family(cfg)
    shapes = jax.eval_shape(
        lambda k: (lambda p: quantize_params(
            p, present_targets(p, fam.weight_targets),
            make_weight_policy("bf16")))(granite_hybrid_init(k, cfg)),
        jax.random.key(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves) == 3_191_396_096
    assert sum(x.size * x.dtype.itemsize for x in leaves) == \
        hybrid_bytes.param_bytes(published, weight_itemsize=2)
    st = fam.state
    per_slot = st.n_layers * (4 * math.prod(st.ssm) + 2 * math.prod(st.conv))
    assert per_slot == hybrid_bytes.state_bytes_per_slot(published, 2)
    assert 2 * fam.n_layers * fam.n_kv_heads * fam.head_dim * 2 == \
        hybrid_bytes.kv_bytes_per_token(published, 2)
    # and a live engine at a tiny size says the same of itself
    tiny = GraniteHybridConfig.tiny()
    tiny_d = {f: getattr(tiny, f) for f in tiny.__dataclass_fields__}
    from quintnet_tpu.serve import ServeEngine

    gc.collect()
    eng = ServeEngine(granite_hybrid_family(tiny),
                      granite_hybrid_init(jax.random.key(0), tiny),
                      max_slots=2, block_size=4, num_blocks=16,
                      max_seq_len=32, kv_dtype="bf16", weights_dtype="bf16",
                      prefix_cache=False)
    static = eng.recorder.static
    assert static["param_bytes"] == hybrid_bytes.param_bytes(
        tiny_d, weight_itemsize=2)
    assert static["kv_bytes_per_token"] == hybrid_bytes.kv_bytes_per_token(
        tiny_d, 2)
    assert static["state_bytes_per_slot"] == \
        hybrid_bytes.state_bytes_per_slot(tiny_d, 2)
    assert eng.pool.conv.dtype == jnp.bfloat16
    assert eng.pool.ssm.dtype == jnp.float32
    assert step_ring.find_ring() is eng.recorder


class _Ring:
    def __init__(self, records, static):
        self._records, self.static = records, static

    def snapshot(self):
        return list(self._records)


def test_the_three_readers_on_a_hand_made_run(bench, published,
                                              monkeypatch):
    """Ten window steps and four traced ones of 64 decoding rows at 450
    positions each, two prefills of 300 tokens in the traced stretch."""
    static = {"param_bytes": 6_796_041_216, "kv_bytes_per_token": 8_192.0,
              "state_bytes_per_slot": 76_437_504}
    steps, records = [], []
    for i in range(14):
        s = 100.0 + i
        if i < 10:
            steps.append((s, s + 0.9, 64))
        records.append({"t0": s + 0.1, "t1": s + 0.8, "decoding": 64,
                        "context_tokens": 64 * 450,
                        "state_bytes": 2 * 64 * 76_437_504 * (
                            2 if i == 3 else 1),
                        "prefill_tokens": 300 if i in (3, 11, 12) else 0})
    monkeypatch.setattr(step_ring, "find_ring",
                        lambda: _Ring(records, static))
    ctx = {"engine_steps": steps, "traced_steps": 4, "model": published,
           "device_kind": "TPU v5 lite",
           "trace": {"modules": {"jit_serve_decode(1)": (4, 4 * 0.040),
                                 "jit_serve_prefill_b512(2)": (2, 0.070)}}}
    least_ms = 1e3 * (6_796_041_216 + 64 * 450 * 8_192
                      + 2 * 64 * 76_437_504) / 819e9
    assert bench.reader(NEW[0])(ctx) == pytest.approx(100 * least_ms / 40.0)
    assert 50 < bench.reader(NEW[0])(ctx) < 52              # 20.5 of 40 ms
    assert bench.reader(NEW[1])(ctx) == pytest.approx(
        100 * 2 * 64 * 76_437_504 / (least_ms * 819e6))
    assert 58 < bench.reader(NEW[1])(ctx) < 59
    flops = 600 * hybrid_bytes.prefill_flops_per_token(published, 300)
    assert bench.reader(NEW[2])(ctx) == pytest.approx(
        100 * flops / 197e12 / 0.070)
    assert 26 < bench.reader(NEW[2])(ctx) < 27
    # nothing to read: a KV-only engine's ring, no model, no prefill
    for broken in ({**ctx, "trace": None}, {**ctx, "traced_steps": 0}):
        assert bench.reader(NEW[0])(broken) is None
        assert bench.reader(NEW[2])(broken) is None
    assert bench.reader(NEW[2])({**ctx, "model": None}) is None
    assert bench.reader(NEW[2])({**ctx, "model": {"n_layer": 48}}) is None
    static["state_bytes_per_slot"] = 0
    for r in records:
        r["state_bytes"] = 0
    assert bench.reader(NEW[0])(ctx) is None
    assert bench.reader(NEW[1])(ctx) is None
    for r in records:
        del r["state_bytes"]         # a program whose ring lacks it
    assert bench.reader(NEW[1])(ctx) is None
    monkeypatch.setattr(step_ring, "find_ring", lambda: None)
    assert all(bench.reader(n)(ctx) is None for n in NEW)


TINY = {"vocab_size": 128, "hidden_size": 64,
        "shared_intermediate_size": 96, "num_hidden_layers": 8,
        "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "attention_multiplier": 0.0625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "rms_norm_eps": 1e-5, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_chunk_size": 8,
        "max_position_embeddings": 256}


def _tiny_spec(bench):
    spec = json.loads(json.dumps(bench.cell(CELL).spec))
    spec["engine"].update(max_slots=3, num_blocks=96, block_size=4,
                          max_seq_len=64, kv_dtype="f32",
                          weights_dtype="f32")
    spec["correctness"].update(prompt_lens=[27, 31], chunk_calls=[16, 4],
                               logits_tolerance=2e-4, state_tolerance=1e-4)
    return spec


def test_serve_hybrid_driver_rehearsal_at_a_tiny_size(bench, tmp_path):
    import jax

    from benchmarks.lib.device import CompileMeter

    spec = _tiny_spec(bench)
    mix = {"kind": "requests", "stratify": 4,
           "arrivals": {"kind": "backlog"},
           "prompt_len": {"dist": "uniform", "low": 4, "high": 24},
           "output_len": {"dist": "uniform", "low": 2, "high": 8}}
    cell = harness.Cell(name="tiny", chips=1, spec=spec, config=TINY,
                        traffic=mix, end_to_end=[], per_layer=[])
    lines = []
    gc.collect()
    ctx = harness.RunContext(
        cell=cell, seed=2**31 + 5, seconds=1.5, trace=False,
        devices=jax.devices()[:1], meter=CompileMeter(),
        t_process_start=time.perf_counter(), scratch=str(tmp_path),
        info=lines.append)
    rec = bench.driver("serve_hybrid").run(ctx)
    assert all(c["ok"] for c in rec["checks"].values()), rec["checks"]
    check = rec["checks"]["logits_vs_reference"]
    assert check["decode_steps"] == 31 - 20 and check["ref_std"] > 0
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert rec["end_to_end"]["serve_tok_s"] > 0 and rec["setup_s"] > 0
    c = rec["context"]
    assert c["steps"] > 0 and c["max_slots"] == 3 and c["model"] is TINY
    assert c["trace"] is None           # a CPU trace has no TPU plane
    assert 0 < bench.reader("batch_occupancy_pct")(c) <= 100
    assert bench.reader("engine_step_ms.sat")(c) > 0
    assert 0 < bench.reader("state_share_of_decode_bytes_pct.sat")(c) < 100
    assert bench.reader("decode_state_hbm_roofline_pct.sat")(c) is None
    assert bench.reader("prefill_flops_roofline_pct.sat")(c) is None
    assert lines and lines[0]["serve"]["state_bytes_per_slot"] > 0
    # a stale or forgotten state must not pass: the same check with the
    # tolerance it has here refuses logits moved by what a state moves
    assert check["max_abs_diff"] < 2e-4 < 0.003
    assert check["state_rel_err"] < 1e-5
    assert len(check["state_rel_err_by_layer"]) == 6
    assert check["state_rel_err"] <= check["state_rel_err_max_head"]


@pytest.mark.parametrize("state_as", ["f32", "bf16"])
def test_the_check_holds_the_pool_to_its_f32_state(bench, state_as):
    """The SSM state rounded to bf16 every time a program hands it back
    (what a bf16 state pool would hold between steps), passed off as
    the stated f32: the logits do not see it (a head's 16 x 16 entries
    average out behind one inner product), the state's own limit does,
    through the driver's own comparison."""
    import jax.numpy as jnp

    driver = bench.driver("serve_hybrid")
    spec, seed = _tiny_spec(bench), 2**31 + 5
    cfg = driver.GraniteHybridConfig.from_dict(TINY)
    gc.collect()
    engine = driver.build_engine(spec, cfg,
                                 driver.make_params(cfg, "f32", seed))
    if state_as == "bf16":
        update = engine.pool.update
        engine.pool.update = lambda k, v, ssm, conv: update(
            k, v, ssm.astype(jnp.bfloat16).astype(jnp.float32), conv)
    check = driver.check_logits(engine, TINY, spec, seed)
    assert check["max_abs_diff"] < check["tolerance"]
    if state_as == "f32":
        assert check["ok"] and check["state_rel_err"] < 1e-5
    else:
        assert not check["ok"]
        assert check["state_rel_err"] > 10 * check["state_tolerance"]
