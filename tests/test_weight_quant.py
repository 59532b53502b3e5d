"""Weight layout policies (quintnet_tpu/serve/weight_quant.py).

THE contract, mirroring tests/test_kv_quant.py on the weights side of
the shared LayoutPolicy protocol: a ``fake_quant``-weights engine —
f32 storage, all-ones per-output-channel scales, the FULL scaled code
path through nn/layers.quantized_matmul — is BIT-identical to the f32
engine across greedy, sampled, prefix-cache reuse, speculation,
chunked prefill, tp=2 and the llama family, which pins the
quantized-matmul seam as numerically inert. int8/fp8 are then gated
by the paged teacher-forced NLL delta (< 0.05 through the serving
path) and the provable per-channel round-trip bounds (int8: <=
scale/2; fp8 e4m3: <= scale * 448 * 2**-4 — one ulp at the binade
top). The policy is baked into the param tree at engine build, so
compile counts are UNCHANGED for every policy (one prefill, one
decode — zero backend compiles observed after warmup), the LoRA
delta path stays full-precision on top (adapter identity preserved
under fake_quant), and ServeMetrics surfaces
weight_bytes/weights_dtype through summary(), aggregate() and the
strict-parser Prometheus exposition.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.serve import (ServeEngine, SpecConfig, gpt2_family,
                                make_weight_policy)
from quintnet_tpu.serve.kv_pool import KVPool
from quintnet_tpu.serve.kv_quant import (FLOAT8_DTYPE,
                                         dequant_roundtrip_error,
                                         paged_eval_nll)
from quintnet_tpu.serve.weight_quant import (WeightLayoutPolicy,
                                             present_targets,
                                             quantize_params,
                                             weight_bytes,
                                             weight_policy_names)

CFG = GPT2Config.tiny(n_layer=2)

needs_fp8 = pytest.mark.skipif(FLOAT8_DTYPE is None,
                               reason="no float8_e4m3fn in this jax")


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _prompts(rng, lengths):
    return [np.asarray(rng.integers(0, CFG.vocab_size, (t,)), np.int32)
            for t in lengths]


def _engine(params, weights_dtype, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("max_seq_len", 32)
    return ServeEngine(gpt2_family(CFG), params,
                       weights_dtype=weights_dtype, **kw)


def _serve(eng, prompts, max_new, *, arrivals=None, keys=None):
    """Submit with staggered arrivals, run to completion, return
    outputs in submission order."""
    arrivals = arrivals or [0] * len(prompts)
    keys = keys or [jax.random.key(100 + i) for i in range(len(prompts))]
    rids = {}
    submitted, step = 0, 0
    while submitted < len(prompts) or eng.has_work:
        while (submitted < len(prompts)
               and arrivals[submitted] <= step):
            rids[submitted] = eng.submit(prompts[submitted], max_new,
                                         key=keys[submitted])
            submitted += 1
        eng.step()
        step += 1
        assert step < 1000, "engine failed to drain"
    return [eng.result(rids[i]) for i in range(len(prompts))]


# ---------------------------------------------------------------------
# policy objects: one protocol, two faces
# ---------------------------------------------------------------------

class TestPolicy:
    def test_resolution(self):
        assert make_weight_policy(None).name == "f32"
        assert make_weight_policy("int8").name == "int8"
        assert make_weight_policy(jnp.float32).name == "f32"
        assert make_weight_policy(jnp.bfloat16).name == "bf16"
        p = make_weight_policy("fake_quant")
        assert make_weight_policy(p) is p
        with pytest.raises(ValueError, match="unknown weights_dtype"):
            make_weight_policy("int4")
        with pytest.raises(ValueError, match="no weight policy"):
            make_weight_policy(jnp.int8)  # raw int8 needs the scales

    def test_ladder_pinned_in_specs(self):
        from quintnet_tpu.analysis.specs import weight_layout_policies

        assert weight_policy_names() == weight_layout_policies()

    def test_shared_protocol(self):
        """Weights and KV consume ONE LayoutPolicy contract — the
        weight ladder subclasses the same base the KV ladder does,
        without the two ladders' objects being interchangeable."""
        from quintnet_tpu.serve.kv_quant import (KVLayoutPolicy,
                                                 LayoutPolicy,
                                                 make_policy)

        for name in weight_policy_names():
            if name == "fp8" and FLOAT8_DTYPE is None:
                continue
            pol = make_weight_policy(name)
            assert isinstance(pol, WeightLayoutPolicy)
            assert isinstance(pol, LayoutPolicy)
            assert not isinstance(pol, KVLayoutPolicy)
        assert not isinstance(make_policy("int8"), WeightLayoutPolicy)

    def test_scaled_flags(self):
        assert not make_weight_policy("f32").scaled
        assert not make_weight_policy("bf16").scaled
        assert make_weight_policy("int8").scaled
        assert make_weight_policy("fake_quant").scaled
        assert make_weight_policy("fake_quant").qmax == 0.0

    def test_int8_roundtrip_bound(self, rng):
        # [L, in, out] with per-OUTPUT-channel scales (axes = in dim)
        x = rng.normal(size=(2, 16, 8)).astype(np.float32)
        err, sc = dequant_roundtrip_error(make_weight_policy("int8"), x,
                                          axes=(-2,))
        assert err.shape == sc.shape == (2, 8)
        # the provable absmax bound: <= scale / 2 per element
        assert np.all(np.asarray(err) <= np.asarray(sc) * 0.5 + 1e-6)
        assert np.asarray(err).max() > 0  # rounding really happened
        err0, sc0 = dequant_roundtrip_error(
            make_weight_policy("fake_quant"), x, axes=(-2,))
        assert np.all(np.asarray(err0) == 0.0)
        assert np.all(np.asarray(sc0) == 1.0)

    @needs_fp8
    def test_fp8_roundtrip_bound(self, rng):
        """e4m3's worst relative spacing below qmax is 2**-3 between
        mantissa steps at a binade top; after the absmax prescale the
        provable per-element bound is scale * 448 * 2**-4 (half a
        step). Rounding must really be float-shaped: small values
        survive (no integer truncation to zero)."""
        x = rng.normal(size=(2, 16, 8)).astype(np.float32)
        pol = make_weight_policy("fp8")
        err, sc = dequant_roundtrip_error(pol, x, axes=(-2,))
        bound = np.asarray(sc) * 448.0 * 2.0 ** -4
        assert np.all(np.asarray(err) <= bound + 1e-6)
        assert np.asarray(err).max() > 0
        # fractions survive the narrowing cast (no jnp.round in the
        # float-storage quant path)
        q = pol.quant(jnp.asarray([0.3, -0.7]), jnp.asarray(1.0))
        assert q.dtype == jnp.dtype(FLOAT8_DTYPE)
        assert np.all(np.asarray(pol.dequant(q, jnp.asarray(1.0)))
                      != 0.0)


# ---------------------------------------------------------------------
# tree surgery
# ---------------------------------------------------------------------

class TestPacking:
    def test_quantize_params_targets_only(self, params):
        fam = gpt2_family(CFG)
        targets = present_targets(params, fam.weight_targets)
        assert targets == fam.weight_targets  # dense: all present
        q = quantize_params(params, targets,
                            make_weight_policy("int8"))
        for path in targets:
            node = q["blocks"]
            ref = params["blocks"]
            for k in path:
                node, ref = node[k], ref[k]
            assert node["w"].dtype == jnp.int8
            L, _fin, fout = ref["w"].shape
            assert node["w_scale"].shape == (L, fout)
            assert node["w_scale"].dtype == jnp.float32
            if "b" in ref:                 # bias stays full-precision
                assert node["b"] is ref["b"]
        # untargeted leaves keep their identity (same device buffers)
        assert q["embedding"] is params["embedding"]
        assert q["head"] is params["head"]
        assert q["blocks"]["ln1"] is params["blocks"]["ln1"]
        # the f32 policy is the identity, same OBJECT
        assert quantize_params(params, targets,
                               make_weight_policy("f32")) is params

    def test_present_targets_drop_missing(self, params):
        """An MoE block swaps mlp for moe — the dense-mlp targets must
        drop out instead of KeyError-ing (experts stay f32)."""
        fam = gpt2_family(CFG)
        no_mlp = {**params,
                  "blocks": {k: v for k, v in params["blocks"].items()
                             if k != "mlp"}}
        kept = present_targets(no_mlp, fam.weight_targets)
        assert kept == (("attn", "qkv"), ("attn", "proj"))

    def test_weight_bytes_ratio(self, params):
        fam = gpt2_family(CFG)
        targets = present_targets(params, fam.weight_targets)
        b32 = weight_bytes(params, targets)
        q = quantize_params(params, targets,
                            make_weight_policy("int8"))
        b8 = weight_bytes(q, targets)
        # THE capacity claim: >= 3.5x fewer bytes on the serving
        # matmul weights, per-channel f32 scales included
        assert b32 / b8 >= 3.5
        # and the engine accounts the same numbers
        eng = _engine(params, "int8")
        assert eng.weight_bytes == b8
        assert _engine(params, "f32").weight_bytes == b32


# ---------------------------------------------------------------------
# the identity golden matrix: fake_quant weights == f32, bit for bit
# ---------------------------------------------------------------------

class TestFakeQuantIdentity:
    def _match(self, params, rng, *, kw_a=None, lengths=(5, 9, 3),
               max_new=6, arrivals=None):
        kw_a = kw_a or {}
        prompts = _prompts(rng, lengths)
        keys = [jax.random.key(70 + i) for i in range(len(prompts))]
        out32 = _serve(_engine(params, "f32", **kw_a), prompts, max_new,
                       arrivals=arrivals, keys=keys)
        outfk = _serve(_engine(params, "fake_quant", **kw_a),
                       prompts, max_new, arrivals=arrivals, keys=keys)
        for a, b in zip(out32, outfk):
            np.testing.assert_array_equal(a, b)
        return out32

    def test_greedy(self, params, rng):
        self._match(params, rng)

    def test_sampled(self, params, rng):
        self._match(params, rng, kw_a=dict(temperature=0.9, top_k=7))

    def test_prefix_cache_with_reuse(self, params, rng):
        shared = np.asarray(rng.integers(0, CFG.vocab_size, (10,)),
                            np.int32)
        tails = [np.asarray(rng.integers(0, CFG.vocab_size, (t,)),
                            np.int32) for t in (3, 5, 2, 4)]
        prompts = [np.concatenate([shared, t]) for t in tails]
        keys = [jax.random.key(200 + i) for i in range(4)]
        outs = {}
        for name in ("f32", "fake_quant"):
            eng = _engine(params, name, max_slots=2)
            outs[name] = _serve(eng, prompts, 5,
                                arrivals=[0, 0, 6, 6], keys=keys)
            assert eng.metrics.prefix_hit_tokens > 0  # cache really hit
        for a, b in zip(outs["f32"], outs["fake_quant"]):
            np.testing.assert_array_equal(a, b)

    def test_speculative_sampled(self, params, rng):
        self._match(params, rng,
                    kw_a=dict(spec=SpecConfig(), temperature=0.7),
                    max_new=8)

    def test_chunked_prefill(self, params, rng):
        self._match(params, rng,
                    kw_a=dict(chunked_prefill=True, prefill_len=8,
                              prefill_chunk_budget=4),
                    lengths=(5, 14, 3))

    def test_stacked_with_kv_fake_quant(self, params, rng):
        """Both seams at once: fake_quant WEIGHTS over a fake_quant KV
        pool is still bit-identical to the all-f32 engine."""
        self._match(params, rng, kw_a=dict(kv_dtype="fake_quant"))

    def test_tp2(self, params, rng):
        """Scaled weights under a tp=2 shard_map: w_scale shards like
        the out dim of its weight (augment_weight_specs), outputs
        bit-identical to the single-device f32 engine."""
        from quintnet_tpu.core.mesh import mesh_from_sizes
        from quintnet_tpu.models.gpt2 import gpt2_to_tp_layout

        prompts = _prompts(rng, (5, 9, 3))
        keys = [jax.random.key(50 + i) for i in range(3)]
        out32 = _serve(_engine(params, "f32"), prompts, 6, keys=keys)
        mesh = mesh_from_sizes(tp=2)
        tp_params = gpt2_to_tp_layout(params, CFG, 2)
        outfk = _serve(_engine(tp_params, "fake_quant", mesh=mesh),
                       prompts, 6, keys=keys)
        for a, b in zip(out32, outfk):
            np.testing.assert_array_equal(a, b)

    def test_llama_family(self, rng):
        from quintnet_tpu.models.llama import LlamaConfig, llama_init
        from quintnet_tpu.serve import llama_family

        cfg = LlamaConfig.tiny(n_layers=2)
        lparams = llama_init(jax.random.key(1), cfg)
        prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (t,)),
                   np.int32) for t in (4, 7)]
        keys = [jax.random.key(300 + i) for i in range(2)]
        outs = {}
        for name in ("f32", "fake_quant"):
            eng = ServeEngine(llama_family(cfg), lparams, max_slots=2,
                              block_size=4, num_blocks=32,
                              max_seq_len=24, weights_dtype=name)
            outs[name] = _serve(eng, prompts, 5, keys=keys)
        for a, b in zip(outs["f32"], outs["fake_quant"]):
            np.testing.assert_array_equal(a, b)

    def test_lora_stays_full_precision_on_top(self, params, rng,
                                              tmp_path):
        """The adapter delta rides OVER the scaled dot: a fake_quant
        engine serving a LoRA tenant is bit-identical to the f32
        engine serving the same tenant (and the packed factors never
        inherit the storage dtype)."""
        from quintnet_tpu.models.lora import (LoRAConfig, lora_init,
                                              save_lora)
        from quintnet_tpu.serve import AdapterRegistry

        lcfg = LoRAConfig(rank=4)
        lora = lora_init(jax.random.key(3), params["blocks"], lcfg)
        lora = jax.tree.map(
            lambda l: l + 0.02 * jax.random.normal(
                jax.random.key(103), l.shape), lora)
        path = str(tmp_path / "t.safetensors")
        save_lora(lora, lcfg, path)
        prompts = _prompts(rng, (5, 8))
        keys = [jax.random.key(400 + i) for i in range(2)]
        outs = {}
        for name in ("f32", "fake_quant"):
            reg = AdapterRegistry()
            reg.register("t", path)
            eng = _engine(params, name, adapters=reg, max_seq_len=48)
            rids = [eng.submit(p, 5, key=k, adapter_id="t")
                    for p, k in zip(prompts, keys)]
            eng.run()
            outs[name] = [eng.result(r) for r in rids]
        for a, b in zip(outs["f32"], outs["fake_quant"]):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------
# int8/fp8 quality gates + the compile bound
# ---------------------------------------------------------------------

class TestQuality:
    def _nll(self, params, name, rows):
        fam = gpt2_family(CFG)
        qparams = quantize_params(
            params, present_targets(params, fam.weight_targets),
            make_weight_policy(name))
        pool = KVPool(n_layers=CFG.n_layer, n_kv_heads=CFG.n_head,
                      head_dim=CFG.n_embd // CFG.n_head, block_size=4,
                      num_blocks=32)
        return paged_eval_nll(fam, qparams, pool, rows)

    def test_paged_ppl_delta_gate(self, params, rng):
        """Teacher-forced NLL THROUGH the paged serving path under
        packed weights: int8/fp8 quality loss stays under the gate,
        fake_quant's is exactly zero."""
        rows = rng.integers(0, CFG.vocab_size, (4, 24)).astype(np.int32)
        names = ["f32", "fake_quant", "int8"]
        if FLOAT8_DTYPE is not None:
            names.append("fp8")
        nll = {name: self._nll(params, name, rows) for name in names}
        assert nll["fake_quant"] == nll["f32"]  # the identity, again
        for name in names[2:]:
            assert abs(nll[name] - nll["f32"]) < 0.05, (
                f"{name} paged ppl delta too large: "
                f"{nll[name]:.4f} vs {nll['f32']:.4f}")

    @pytest.mark.parametrize("name", ["bf16", "int8", "fake_quant"])
    def test_serves_and_compile_bound_holds(self, params, rng, name):
        """Mixed staggered trace per policy: everything finishes and
        the compile counts are exactly the f32 engine's — one
        prefill, one decode (the policy is baked into the tree, not
        a program)."""
        prompts = _prompts(rng, (3, 5, 4, 6, 3))
        eng = _engine(params, name, max_slots=3, block_size=2,
                      num_blocks=12, max_seq_len=16)
        outs = _serve(eng, prompts, 5, arrivals=[0, 1, 2, 5, 8])
        assert all(len(o) == len(p) + 5
                   for o, p in zip(outs, prompts))
        assert eng.metrics.finished == len(prompts)
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
        eng.assert_compile_count()

    @needs_fp8
    def test_fp8_serves_and_compile_bound_holds(self, params, rng):
        eng = _engine(params, "fp8")
        outs = _serve(eng, _prompts(rng, (4, 7)), 5)
        assert all(len(o) > 0 for o in outs)
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
        eng.assert_compile_count()

    def test_zero_backend_compiles_after_warmup(self, params, rng):
        """jax.monitoring sees ZERO backend_compile events across a
        20-step int8 trace after warmup — the quantized tree hits the
        same two compiled programs."""
        from quintnet_tpu.obs.recorder import startup

        eng = _engine(params, "int8", max_slots=3, block_size=2,
                      num_blocks=12, max_seq_len=16)
        eng.submit(_prompts(rng, (4,))[0], 3)
        eng.run()
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}

        # the program's own record counts every backend compile or load
        compiled0 = startup().totals.get("programs", 0)
        assert compiled0 > 0                # (and it was listening)
        prompts = _prompts(rng, (3, 5, 4, 6, 3, 5))
        arrivals = [0, 1, 3, 6, 10, 14]
        submitted = 0
        for step in range(20):
            while (submitted < len(prompts)
                   and arrivals[submitted] <= step):
                eng.submit(prompts[submitted], 4)
                submitted += 1
            eng.step()
        assert submitted == len(prompts)
        assert startup().totals.get("programs", 0) == compiled0
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}


# ---------------------------------------------------------------------
# metrics surface
# ---------------------------------------------------------------------

class TestMetrics:
    def test_summary_surfaces_weight_bytes(self, params, rng):
        eng = _engine(params, "int8")
        _serve(eng, _prompts(rng, (4,)), 3)
        s = eng.metrics.summary()
        assert s["weight_bytes"] == eng.weight_bytes > 0
        assert s["weights_dtype"] == "int8"

    def test_aggregate_sums_weight_bytes(self, params, rng):
        from quintnet_tpu.serve.metrics import aggregate

        engines = [_engine(params, d) for d in ("f32", "int8")]
        for eng in engines:
            _serve(eng, _prompts(rng, (4,)), 3)
        agg = aggregate([e.metrics for e in engines])
        assert agg["weight_bytes"] == sum(e.weight_bytes
                                          for e in engines)
        assert agg["weights_dtype"] == "f32,int8"

    def test_prom_exposition_weight_bytes(self, params, rng):
        """weight_bytes rides the strict-parser GET /metrics gate as
        quintnet_engine_weight_bytes (the string-valued weights_dtype
        is correctly NOT a series)."""
        from quintnet_tpu.obs.prom import (parse_exposition,
                                           render_exposition, sample)

        eng = _engine(params, "int8")
        _serve(eng, _prompts(rng, (4,)), 3)
        s = eng.metrics.summary()
        text = render_exposition({}, {"r0": s})
        parsed = parse_exposition(text)
        assert sample(parsed, "quintnet_engine_weight_bytes",
                      replica="r0") == s["weight_bytes"] > 0
        assert "weights_dtype" not in text
