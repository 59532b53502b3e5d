"""Quantized KV pool goldens (serve/kv_quant.py).

The contract ladder:

1. **Identity proof** — an engine on the ``fake_quant`` policy (f32
   storage, all-ones scales, FULL scaled code path: gather -> dequant
   -> insert -> requant -> scatter) is BIT-IDENTICAL to the f32
   engine, across greedy + sampled decoding, prefix-cache sharing,
   speculative decoding, chunked prefill and a tp=2 mesh. This pins
   the restructured kernels as numerically inert, so the int8
   rounding itself is the only quality variable.
2. **int8 quality gates** — the paged-ppl delta (teacher-forced NLL
   through the quantized pool vs the f32 pool) stays under a
   threshold, and the per-block max-abs dequant error respects the
   provable absmax bound (<= scale / 2 per element after a single
   quantization pass).
3. **Operational invariants** — compile counts are UNCHANGED per
   policy (the policy widens the pool operand list inside the SAME
   sentinel set), and the capacity metrics (`bytes_per_block`,
   `pool_bytes`, `kv_pool_bytes`/`kv_bytes_per_token` in
   summary/aggregate) report the ~4x equal-bytes win int8 buys.
4. **fp8 passthrough** — the ``fp8`` rung stores blocks as UNSCALED
   ``float8_e4m3fn`` (narrow on scatter, upcast on gather — no scale
   arrays at all), buying int8's exact 4x byte ratio WITHOUT the
   per-block scale overhead; gated by the same paged-ppl delta.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.serve import (KVLayoutPolicy, KVPool, ServeEngine,
                                SpecConfig, gpt2_family, make_policy)
from quintnet_tpu.serve.kv_quant import (FLOAT8_DTYPE,
                                         dequant_roundtrip_error,
                                         paged_eval_nll)

CFG = GPT2Config.tiny(n_layer=2)

needs_fp8 = pytest.mark.skipif(FLOAT8_DTYPE is None,
                               reason="no float8_e4m3fn in this jax")


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


def _prompts(rng, lengths):
    return [np.asarray(rng.integers(0, CFG.vocab_size, (t,)), np.int32)
            for t in lengths]


def _engine(params, kv_dtype, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("max_seq_len", 32)
    return ServeEngine(gpt2_family(CFG), params, kv_dtype=kv_dtype, **kw)


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
# policy object + capacity math
# ---------------------------------------------------------------------

class TestPolicy:
    def test_resolution(self):
        assert make_policy(None).name == "f32"
        assert make_policy("int8").name == "int8"
        assert make_policy(jnp.float32).name == "f32"
        assert make_policy(jnp.bfloat16).name == "bf16"
        p = make_policy("fake_quant")
        assert make_policy(p) is p
        with pytest.raises(ValueError, match="unknown kv_dtype"):
            make_policy("int4")
        with pytest.raises(ValueError, match="no passthrough policy"):
            make_policy(jnp.int8)  # raw int8 needs the scaled policy

    def test_ladder_pinned_in_specs(self):
        from quintnet_tpu.analysis.specs import kv_layout_policies
        from quintnet_tpu.serve.kv_quant import policy_names

        assert policy_names() == kv_layout_policies()

    def test_scaled_flags(self):
        assert not make_policy("f32").scaled
        assert not make_policy("bf16").scaled
        assert make_policy("int8").scaled
        assert make_policy("fake_quant").scaled
        assert isinstance(make_policy("int8"), KVLayoutPolicy)

    @needs_fp8
    def test_fp8_resolution_and_capacity(self):
        """fp8 is UNSCALED passthrough: raw float8 dtype resolves to
        the policy, no scale arrays, and a block costs exactly 1/4 of
        f32's bytes (int8's data shrink without its scale tax)."""
        pol = make_policy("fp8")
        assert pol.name == "fp8" and not pol.scaled
        assert make_policy(FLOAT8_DTYPE) is pol
        kw = dict(n_layers=2, n_kv_heads=4, head_dim=8, block_size=16)
        f32 = make_policy("f32").bytes_per_block(**kw)
        fp8 = pol.bytes_per_block(**kw)
        assert fp8 * 4 == f32
        assert fp8 < make_policy("int8").bytes_per_block(**kw)
        pool = KVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                      block_size=4, num_blocks=8, policy="fp8")
        assert len(pool.caches()) == 2     # passthrough: no scales
        assert pool.k.dtype == jnp.dtype(FLOAT8_DTYPE)

    def test_bytes_per_block_capacity_math(self):
        kw = dict(n_layers=2, n_kv_heads=4, head_dim=8, block_size=16)
        f32 = make_policy("f32").bytes_per_block(**kw)
        int8 = make_policy("int8").bytes_per_block(**kw)
        # k+v slot data: 2 * L * bs * H * Dh * itemsize
        assert f32 == 2 * 2 * 16 * 4 * 8 * 4
        # int8 adds 2 * L * H f32 scales per block
        assert int8 == 2 * 2 * 16 * 4 * 8 * 1 + 2 * 2 * 4 * 4
        # THE capacity claim: equal pool bytes hold >= 1.8x the blocks
        assert f32 / int8 >= 1.8

    def test_pool_exposes_policy_aware_bytes(self):
        def pool(policy):
            return KVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                          block_size=4, num_blocks=8, policy=policy)

        p32, p8 = pool("f32"), pool("int8")
        assert p32.pool_bytes == 8 * p32.bytes_per_block
        assert p32.bytes_per_token == p32.bytes_per_block / 4
        assert p8.bytes_per_block < p32.bytes_per_block
        # scaled pools carry 4 device buffers, passthrough 2
        assert len(p8.caches()) == 4
        assert len(p32.caches()) == 2
        with pytest.raises(ValueError, match="scale arrays"):
            p8.update(p8.k, p8.v)

    def test_dequant_roundtrip_error_bound(self, rng):
        # [blocks, heads, slots, dh] — per-block-per-head scales
        x = rng.normal(size=(6, 4, 16, 8)).astype(np.float32)
        err, sc = dequant_roundtrip_error(make_policy("int8"), x,
                                          axes=(-2, -1))
        assert err.shape == sc.shape == (6, 4)
        # the provable absmax bound: <= scale / 2 per element
        assert np.all(np.asarray(err) <= np.asarray(sc) * 0.5 + 1e-6)
        assert np.asarray(err).max() > 0  # rounding really happened
        # identity policy: exactly zero error, scales exactly one
        err0, sc0 = dequant_roundtrip_error(make_policy("fake_quant"), x,
                                            axes=(-2, -1))
        assert np.all(np.asarray(err0) == 0.0)
        assert np.all(np.asarray(sc0) == 1.0)

    def test_quant_storage_dtype(self, rng):
        pol = make_policy("int8")
        x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
        sc = pol.compute_scale(x, axes=(1,))
        q = pol.quant(x, sc[:, None])
        assert q.dtype == jnp.int8
        assert pol.dequant(q, sc[:, None]).dtype == jnp.float32


# ---------------------------------------------------------------------
# the identity golden matrix: fake_quant == f32, bit for bit
# ---------------------------------------------------------------------

class TestFakeQuantIdentity:
    def _match(self, params, rng, *, kw_a=None, kw_b=None, lengths=(5, 9, 3),
               max_new=6, arrivals=None):
        kw_a = kw_a or {}
        prompts = _prompts(rng, lengths)
        keys = [jax.random.key(70 + i) for i in range(len(prompts))]
        out32 = _serve(_engine(params, "f32", **kw_a), prompts, max_new,
                       arrivals=arrivals, keys=keys)
        outfk = _serve(_engine(params, "fake_quant", **(kw_b or kw_a)),
                       prompts, max_new, arrivals=arrivals, keys=keys)
        for a, b in zip(out32, outfk):
            np.testing.assert_array_equal(a, b)
        return out32

    def test_greedy(self, params, rng):
        self._match(params, rng)

    def test_sampled(self, params, rng):
        self._match(params, rng,
                    kw_a=dict(temperature=0.9, top_k=7))

    def test_prefix_cache_with_reuse(self, params, rng):
        """Shared-prefix prompts in two waves: the second wave hits the
        published chain (COW + scale copy on the scaled side)."""
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

    def test_tp2(self, params, rng):
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
                              max_seq_len=24, kv_dtype=name)
            outs[name] = _serve(eng, prompts, 5, keys=keys)
        for a, b in zip(outs["f32"], outs["fake_quant"]):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------
# int8 quality gates
# ---------------------------------------------------------------------

class TestInt8Quality:
    def _pool(self, kv_dtype, num_blocks=32):
        return KVPool(n_layers=CFG.n_layer, n_kv_heads=CFG.n_head,
                      head_dim=CFG.n_embd // CFG.n_head, block_size=4,
                      num_blocks=num_blocks, policy=kv_dtype)

    def test_paged_ppl_delta_gate(self, params, rng):
        """Teacher-forced NLL THROUGH the paged pool: the int8 engine's
        quality loss vs the f32 pool stays under the gate (and the
        fake-quant policy's is exactly zero)."""
        fam = gpt2_family(CFG)
        rows = rng.integers(0, CFG.vocab_size, (4, 24)).astype(np.int32)
        nll = {name: paged_eval_nll(fam, params, self._pool(name), rows)
               for name in ("f32", "fake_quant", "int8")}
        assert nll["fake_quant"] == nll["f32"]  # the identity, again
        assert abs(nll["int8"] - nll["f32"]) < 0.05, (
            f"int8 paged ppl delta too large: "
            f"{nll['int8']:.4f} vs {nll['f32']:.4f}")

    def test_per_block_dequant_error_bounded(self, params, rng):
        """Serve the SAME single prompt through an f32 and an int8
        engine (identical deterministic block allocation) and check
        every written block's dequantized content against the f32
        truth: after the single prefill quantization pass the max-abs
        error per block-head is <= scale / 2."""
        prompt = np.asarray(rng.integers(0, CFG.vocab_size, (14,)),
                            np.int32)
        pools = {}
        for name in ("f32", "int8"):
            eng = _engine(params, name, max_slots=1, num_blocks=16)
            _serve(eng, [prompt], 1, keys=[jax.random.key(7)])
            pools[name] = eng.pool
        p32, p8 = pools["f32"], pools["int8"]
        bs = p8.block_size
        nb = p8.num_blocks
        every = np.arange(nb * bs)
        for ref, q, sc in zip(p32.read_slots(every), p8.read_slots(every),
                              (p8.k_scale, p8.v_scale)):
            # [L, nb, bs, H, Dh] block views; scales [L, nb, H]
            refb = np.asarray(ref).reshape(CFG.n_layer, nb, bs,
                                           CFG.n_head, -1)
            dq = (np.asarray(q, np.float32).reshape(refb.shape)
                  * np.asarray(sc)[:, :, None, :, None])
            err = np.abs(dq - refb).max(axis=(2, 4))      # [L, nb, H]
            bound = np.asarray(sc) * 0.5 + 1e-5
            written = np.abs(refb).max(axis=(2, 4)) > 0
            # block 0 is the reserved NULL block — scratch memory the
            # two layouts use differently (f32 scatters pad columns
            # into it, the scaled path zero-fills it); nobody reads it
            written[:, 0, :] = False
            assert np.all(err[written] <= bound[written]), (
                f"per-block dequant error exceeds scale/2: "
                f"max excess {(err - bound)[written].max()}")
            assert written.any()  # the comparison saw real blocks

    def test_recycled_block_scale_not_inflated(self):
        """A freed block's stale bytes (a previous owner's large
        values, still in storage under their old scale — the allocator
        never scrubs) must NOT leak into the absmax when the block is
        recycled: the requant masks slots beyond the new owner's last
        written position, so the fresh scale reflects only real
        tokens. Without the mask a 50-absmax ghost coarsens a
        0.5-absmax newcomer's quantization ~100x."""
        from quintnet_tpu.nn.attention import (paged_gather_dequant,
                                               paged_quant_update)

        policy = make_policy("int8")
        bs, H, Dh, nb, F, layer = 4, 2, 4, 3, 12, 1
        cache = jnp.zeros((2, nb * bs, F), jnp.int8)
        scales = jnp.ones((2, nb, H), jnp.float32)
        table = jnp.asarray([[1, 0]], jnp.int32)
        # first owner fills pool block 1 with large values
        row = paged_gather_dequant(policy, cache, scales, layer, table,
                                   block_size=bs, head_shape=(H, Dh))
        cache, scales, _ = paged_quant_update(
            policy, cache, scales, layer, row,
            jnp.full((1, H, bs, Dh), 50.0),
            jnp.arange(bs, dtype=jnp.int32)[None, :],
            jnp.asarray([bs], jnp.int32),
            block_tables=table, block_size=bs, max_blocks=2)
        assert float(scales[layer, 1].max()) > 0.3          # ~50/127
        # block 1 recycled: new owner writes ONE small token at pos 0
        row2 = paged_gather_dequant(policy, cache, scales, layer, table,
                                    block_size=bs, head_shape=(H, Dh))
        cache, scales, view = paged_quant_update(
            policy, cache, scales, layer, row2,
            jnp.full((1, H, 1, Dh), 0.5),
            jnp.zeros((1, 1), jnp.int32), jnp.asarray([1], jnp.int32),
            block_tables=table, block_size=bs, max_blocks=1)
        sc = np.asarray(scales[layer, 1])
        assert np.all(sc <= 0.5 / 127 + 1e-6), (
            f"stale bytes inflated the recycled block's scale: {sc}")
        got = np.asarray(policy.dequant(
            cache[layer, bs, :H * Dh].reshape(H, Dh), sc[:, None]))
        assert np.all(np.abs(got - 0.5) <= sc.max() * 0.5 + 1e-6)
        # the other layer and the pad lanes were never written
        assert not np.asarray(cache[0]).any()
        assert not np.asarray(cache[..., H * Dh:]).any()
        assert np.all(np.asarray(scales[0]) == 1.0)

    def test_int8_serves_and_compile_bound_holds(self, params, rng):
        """Mixed staggered trace on int8: everything finishes, with
        preemption pressure, and the compile counts are exactly the
        f32 engine's — one prefill total, one decode (the policy is
        not a program)."""
        prompts = _prompts(rng, (3, 5, 4, 6, 3))
        eng = _engine(params, "int8", max_slots=3, block_size=2,
                      num_blocks=12, max_seq_len=16)
        outs = _serve(eng, prompts, 5, arrivals=[0, 1, 2, 5, 8])
        assert all(len(o) == len(p) + 5
                   for o, p in zip(outs, prompts))
        assert eng.metrics.finished == len(prompts)
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
        eng.assert_compile_count()

    def test_int8_spec_compile_bound(self, params, rng):
        eng = _engine(params, "int8", spec=SpecConfig())
        prompts = _prompts(rng, (6, 6))
        _serve(eng, prompts, 8)
        stats = eng.compile_stats()
        assert stats["prefill"] == 1 and stats["decode"] == 1
        assert stats["verify"] <= len(eng.spec.buckets)
        eng.assert_compile_count()

    @needs_fp8
    def test_fp8_ppl_delta_gate(self, params, rng):
        """The unscaled fp8 pool passes the same serving quality gate
        the int8 pool does."""
        fam = gpt2_family(CFG)
        rows = rng.integers(0, CFG.vocab_size, (4, 24)).astype(np.int32)
        nll32 = paged_eval_nll(fam, params, self._pool("f32"), rows)
        nll8 = paged_eval_nll(fam, params, self._pool("fp8"), rows)
        assert abs(nll8 - nll32) < 0.05, (
            f"fp8 paged ppl delta too large: {nll8:.4f} vs {nll32:.4f}")

    @needs_fp8
    def test_fp8_serves_and_compile_bound_holds(self, params, rng):
        """Mixed staggered trace on the fp8 pool: everything finishes
        and the compile counts are exactly the f32 engine's."""
        prompts = _prompts(rng, (3, 5, 4))
        eng = _engine(params, "fp8")
        outs = _serve(eng, prompts, 5, arrivals=[0, 1, 2])
        assert all(len(o) == len(p) + 5
                   for o, p in zip(outs, prompts))
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
        eng.assert_compile_count()


# ---------------------------------------------------------------------
# capacity metrics surface
# ---------------------------------------------------------------------

class TestCapacityMetrics:
    def test_summary_surfaces_pool_bytes(self, params, rng):
        eng = _engine(params, "int8")
        _serve(eng, _prompts(rng, (4,)), 3)
        s = eng.metrics.summary()
        assert s["kv_pool_bytes"] == eng.pool.pool_bytes > 0
        assert s["kv_bytes_per_token"] == pytest.approx(
            eng.pool.bytes_per_token)

    def test_aggregate_inherits_capacity(self, params, rng):
        """fleet.engine_summary goes through metrics.aggregate: pool
        bytes SUM across replicas, bytes/token reports the heaviest."""
        from quintnet_tpu.serve.metrics import aggregate

        engines = [_engine(params, d) for d in ("f32", "int8")]
        for eng in engines:
            _serve(eng, _prompts(rng, (4,)), 3)
        agg = aggregate([e.metrics for e in engines])
        assert agg["kv_pool_bytes"] == sum(e.pool.pool_bytes
                                           for e in engines)
        assert agg["kv_bytes_per_token"] == pytest.approx(
            max(e.pool.bytes_per_token for e in engines))
