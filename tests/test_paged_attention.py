"""Fused paged-attention Pallas kernels (ops/paged_attention.py) vs
the XLA gathered-view oracle.

The contract ladder:

1. **Kernel parity matrix** — the real nn/attention entry point
   (``mha_verify_paged`` at decode, verify and prefill widths) runs
   once per backend from identical pool state, across every
   ``kv_layout_policies`` entry x verify bucket widths x chunked
   prefill offsets, in CPU interpret mode: outputs within four f32
   ulps for f32/fake_quant (``F32_ATOL``: one op sequence compiled
   twice may sum in two orders), within the pinned tolerance for
   bf16/int8/fp8 (the kernel mirrors the oracle's op sequence and the
   observed diff is at most 3e-08, but nothing guarantees it, so the
   quantized dtypes pin a bound instead of a bit pattern), and POOL BYTES +
   SCALES exactly equal everywhere (the write paths are one math).
2. **GQA** — the same matrix through the llama blocks (4 query heads
   on 2 kv heads): the kernel resolves the repeat in its index maps.
3. **Engine goldens** — ``ServeEngine(attn_kernel="pallas")`` serves
   prefix-cache, speculative-decode, chunked-prefill, preemption and
   tp=2 traffic TOKEN-IDENTICAL to ``attn_kernel="xla"``, greedy and
   sampled, f32 and int8, gpt2 and llama.
4. **Structural win** — the jaxpr auditor
   (analysis.gathered_view_gathers) proves the pallas programs issue
   ZERO full-row block-table gathers where the xla ones issue 2-4 per
   layer; compile counts and sentinels are unchanged per backend.
5. **Arithmetic contract** — every gathered-view program contracts the
   view in the dtype it is STORED in (analysis.widened_view_dots reads
   zero for f32 and bf16 pools, gpt2 and llama), a bf16 pool's decode
   stays inside the bound 8 bits of ``q`` and of the probabilities
   earn, and an f32 pool's program rounds and pads nothing.
"""

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.analysis import (gathered_view_gathers, row_walk_calls,
                                   view_head_splits, widened_view_dots)
from quintnet_tpu.analysis.specs import attn_kernels, kv_layout_policies
from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.serve import ServeEngine, SpecConfig, gpt2_family
from quintnet_tpu.serve.kv_quant import make_policy

CFG = GPT2Config.tiny(n_layer=2)

# quantized-dtype tolerance: the kernel mirrors the oracle op for op
# (the OBSERVED diff is 0.0 to 3e-08 by case); the pin leaves headroom
# only for platform-lowering drift in ops that are not exact by
# construction
QUANT_ATOL = 1e-6

# f32 / fake_quant outputs: the two backends run the SAME op sequence
# on the same f32 values (ops/paged_attention._kernel mirrors
# _masked_sdpa op for op, head-batched dots included), but XLA:CPU
# compiles it twice in different surroundings — once fused into the
# whole attention program, once as the interpreter's grid-step body —
# and is free to vectorise a reduction (a contraction, the softmax's
# sum) in another order each time; float addition does not associate.
# One case shows it, on every tree since the seed: the SECOND chunk of
# the chunked prefill (one row of 8 at offset 8, 5 real tokens over 8
# cached) differs by 5.96e-08 in a quarter of its outputs, an ulp or
# two at their magnitude (up to 0.34 after the output projection);
# every other f32 case of the matrix reads 0.0 on this machine. Bit
# equality is therefore a property of the shapes, not of the kernel:
# hold the outputs to four ulps of [0.5, 1) and the POOL, which both
# backends write with one scatter, to bits (_assert_pools_match).
F32_ATOL = 4 * 2.0 ** -24


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


# ---------------------------------------------------------------------
# 1. kernel parity matrix through the real mha entry points
# ---------------------------------------------------------------------

H, D, BS, M, NB = 2, 8, 4, 6, 20        # geometry: M collides with no
S = 3                                   # other dim (auditor contract)


def _mha_params(key):
    from quintnet_tpu.nn.attention import mha_init

    return mha_init(key, H * D)


LAYERS, LAYER, PAD = 3, 1, 8            # the whole pool: 3 layers, the
#                                         ops address the middle one;
#                                         8 pad lanes past H * D


def _pool(policy, h=H, d=D):
    k = jnp.zeros((LAYERS, NB * BS, h * d + PAD), policy.store_dtype)
    v = jnp.zeros_like(k)
    if policy.scaled:
        return [k, v, jnp.ones((LAYERS, NB, h), jnp.float32),
                jnp.ones((LAYERS, NB, h), jnp.float32)]
    return [k, v, None, None]


def _blocks(pool_array, h=H, d=D):
    """The addressed layer of a pool array as [NB, BS, h, d] — after
    checking that no other layer and no pad lane was ever written."""
    a = np.asarray(pool_array)
    assert not a[..., h * d:].any(), "a pad lane was written"
    assert not np.delete(a, LAYER, axis=0).any(), "another layer written"
    return a[LAYER, :, :h * d].reshape(NB, BS, h, d)


def _scales(pool):
    return (pool[2], pool[3]) if pool[2] is not None else None


def _tables():
    # disjoint per-row tables; block 0 stays the null block
    return jnp.asarray([[1 + s * M + m for m in range(M)]
                        for s in range(S)], jnp.int32)


def _assert_pools_match(pa, pb, policy, tables):
    """Pool bytes + scales bit-equal on every REAL block (the null
    block legitimately collects both backends' masked-pad scatters)."""
    real = np.asarray(tables).reshape(-1)
    for a, b in zip(pa[:2], pb[:2]):
        np.testing.assert_array_equal(_blocks(a)[real], _blocks(b)[real])
    if policy.scaled:
        for a, b in zip(pa[2:], pb[2:]):
            assert np.all(np.delete(np.asarray(a), LAYER, axis=0) == 1.0)
            np.testing.assert_array_equal(np.asarray(a)[LAYER, real],
                                          np.asarray(b)[LAYER, real])


def _assert_out(ya, yb, policy):
    ya, yb = np.asarray(ya), np.asarray(yb)
    atol = F32_ATOL if policy.name in ("f32", "fake_quant") else QUANT_ATOL
    np.testing.assert_allclose(ya, yb, atol=atol, rtol=0)


@contextlib.contextmanager
def _oracle(kernel):
    """The "xla" side of the parity matrix is the GATHERED view, for
    every pool: the whole-row kernel is pinned against
    ``_lane_diag_sdpa`` / ``_masked_sdpa`` on it, to ``QUANT_ATOL``. A
    bf16 pool under few query rows would walk each row's live blocks
    where the kernels' interpreter is on (conftest.py) — another
    rounding of the probabilities, bounded in TestRowWalk, not here —
    so it is off while the xla side is traced: with no interpreter and
    off the TPU, ``paged_attend`` keeps the gathered form."""
    pa = _kernels()
    was = pa.INTERPRET
    pa.INTERPRET = was and kernel != "xla"
    try:
        yield
    finally:
        pa.INTERPRET = was


class TestMhaParityMatrix:
    """Each scenario runs the SAME op sequence per backend from the
    same initial pool, twice back to back (history accumulates across
    the calls, covering requant-on-top-of-requant)."""

    @pytest.fixture(scope="class")
    def attn(self):
        return _mha_params(jax.random.key(1))

    def _run_verify(self, attn, policy, kernel, P, steps=2):
        from quintnet_tpu.nn.attention import mha_verify_paged

        rng = np.random.default_rng(7)
        pool = _pool(policy)
        tables = _tables()
        starts = np.asarray([5, 0, 11], np.int32)
        outs = []
        for it in range(steps):
            x = jnp.asarray(rng.standard_normal((S, P, H * D)),
                            jnp.float32)
            positions = jnp.asarray(starts)[:, None] + jnp.arange(
                P, dtype=jnp.int32)[None, :]
            tail_lens = jnp.asarray([P, max(P - 1, 1), P], jnp.int32)
            kv = _scales(pool)
            out = jax.jit(
                lambda x, kp, vp, ks, vs: mha_verify_paged(
                    attn, x, kp, vp, positions, tail_lens,
                    num_heads=H, layer=jnp.int32(LAYER),
                    block_tables=tables, block_size=BS,
                    kv_scales=(ks, vs) if ks is not None else None,
                    policy=policy if kv is not None else None,
                    attn_kernel=kernel)
            )(x, pool[0], pool[1], pool[2], pool[3])
            outs.append(out[0])
            pool = list(out[1:]) + ([None, None] if kv is None else [])
            starts = starts + np.asarray(tail_lens)
        return outs, pool

    @pytest.mark.parametrize("policy_name", kv_layout_policies())
    @pytest.mark.parametrize("P", (1, 3, 5))
    def test_verify_and_decode_widths(self, attn, policy_name, P):
        """P=1 IS the decode shape; 3/5 are the verify buckets + 1."""
        policy = make_policy(policy_name)
        with _oracle("xla"):
            ya, pa = self._run_verify(attn, policy, "xla", P)
        yb, pb = self._run_verify(attn, policy, "pallas", P)
        for a, b in zip(ya, yb):
            _assert_out(a, b, policy)
        _assert_pools_match(pa, pb, policy, _tables())

    def _run_prefill(self, attn, policy, kernel):
        """Chunked prefill: one row, two chunks at dynamic offsets
        (start 0 then 8) through the SAME bucket width — the
        prefix-cache tail shape."""
        from quintnet_tpu.nn.attention import mha_verify_paged

        rng = np.random.default_rng(9)
        pool = _pool(policy)
        tables = _tables()[0]
        P = 8
        outs = []
        for start, tail in ((0, 8), (8, 5)):
            x = jnp.asarray(rng.standard_normal((1, P, H * D)),
                            jnp.float32)
            positions = start + jnp.arange(P, dtype=jnp.int32)
            kv = _scales(pool)
            out = jax.jit(
                lambda x, kp, vp, ks, vs: mha_verify_paged(
                    attn, x, kp, vp, positions[None],
                    jnp.full((1,), tail, jnp.int32),
                    num_heads=H, layer=jnp.int32(LAYER),
                    block_tables=tables[None], block_size=BS,
                    kv_scales=(ks, vs) if ks is not None else None,
                    policy=policy if kv is not None else None,
                    attn_kernel=kernel)
            )(x, pool[0], pool[1], pool[2], pool[3])
            outs.append(out[0])
            pool = list(out[1:]) + ([None, None] if kv is None else [])
        return outs, pool

    @pytest.mark.parametrize("policy_name", kv_layout_policies())
    def test_chunked_prefill_offsets(self, attn, policy_name):
        policy = make_policy(policy_name)
        with _oracle("xla"):
            ya, pa = self._run_prefill(attn, policy, "xla")
        yb, pb = self._run_prefill(attn, policy, "pallas")
        for a, b in zip(ya, yb):
            _assert_out(a, b, policy)
        _assert_pools_match(pa, pb, policy, _tables())


# ---------------------------------------------------------------------
# 2. GQA through the llama block (4 query heads on 2 kv heads)
# ---------------------------------------------------------------------

class TestGQAParity:
    @pytest.mark.parametrize("policy_name", ("f32", "int8"))
    @pytest.mark.parametrize("P", (1, 3))
    def test_llama_verify_gqa(self, policy_name, P):
        from quintnet_tpu.models.llama import (LlamaConfig, llama_init,
                                               llama_block_verify_paged,
                                               llama_rope_tables)

        cfg = LlamaConfig.tiny()
        assert cfg.n_heads != cfg.n_kv_heads  # the point of this test
        policy = make_policy(policy_name)
        params = llama_init(jax.random.key(2), cfg)
        blk = jax.tree.map(lambda a: a[0], params["blocks"])
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        pool = _pool(policy, hkv, hd)
        tables = _tables()
        rng = np.random.default_rng(3)
        starts = np.asarray([5, 0, 11], np.int32)
        results = {}
        for kernel in attn_kernels():
            p = [jnp.array(a) if a is not None else None for a in pool]
            outs = []
            st = starts.copy()
            rng2 = np.random.default_rng(3)
            for it in range(2):
                x = jnp.asarray(rng2.standard_normal((S, P, cfg.dim)),
                                jnp.float32)
                positions = (jnp.asarray(st)[:, None]
                             + jnp.arange(P, dtype=jnp.int32)[None, :])
                tails = jnp.asarray([P, max(P - 1, 1), P], jnp.int32)
                cos, sin = llama_rope_tables(positions, cfg)
                cos, sin = cos[:, None], sin[:, None]
                kv = (p[2], p[3]) if p[2] is not None else None
                out = jax.jit(
                    lambda x, kp, vp, ks, vs: llama_block_verify_paged(
                        blk, x, kp, vp, positions, tails, cfg, cos,
                        sin, layer=jnp.int32(LAYER),
                        block_tables=tables, block_size=BS,
                        kv_scales=(ks, vs) if ks is not None else None,
                        policy=policy if kv is not None else None,
                        attn_kernel=kernel)
                )(x, p[0], p[1], p[2], p[3])
                outs.append(out[0])
                p = list(out[1]) + ([None, None] if kv is None else [])
                st = st + np.asarray(tails)
            results[kernel] = (outs, p)
        (ya, pa), (yb, pb) = results["xla"], results["pallas"]
        for a, b in zip(ya, yb):
            _assert_out(a, b, policy)
        real = np.asarray(tables).reshape(-1)
        for a, b in zip(pa[:2], pb[:2]):
            np.testing.assert_array_equal(_blocks(a, hkv, hd)[real],
                                          _blocks(b, hkv, hd)[real])


# ---------------------------------------------------------------------
# 3. engine goldens: pallas serves token-identical to xla
# ---------------------------------------------------------------------

def _engine(params, kernel, family=None, fam_params=None, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("max_seq_len", 32)
    return ServeEngine(family or gpt2_family(CFG),
                       fam_params if fam_params is not None else params,
                       attn_kernel=kernel, **kw)


def _serve(eng, prompts, max_new, *, arrivals=None):
    arrivals = arrivals or [0] * len(prompts)
    keys = [jax.random.key(100 + i) for i in range(len(prompts))]
    rids, submitted, step = {}, 0, 0
    while submitted < len(prompts) or eng.has_work:
        while (submitted < len(prompts)
               and arrivals[submitted] <= step):
            rids[submitted] = eng.submit(prompts[submitted], max_new,
                                         key=keys[submitted])
            submitted += 1
        eng.step()
        step += 1
        assert step < 1000
    return [np.asarray(eng.result(rids[i])) for i in range(len(prompts))]


def _ab(params, prompts, max_new, *, arrivals=None, **kw):
    a = _serve(_engine(params, "xla", **kw), prompts, max_new,
               arrivals=arrivals)
    b = _serve(_engine(params, "pallas", **kw), prompts, max_new,
               arrivals=arrivals)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    return a


class TestEngineGoldens:
    @pytest.fixture(scope="class")
    def prompts(self):
        rng = np.random.default_rng(11)
        shared = rng.integers(0, CFG.vocab_size, (9,)).astype(np.int32)
        mixed = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)),
                            np.int32) for n in (5, 12, 3)]
        shared_tails = [np.concatenate(
            [shared, rng.integers(0, CFG.vocab_size, (t,)
                                  ).astype(np.int32)]) for t in (3, 5)]
        return mixed + shared_tails

    def test_greedy_prefix_cache_f32(self, params, prompts):
        _ab(params, prompts, 8, arrivals=[0, 1, 2, 4, 6])

    def test_sampled_spec_int8(self, params, prompts):
        _ab(params, prompts, 8, arrivals=[0, 0, 2, 3, 5],
            kv_dtype="int8", temperature=0.8,
            spec=SpecConfig(max_draft=4))

    def test_chunked_prefill_fake_quant(self, params):
        rng = np.random.default_rng(13)
        long = np.asarray(rng.integers(0, CFG.vocab_size, (20,)),
                          np.int32)
        short = np.asarray(rng.integers(0, CFG.vocab_size, (4,)),
                           np.int32)
        _ab(params, [long, short], 6, kv_dtype="fake_quant",
            prefill_len=8, chunked_prefill=True, prefill_chunk_budget=8,
            max_seq_len=32)

    def test_preemption_pressure_int8(self, params, prompts):
        # pool sized to force growth + preemption mid-trace
        _ab(params, prompts, 8, arrivals=[0, 0, 0, 1, 1],
            kv_dtype="int8", num_blocks=14, max_slots=3)

    def test_llama_gqa_engine_int8(self):
        from quintnet_tpu.models.llama import LlamaConfig, llama_init
        from quintnet_tpu.serve import llama_family

        cfg = LlamaConfig.tiny()
        lp = llama_init(jax.random.key(4), cfg)
        rng = np.random.default_rng(17)
        prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (n,)),
                              np.int32) for n in (5, 9)]
        _ab(None, prompts, 6, family=llama_family(cfg), fam_params=lp,
            kv_dtype="int8", max_slots=2)

    @staticmethod
    def _bf16_family(family):
        """Engine arguments of a tiny bf16-KV engine of ``family``."""
        kw = dict(kv_dtype="bf16")
        if family == "gpt2-tp2":
            from jax.sharding import Mesh

            kw.update(mesh=Mesh(np.array(jax.devices()[:2]), ("tp",)))
        elif family == "llama":
            from quintnet_tpu.models.llama import LlamaConfig, llama_init
            from quintnet_tpu.serve import llama_family

            cfg = LlamaConfig.tiny()
            kw.update(family=llama_family(cfg),
                      fam_params=llama_init(jax.random.key(4), cfg))
        elif family == "granite_hybrid":
            from quintnet_tpu.models.granite_hybrid import (
                GraniteHybridConfig, granite_hybrid_init)
            from quintnet_tpu.serve import granite_hybrid_family

            cfg = GraniteHybridConfig.tiny()
            kw.update(family=granite_hybrid_family(cfg),
                      fam_params=granite_hybrid_init(jax.random.key(6),
                                                     cfg),
                      prefix_cache=False)
        elif family == "laguna":
            from quintnet_tpu.models.laguna import (LagunaConfig,
                                                    laguna_init)
            from quintnet_tpu.serve import laguna_family

            cfg = LagunaConfig.tiny()
            kw.update(family=laguna_family(cfg, block_size=4),
                      fam_params=laguna_init(jax.random.key(7), cfg),
                      prefix_cache=False, chunked_prefill=True,
                      prefill_len=16)
        return kw

    @pytest.mark.parametrize("family", ("gpt2", "gpt2-tp2", "llama",
                                        "granite_hybrid"))
    def test_bf16_kv_tokens_equal_either_form(self, params, prompts,
                                              family, monkeypatch):
        """Greedy tokens of a bf16-KV engine are the same whether decode
        walks each row's live blocks with heads on the lane diagonal or
        gathers the view and splits it into heads, the form every
        program took before either: the two differ by the order of f32
        sums and by where a probability is rounded. Under tp each rank
        spreads its LOCAL heads over its own part of the row."""
        import quintnet_tpu.nn.attention as attention

        kw = self._bf16_family(family)
        served = {}
        for form, most_rows in (("walk", attention._MAX_DIAG_ROWS),
                                ("split", 0)):
            monkeypatch.setattr(attention, "_MAX_DIAG_ROWS", most_rows)
            eng = _engine(params, "xla", **kw)
            served[form] = _serve(eng, prompts, 8)
            args = next(a for s, a in eng._warmup_calls()
                        if s.fn is eng._decode.fn)
            assert view_head_splits(
                eng._decode.fn, *args, table_width=eng.table_width,
                block_size=eng.pool.block_size) == (
                    0 if form == "walk" else 2)
        for a, b in zip(served["walk"], served["split"]):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("traffic", ("steady", "chunked", "preempted"))
    @pytest.mark.parametrize("family", ("gpt2", "llama", "granite_hybrid",
                                        "laguna"))
    def test_bf16_kv_tokens_equal_before_and_after_the_walk(
            self, params, family, traffic, monkeypatch):
        """Greedy tokens of a bf16-KV engine of every paged family are
        the same whether its decode program walks each row's live
        blocks in place (the kernel, under the interpreter) or gathers
        the table's width with heads on the lane diagonal, the form it
        took before and still takes off the TPU — through a steady
        batch, a prompt prefilled in chunks beside decoding rows, and a
        pool small enough to preempt."""
        kw = self._bf16_family(family)
        vocab = (kw["family"].cfg if "family" in kw else CFG).vocab_size
        # (a tiny model of random weights has nearly flat logits, and
        # the forms part by a bf16 rounding of the probabilities: a
        # near-tie may fall either way — seed 41's third prompt meets
        # one in the GPT-2 engine, and the Laguna engine's top-k router
        # turns one into another expert set at seeds 42-44 since its
        # sliding layers walk their rings too (at 44 on the global walk
        # alone): logits 0.2 apart after sliding-layer outputs 2e-3
        # apart. The bounds on the rounding itself are TestRowWalk's and
        # tests/test_window_walk.py's.)
        rng = np.random.default_rng(45)
        lens, arrivals = (5, 12, 3, 12, 14), [0, 0, 0, 1, 1]
        if traffic == "chunked":
            lens, arrivals = (4, 21), [0, 2]
            kw.update(chunked_prefill=True, prefill_len=8,
                      prefill_chunk_budget=8)
        elif traffic == "preempted":
            kw.update(num_blocks=11)
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in lens]
        served = {}
        for form, interpret in (("walk", True), ("gathered", False)):
            monkeypatch.setattr(_kernels(), "INTERPRET", interpret)
            eng = _engine(params, "xla", **kw)
            served[form] = _serve(eng, prompts, 8, arrivals=arrivals)
            args = next(a for s, a in eng._warmup_calls()
                        if s.fn is eng._decode.fn)
            assert row_walk_calls(
                eng._decode.fn, *args, pool_shape=eng.pool.k.shape) == (
                    form == "walk") * (2 if family == "laguna" else 1)
            summary = eng.metrics.summary()
            if traffic == "preempted":
                assert summary["preempted"] > 0
            if traffic == "chunked":
                assert summary["prefill_chunks"] >= 3
        for a, b in zip(served["walk"], served["gathered"]):
            np.testing.assert_array_equal(a, b)

    def test_tp2_fake_quant(self, params, prompts):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        _ab(params, prompts[:3], 6, kv_dtype="fake_quant", mesh=mesh,
            max_slots=2)


# ---------------------------------------------------------------------
# 4. structural win + validation + import surface
# ---------------------------------------------------------------------

def _program_args(eng, params, which, bucket=None):
    """Arguments of one engine program (decode / verify bucket /
    prefill bucket), for tracing."""
    caches = eng.pool.caches()
    if which == "decode":
        return (params, *caches, jnp.asarray(eng._tok),
                jnp.asarray(eng._pos), jnp.asarray(eng._tables),
                jnp.asarray(eng._key_data))
    if which == "verify":
        S = eng.max_slots
        ids = np.zeros((S, bucket + 1), np.int32)
        return (params, *caches, jnp.asarray(ids),
                jnp.asarray(eng._pos),
                jnp.asarray(np.ones(S, np.int32)),
                jnp.asarray(eng._tables), jnp.asarray(eng._key_data))
    ids = np.zeros((1, bucket), np.int32)
    row = np.zeros((eng.table_width,), np.int32)
    return (params, *caches, jnp.asarray(ids), jnp.int32(1),
            jnp.int32(3), jnp.asarray(row), jnp.int32(0),
            jnp.int32(0), jnp.asarray(eng._key_data[0]))


class TestStructure:
    _args = staticmethod(_program_args)

    @pytest.mark.parametrize("kv_dtype", ("f32", "int8"))
    def test_pallas_issues_zero_gathered_view_gathers(self, params,
                                                      kv_dtype):
        """THE structural gate: every xla serving program gathers the
        full block-table row (2 pools, +2 scale arrays when scaled) per
        layer; every pallas program gathers it ZERO times — the walk
        happens inside the kernel. Asserted on decode, the smallest
        prefill bucket (requant span < table width — the auditor's
        caller contract), and a verify bucket."""
        counts = {}
        for kernel in attn_kernels():
            eng = _engine(params, kernel, kv_dtype=kv_dtype,
                          num_blocks=24, spec=SpecConfig(max_draft=4))
            kw = dict(num_blocks=24, table_width=eng.table_width)
            b0 = eng.prefill_buckets[0]
            counts[kernel] = dict(
                decode=gathered_view_gathers(
                    eng._decode.fn, *self._args(eng, params, "decode"),
                    **kw),
                prefill=gathered_view_gathers(
                    eng._prefills[b0].fn,
                    *self._args(eng, params, "prefill", b0), **kw),
                verify=gathered_view_gathers(
                    eng._verifies[2].fn,
                    *self._args(eng, params, "verify", 2), **kw),
            )
        per_layer = 4 if kv_dtype == "int8" else 2
        for which in ("decode", "prefill", "verify"):
            assert counts["xla"][which] == per_layer, counts
            assert counts["pallas"][which] == 0, counts

    def test_compile_counts_unchanged_per_backend(self, params):
        """Same sentinel set, same bounds, either backend — the kernel
        never adds a program."""
        rng = np.random.default_rng(5)
        prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)),
                              np.int32) for n in (3, 7)]
        for kernel in attn_kernels():
            eng = _engine(params, kernel)
            _serve(eng, prompts, 5)
            assert eng.compile_stats() == {"prefill": 1, "decode": 1}
            eng.assert_compile_count()

    def test_unknown_kernel_rejected(self, params):
        with pytest.raises(ValueError, match="attn_kernel"):
            _engine(params, "triton")

    def test_pallas_vmem_rejected_at_construction(self, params,
                                                  monkeypatch):
        """A program whose whole-row scratch cannot fit the chip's
        VMEM must fail at ServeEngine construction, naming the program
        and the computed number — not deep inside the first serving
        step, and never by falling back to the gathered-view path."""
        import importlib

        # the ops package re-exports the paged_attention FUNCTION, so
        # attribute-style module access resolves to it — go via
        # importlib for the module object
        pa = importlib.import_module(
            "quintnet_tpu.ops.paged_attention")
        monkeypatch.setattr(pa, "VMEM_CAP_BYTES", 64 * 1024)
        with pytest.raises(ValueError,
                           match=r"program decode .*MiB of VMEM"):
            _engine(params, "pallas")
        # the kernel itself refuses the same way when called directly
        q = jnp.zeros((1, 2, 1, 8))
        pool = jnp.zeros((4 * 8, 2, 8))
        with pytest.raises(ValueError, match="MiB of VMEM"):
            pa.paged_attention(q, pool, pool, jnp.zeros((1, 4), jnp.int32),
                               jnp.zeros((1,), jnp.int32), block_size=8)

    def test_pallas_sp_rejected(self, params):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        with pytest.raises(NotImplementedError, match="pallas"):
            _engine(params, "pallas", mesh=mesh, sp_axis="sp",
                    prefill_bucket_sizes=(16, 32))

    def test_pallas_refuses_a_stated_score_scale(self):
        """The fused kernel scales its scores by 1/sqrt(head_dim) only:
        a family that states its own scale (the hybrid's
        ``attention_multiplier``) is refused by name, never served
        with the wrong one."""
        from quintnet_tpu.nn.attention import paged_attend

        q = jnp.zeros((1, H, 1, D))
        pool = jnp.zeros((1, NB * BS, H * D))
        with pytest.raises(NotImplementedError, match="score scale"):
            paged_attend(q, q, q, (pool, pool), 0,
                         jnp.zeros((1, 1), jnp.int32),
                         jnp.ones((1,), jnp.int32),
                         jnp.zeros((1, M), jnp.int32), block_size=BS,
                         attn_kernel="pallas", scale=0.25)

    def test_scaled_kernel_requires_fresh_kv(self):
        from quintnet_tpu.ops.paged_attention import paged_attention

        q = jnp.zeros((1, H, 1, D))
        pool = jnp.zeros((NB * BS, H, D), jnp.int8)
        sc = jnp.ones((NB, H), jnp.float32)
        with pytest.raises(ValueError, match="fresh_kv"):
            paged_attention(q, pool, pool, _tables()[:1],
                            jnp.zeros((1,), jnp.int32), block_size=BS,
                            kv_scales=(sc, sc))


# ---------------------------------------------------------------------
# 5. the arithmetic contract: the view is contracted as it is stored
# ---------------------------------------------------------------------

def _kernels():
    """The kernels' module (the ops package re-exports the
    paged_attention FUNCTION under the submodule's name)."""
    import importlib

    return importlib.import_module("quintnet_tpu.ops.paged_attention")


def _eqns(closed):
    """Every eqn of a traced program, sub-jaxprs (pjit, scan) included."""
    from quintnet_tpu.analysis.jaxpr_audit import _walk_skip_kernels

    found = []
    _walk_skip_kernels(closed.jaxpr, found.append)
    return found


class TestStoredDtypeContract:
    # a row of 48 positions: collides with no dim of either tiny model
    # (widths 32 / 64 / 96 / 128) — the auditor's caller contract
    SEQ = 48

    @pytest.fixture(scope="class")
    def engines(self, params):
        """(family, kv_dtype) -> (engine, its params), built on first
        use: tracing only, nothing compiles."""
        from quintnet_tpu.models.llama import LlamaConfig, llama_init
        from quintnet_tpu.serve import llama_family

        built = {}

        def get(family, kv_dtype):
            if (family, kv_dtype) not in built:
                fam, p = None, params
                if family == "llama":
                    cfg = LlamaConfig.tiny()
                    fam, p = llama_family(cfg), llama_init(
                        jax.random.key(4), cfg)
                eng = _engine(params, "xla", family=fam, fam_params=p,
                              kv_dtype=kv_dtype, max_seq_len=self.SEQ,
                              spec=SpecConfig(max_draft=4))
                built[family, kv_dtype] = (eng, p)
            return built[family, kv_dtype]

        return get

    @pytest.mark.parametrize("kv_dtype", ("f32", "bf16"))
    @pytest.mark.parametrize("family", ("gpt2", "llama"))
    @pytest.mark.parametrize("which", ("decode", "verify", "prefill"))
    def test_no_dot_widens_the_view(self, engines, which, family,
                                    kv_dtype):
        """The mixed form (f32 q against a bf16 view) reads 2 here: a
        TPU compile would write an f32 copy of both views a layer."""
        eng, p = engines(family, kv_dtype)
        fn, bucket = {
            "decode": (eng._decode.fn, None),
            "verify": (eng._verifies[2].fn, 2),
            "prefill": (eng._prefills[eng.prefill_buckets[0]].fn,
                        eng.prefill_buckets[0])}[which]
        bs = eng.pool.block_size
        assert eng.table_width * bs == self.SEQ
        assert widened_view_dots(
            fn, *_program_args(eng, p, which, bucket),
            table_width=eng.table_width, block_size=bs) == 0

    def test_counter_sees_the_mixed_form(self):
        """The zeroes above mean something: the same two contractions
        written mixed count 2, cast down count 0."""
        q = jnp.zeros((S, H, 1, D), jnp.float32)
        view = jnp.zeros((S, H, M * BS, D), jnp.bfloat16)

        def mixed(q, k, v):
            pr = jnp.einsum("bhsd,bhtd->bhst", q, k)
            return jnp.einsum("bhst,bhtd->bhsd", pr, v)

        def stored(q, k, v):
            pr = jnp.einsum("bhsd,bhtd->bhst", q.astype(k.dtype), k,
                            preferred_element_type=jnp.float32)
            return jnp.einsum("bhst,bhtd->bhsd", pr.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)

        def mixed_by_page(q, k, v):
            k, v = (x.reshape(S, H, M, BS, D) for x in (k, v))
            pr = jnp.einsum("bhsd,bhmtd->bhsmt", q, k)
            return jnp.einsum("bhsmt,bhmtd->bhsd", pr, v)

        def mixed_as_gathered(q, k, v):     # rows [S, T, F], f32 q
            pr = jnp.einsum("brf,btf->brt", q, k)
            return jnp.einsum("brt,btf->brf", pr, v)

        kw = dict(table_width=M, block_size=BS)
        assert widened_view_dots(mixed, q, view, view, **kw) == 2
        assert widened_view_dots(mixed_by_page, q, view, view, **kw) == 2
        rows = jnp.zeros((S, M * BS, H * D + PAD), jnp.bfloat16)
        assert widened_view_dots(
            mixed_as_gathered, jnp.zeros((S, H, H * D + PAD), jnp.float32),
            rows, rows, **kw) == 2
        assert widened_view_dots(stored, q, view, view, **kw) == 0
        f32 = view.astype(jnp.float32)
        assert widened_view_dots(mixed, q, f32, f32, **kw) == 0

    def _decode_inputs(self, pool_dtype):
        rng = np.random.default_rng(21)
        attn = _mha_params(jax.random.key(5))
        x = jnp.asarray(rng.standard_normal((S, 1, H * D)), jnp.float32)
        # the whole pool, every layer and every pad lane full of noise:
        # the step must read the addressed layer's real lanes only
        shape = (LAYERS, NB * BS, H * D + PAD)
        kp = jnp.asarray(rng.standard_normal(shape), pool_dtype)
        vp = jnp.asarray(rng.standard_normal(shape), pool_dtype)
        pos = jnp.asarray([5, 0, 17], jnp.int32)
        return attn, x, kp, vp, pos

    @pytest.mark.parametrize("pool_dtype", ("bfloat16", "float16"))
    def test_decode_inside_the_bound_rounding_earns(self, pool_dtype):
        """A decode step on a narrow pool against plain f32 math on the
        SAME stored K and V. What differs is that q and the
        probabilities are rounded to the pool's dtype before products
        that accumulate in f32, each with relative error u = 2^-p at
        most (p = 8 significand bits for bf16, 11 for f16):

        - a score s_t = q.k_t / sqrt(Dh) moves by at most
          e_t = u * sum_d |q_d k_td| / sqrt(Dh);
        - softmax: dp_t = p_t (ds_t - sum_j p_j ds_j), so
          |dp_t| <= p_t (e_t + sum_j p_j e_j); rounding p_t adds u p_t;
        - o_d = sum_t p_t v_td moves by at most sum_t |dp_t| |v_td|;
        - y_j = sum_i o_i W_ij + b_j by at most sum_i |do_i| |W_ij|.

        First order in u; a tenth of room covers the second order and
        the f32 sums."""
        from quintnet_tpu.nn.attention import _qkv_heads, mha_verify_paged

        dt = jnp.dtype(pool_dtype)
        attn, x, kp, vp, pos = self._decode_inputs(dt)
        tables = _tables()
        y, kp2, vp2 = jax.jit(
            lambda x, kp, vp: mha_verify_paged(
                attn, x, kp, vp, pos[:, None], jnp.ones_like(pos),
                num_heads=H, block_tables=tables, block_size=BS,
                layer=jnp.int32(LAYER)))(x, kp, vp)
        assert y.dtype == jnp.float32

        q, _k, _v = _qkv_heads(attn, x, H)
        q = np.asarray(q, np.float64)[:, :, 0]              # [S, H, D]
        rows = (np.asarray(tables)[:, :, None] * BS
                + np.arange(BS)[None, None, :]).reshape(S, M * BS)
        k, v = (np.asarray(p[LAYER, :, :H * D].astype(jnp.float32),
                           np.float64).reshape(NB * BS, H, D)[rows]
                for p in (kp2, vp2))
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        live = (np.arange(M * BS)[None, :]
                <= np.asarray(pos)[:, None])[:, None]       # [S, 1, T]
        sc = np.einsum("shd,shtd->sht", q, k) / math.sqrt(D)
        sc = np.where(live, sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        o = np.einsum("sht,shtd->shd", pr, v).reshape(S, H * D)
        w = np.asarray(attn["proj"]["w"], np.float64)
        want = o @ w + np.asarray(attn["proj"]["b"], np.float64)

        u = 2.0 ** -(jnp.finfo(dt).nmant + 1)
        e = u * np.einsum("shd,shtd->sht", np.abs(q),
                          np.abs(k)) / math.sqrt(D)
        dp = pr * (e + (pr * e).sum(-1, keepdims=True) + u)
        do = np.einsum("sht,shtd->shd", dp, np.abs(v)).reshape(S, H * D)
        bound = 1.1 * do @ np.abs(w)
        gap = np.abs(np.asarray(y, np.float64)[:, 0] - want)
        assert (gap <= bound).all(), (gap.max(), bound.max())
        # and the bound says something: under a tenth of the output at
        # bf16 (worst case: every error the same sign), and the
        # rounding is really there (f32 products on the same K and V
        # would sit at 1e-7)
        assert bound.max() < 0.1 * np.abs(want).max(), bound.max()
        assert gap.max() > 1e-6 * np.abs(want).max(), gap.max()

    @pytest.mark.parametrize("pool_dtype,narrowed", (("float32", False),
                                                     ("bfloat16", True)))
    def test_f32_pool_rounds_and_pads_nothing(self, pool_dtype, narrowed):
        """An f32 view takes the branch it always took: no convert of q
        or of the probabilities to a 16-bit float, no pad of the query
        row. The bf16 pool is the positive control: q goes down before
        the per-row walk and the probabilities inside its kernel, once
        each, and the spread query rows — two, a head each, on the lane
        diagonal — are padded to a sublane tile of the stored dtype,
        with their positions."""
        from quintnet_tpu.nn.attention import mha_verify_paged

        attn, x, kp, vp, pos = self._decode_inputs(jnp.dtype(pool_dtype))
        jaxpr = jax.make_jaxpr(
            lambda x, kp, vp: mha_verify_paged(
                attn, x, kp, vp, pos[:, None], jnp.ones_like(pos),
                num_heads=H, block_tables=_tables(), block_size=BS,
                layer=jnp.int32(LAYER)))(x, kp, vp)
        eqns = _eqns(jaxpr)

        def down(eqns, ndim):
            return [e for e in eqns
                    if e.primitive.name == "convert_element_type"
                    and e.invars[0].aval.dtype == jnp.float32
                    and e.params["new_dtype"].itemsize == 2
                    and e.invars[0].aval.ndim >= ndim]

        pads = [e for e in eqns if e.primitive.name == "pad"
                and e.invars[0].aval.shape[1] == H]   # the H query rows
        kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
        # (ndim 3: not the pool's rows on their way into the scatter)
        assert len(down(eqns, 3)) == (1 if narrowed else 0)
        assert len(pads) == (2 if narrowed else 0), pads
        assert len(kernels) == (1 if narrowed else 0)
        if narrowed:
            assert {e.outvars[0].aval.shape[1] for e in pads} == {16}
            inside = []
            _walk_all(kernels[0].params["jaxpr"], inside.append)
            assert len(down(inside, 2)) == 1, inside


def _walk_all(jaxpr, visit):
    """Every eqn of a kernel's body, loops and branches included."""
    from quintnet_tpu.analysis.jaxpr_audit import _as_open, _subjaxprs

    for e in jaxpr.eqns:
        visit(e)
        for sub in _subjaxprs(e.params):
            _walk_all(_as_open(sub), visit)


# ---------------------------------------------------------------------
# 6. few query rows: the view is contracted as gathered, heads on the
#    lane diagonal
# ---------------------------------------------------------------------

def _old_form(pool, tables):
    """The gathered view cut into heads — what every program did before
    decode and verify stopped: 1 split a view."""
    pages = pool.reshape(LAYERS, NB, BS, -1)[LAYER, tables]
    pages = pages[..., :H * D].reshape(S, M, BS, H, D)
    return pages.transpose(0, 3, 1, 2, 4).reshape(S, H, M * BS, D)


class TestLaneDiagonal:
    SEQ = 128     # a 128-wide prefill bucket: 4 heads x 128 = 512 rows

    @pytest.fixture(scope="class")
    def engines(self):
        """family -> (bf16-KV engine, its params), built on first use:
        tracing only, nothing compiles."""
        from quintnet_tpu.models.granite_hybrid import (
            GraniteHybridConfig, granite_hybrid_init)
        from quintnet_tpu.models.llama import LlamaConfig, llama_init
        from quintnet_tpu.serve import granite_hybrid_family, llama_family

        built = {}

        def get(family, kv_dtype="bf16"):
            if (family, kv_dtype) in built:
                return built[family, kv_dtype]
            kw = dict(kv_dtype=kv_dtype, max_seq_len=self.SEQ,
                      num_blocks=160, spec=SpecConfig(max_draft=4))
            if family == "gpt2":
                cfg = GPT2Config.tiny(n_layer=2, n_positions=self.SEQ)
                fam, p = gpt2_family(cfg), gpt2_init(jax.random.key(0), cfg)
            elif family == "llama":
                cfg = LlamaConfig.tiny(n_positions=self.SEQ)
                fam, p = llama_family(cfg), llama_init(jax.random.key(4),
                                                       cfg)
            else:
                cfg = GraniteHybridConfig.tiny()
                fam, p = granite_hybrid_family(cfg), granite_hybrid_init(
                    jax.random.key(6), cfg)
                kw.update(prefix_cache=False, spec=None)
            built[family, kv_dtype] = (
                _engine(None, "xla", family=fam, fam_params=p, **kw), p)
            return built[family, kv_dtype]

        return get

    @staticmethod
    def _program(eng, which):
        """(fn, args) of an engine program by name; a recurrent
        family's verify is its contract, jitted by hand (the engine
        refuses speculation there)."""
        for sentinel, args in eng._warmup_calls():
            if sentinel.fn.__name__ == which:
                return sentinel.fn, args
        assert which == "serve_verify_b4", which
        fam, pool = eng.family, eng.pool
        k, v, ssm, conv = pool.caches()
        rows = eng.max_slots

        def verify(params, k, v, ssm, conv, ids, starts, lens, tables):
            return fam.verify(params, k, v, ids, starts, lens, tables,
                              pool.block_size, policy=pool.policy,
                              state=(ssm, conv))

        return verify, (eng.params, k, v, ssm, conv,
                        jnp.zeros((rows, 5), jnp.int32),
                        jnp.zeros((rows,), jnp.int32),
                        jnp.ones((rows,), jnp.int32),
                        jnp.zeros((rows, eng.table_width), jnp.int32))

    @pytest.mark.parametrize("family", ("gpt2", "llama", "granite_hybrid"))
    @pytest.mark.parametrize("which,splits", (
        ("serve_decode", 0), ("serve_verify_b4", 0),
        ("serve_prefill_b16", 0), ("serve_prefill_b128", 2)))
    def test_only_many_rows_split_the_view(self, engines, family, which,
                                           splits):
        """Decode (4 query rows a slot), a verify run (20) and a
        16-wide prefill (64) of a bf16 pool gather NO view: each row
        walks its live blocks of the carried pool in place, one call a
        layer scan; the 128-wide bucket's 512 rows pass the module's
        bound, gather the table's width and take the split, k and v;
        and no form widens a view."""
        from quintnet_tpu.nn.attention import _MAX_DIAG_ROWS

        assert 4 * 16 <= _MAX_DIAG_ROWS < 4 * 128
        eng, _p = engines(family)
        fn, args = self._program(eng, which)
        kw = dict(table_width=eng.table_width,
                  block_size=eng.pool.block_size)
        assert view_head_splits(fn, *args, **kw) == splits
        assert widened_view_dots(fn, *args, **kw) == 0
        assert gathered_view_gathers(
            fn, *args, num_blocks=eng.pool.num_blocks,
            table_width=eng.table_width) == splits
        assert row_walk_calls(
            fn, *args, pool_shape=eng.pool.k.shape) == (0 if splits else 1)

    @pytest.mark.parametrize("kv_dtype,gathers", (("f32", 2), ("int8", 4),
                                                  ("fp8", 2)))
    def test_other_pools_keep_the_split(self, engines, kv_dtype, gathers):
        """Only rows stored in a float narrower than q are walked where
        they lie: an f32 pool, a scaled policy's dequantized view and a
        float8 pool's widened one gather the table's width (k and v,
        and a scaled policy's scales) and take the branch they always
        took."""
        eng, _p = engines("gpt2", kv_dtype)
        fn, args = self._program(eng, "serve_decode")
        assert view_head_splits(fn, *args, table_width=eng.table_width,
                                block_size=eng.pool.block_size) == 2
        assert gathered_view_gathers(
            fn, *args, num_blocks=eng.pool.num_blocks,
            table_width=eng.table_width) == gathers
        assert row_walk_calls(fn, *args, pool_shape=eng.pool.k.shape) == 0

    def test_counter_sees_the_old_form(self):
        """The zeroes mean something: the gathered rows cut into heads
        count one a view, with the pad lanes sliced off first or with
        none to slice; the rows contracted as gathered count none."""
        pool = jnp.zeros((LAYERS, NB * BS, H * D + PAD), jnp.bfloat16)
        bare = jnp.zeros((LAYERS, NB * BS, H * D), jnp.bfloat16)
        q = jnp.zeros((S, H, H * D + PAD), jnp.bfloat16)
        tables = _tables()

        def as_gathered(pool, q):
            rows = pool.reshape(LAYERS, NB, BS, -1)[LAYER, tables]
            return jnp.einsum("brf,btf->brt", q,
                              rows.reshape(S, M * BS, -1))

        kw = dict(table_width=M, block_size=BS)
        assert view_head_splits(_old_form, pool, tables, **kw) == 1
        assert view_head_splits(_old_form, bare, tables, **kw) == 1
        assert view_head_splits(
            lambda k, v: (_old_form(k, tables), _old_form(v, tables)),
            pool, pool, **kw) == 2
        assert view_head_splits(as_gathered, pool, q, **kw) == 0

    def test_every_writer_leaves_the_pad_lanes_zero(self, params):
        """The diagonal form multiplies the pool's pad lanes by exact
        zeros, so they must hold finite numbers; every writer holds
        them at ZERO: the allocation, the programs' scatter
        (``_pool_rows``: prefill, decode, the copy-on-write copy of
        whole rows) and the host's ``write_slots`` (import, the tier)."""
        eng = _engine(params, "xla", kv_dtype="bf16")
        pool = eng.pool
        real = pool.n_kv_heads * pool.head_dim
        assert pool.k.shape[-1] > real          # 32 features in 128 lanes

        def pad_lanes_zero():
            return not any(np.asarray(a[..., real:], np.float32).any()
                           for a in (pool.k, pool.v))

        assert pad_lanes_zero()
        rng = np.random.default_rng(29)
        shared = rng.integers(0, CFG.vocab_size, (10,)).astype(np.int32)
        prompts = [np.concatenate([shared, rng.integers(
            0, CFG.vocab_size, (t,)).astype(np.int32)]) for t in (3, 5, 2)]
        _serve(eng, prompts, 6, arrivals=[0, 20, 40])
        assert eng.metrics.summary()["prefix_hit_tokens"] > 0  # the copy ran
        assert np.asarray(pool.k[..., :real], np.float32).any()
        assert pad_lanes_zero()
        idx = np.arange(8, 24)
        rec = rng.standard_normal((pool.n_layers, len(idx),
                                   pool.n_kv_heads, pool.head_dim))
        pool.update(*pool.write_slots(idx, rec, -rec))
        k_back, _v = pool.read_slots(idx)
        np.testing.assert_array_equal(
            np.asarray(k_back, np.float32),
            np.asarray(jnp.asarray(rec, jnp.bfloat16), np.float32))
        assert pad_lanes_zero()

    @staticmethod
    def _f64_attention(q, k, v, live, scale, u):
        """Plain f64 attention of q [S, Hq, P, D] over stored k, v
        [S, Hq, T, D] under ``live`` [S, P, T], and the first-order
        bound on what rounding q and the probabilities to a dtype of
        unit roundoff ``u`` may move it by (the derivation in
        test_decode_inside_the_bound_rounding_earns, before the output
        projection)."""
        live = live[:, None]
        sc = np.einsum("shpd,shtd->shpt", q, k) * scale
        sc = np.where(live, sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        want = np.einsum("shpt,shtd->shpd", pr, v)
        e = np.where(live, u * np.einsum("shpd,shtd->shpt", np.abs(q),
                                         np.abs(k)) * scale, 0.0)
        dp = pr * (e + (pr * e).sum(-1, keepdims=True) + u)
        return want, 1.1 * np.einsum("shpt,shtd->shpd", dp, np.abs(v))

    @pytest.mark.parametrize("pool_dtype", ("bfloat16", "float16"))
    @pytest.mark.parametrize("P", (1, 3, 5))
    @pytest.mark.parametrize("heads", ("mha", "gqa", "grouped"))
    def test_diagonal_equals_split_inside_the_rounding_bound(
            self, heads, P, pool_dtype, monkeypatch):
        """The three forms on the SAME narrow pool — every layer and
        every pad lane full of noise — for MHA (3 heads), llama's GQA
        (4 query heads on 2 kv heads, repeated) and the hybrid's
        grouped rows (2 kv heads, the 2 query heads of each as rows of
        one score matrix, a stated score scale): the per-row walk, the
        view gathered whole with heads on the lane diagonal, and the
        view split into heads, all inside the bound that rounding q and
        the probabilities earns against f64 math on the stored K and V;
        the two gathered forms each other's to the order of f32 sums
        (the walk rounds a probability before the softmax's sum is
        known: another rounding of the same size, not another order);
        the pool bytes equal to bits."""
        import quintnet_tpu.nn.attention as attention

        hq, hkv, g, scale = {"mha": (3, 3, 1, None), "gqa": (4, 2, 1, None),
                             "grouped": (2, 2, 2, 0.25)}[heads]
        dt = jnp.dtype(pool_dtype)
        rng = np.random.default_rng(23)
        shape = (LAYERS, NB * BS, hkv * D + PAD)
        kp = jnp.asarray(rng.standard_normal(shape), dt)
        vp = jnp.asarray(rng.standard_normal(shape), dt)
        q = jnp.asarray(rng.standard_normal((S, hq, g * P, D)), jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((S, hkv, P, D)),
                            jnp.float32) for _ in range(2))
        tables = _tables()
        positions = (jnp.asarray([5, 0, 17])[:, None]
                     + jnp.arange(P, dtype=jnp.int32)[None, :])
        lens = jnp.asarray([P, max(P - 1, 1), P], jnp.int32)

        def run(most_rows, interpret=True):
            # few rows: the per-row walk under the interpreter; without
            # one (and pages this narrow) the view gathered whole, heads
            # on the lane diagonal — what every platform but the TPU runs
            monkeypatch.setattr(attention, "_MAX_DIAG_ROWS", most_rows)
            monkeypatch.setattr(_kernels(), "INTERPRET", interpret)
            fn = lambda q, k, v, kp, vp: attention.paged_attend(  # noqa: E731
                q, k, v, (kp, vp), jnp.int32(LAYER), positions, lens,
                tables, block_size=BS, scale=scale)
            assert view_head_splits(
                fn, q, k, v, kp, vp, table_width=M,
                block_size=BS) == (2 if most_rows == 0 else 0)
            assert row_walk_calls(fn, q, k, v, kp, vp, pool_shape=shape) \
                == (1 if most_rows and interpret else 0)
            return jax.jit(fn)(q, k, v, kp, vp)

        walk, pools = run(attention._MAX_DIAG_ROWS)
        diag, pools_diag = run(attention._MAX_DIAG_ROWS, interpret=False)
        split, pools_split = run(0)
        for other in (pools_diag, pools_split):
            for a, b in zip(pools, other):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))
        assert walk.dtype == diag.dtype == split.dtype == jnp.float32
        assert walk.shape == diag.shape == split.shape == q.shape

        rows = (np.asarray(tables)[:, :, None] * BS
                + np.arange(BS)[None, None, :]).reshape(S, M * BS)
        ks, vs = (np.asarray(p[LAYER, :, :hkv * D].astype(jnp.float32),
                             np.float64).reshape(NB * BS, hkv, D)[rows]
                  .transpose(0, 2, 1, 3) for p in pools)   # [S, Hkv, T, D]
        rep = hq // hkv
        ks, vs = np.repeat(ks, rep, axis=1), np.repeat(vs, rep, axis=1)
        live = np.tile(np.arange(M * BS)[None, None, :]
                       <= np.asarray(positions)[:, :, None], (1, g, 1))
        u = 2.0 ** -(jnp.finfo(dt).nmant + 1)
        want, bound = self._f64_attention(
            np.asarray(q, np.float64), ks, vs, live,
            1 / math.sqrt(D) if scale is None else scale, u)
        for got in (walk, diag, split):
            gap = np.abs(np.asarray(got, np.float64) - want)
            assert (gap <= bound).all(), (gap.max(), bound.max())
            assert gap.max() > 1e-6 * np.abs(want).max(), gap.max()
        assert bound.max() < 0.1 * np.abs(want).max(), bound.max()
        np.testing.assert_allclose(np.asarray(diag), np.asarray(split),
                                   atol=2e-6, rtol=0)


# ---------------------------------------------------------------------
# 7. few query rows on a narrow pool: each row walks its LIVE key blocks
#    of the carried pool, in place (ops/paged_attention
#    .paged_walk_attention under the interpreter)
# ---------------------------------------------------------------------

class TestRowWalk:
    """The per-row form against ``_lane_diag_sdpa`` on the gathered
    view — the form it replaced — at the cells' head geometries: both
    inside the bound rounding q and the probabilities earns against f64
    math on the stored rows (TestLaneDiagonal's), the pool bytes equal
    to bits, for rows of every length a walk can end at."""

    ROWS, WIDTH, PAGE, KEY_BLOCK = 3, 8, 16, 32     # 128 positions a row
    #   name: (query heads, kv heads, head width, score scale)
    HEADS = {"xl": (25, 25, 64, None),              # 1,600 of 1,664 lanes
             "gqa": (32, 8, 64, 0.0078125),         # the hybrid's, stated
             "grouped": (48, 8, 128, None)}         # the window family's
    #   name: each row's LAST position (None: an all-zero table, the run
    #   at position 0 — an inactive, mid-prefill or warm-up row)
    LENGTHS = {"empty": (None, None, None),
               "edges": (KEY_BLOCK - 2, KEY_BLOCK - 1, KEY_BLOCK),
               "full": (WIDTH * PAGE - 1,) * 3,
               "one_live": (None, 77, None)}

    @pytest.mark.parametrize("pool_dtype", ("bfloat16", "float16"))
    @pytest.mark.parametrize("P", (1, 3, 5))
    @pytest.mark.parametrize("lengths", tuple(LENGTHS))
    @pytest.mark.parametrize("heads", tuple(HEADS))
    def test_walk_equals_gathered_inside_the_rounding_bound(
            self, heads, lengths, P, pool_dtype, monkeypatch):
        import quintnet_tpu.nn.attention as attention
        from quintnet_tpu.serve.kv_pool import feature_width

        hq, hkv, d, scale = self.HEADS[heads]
        g = hq // hkv
        rows, m, bs = self.ROWS, self.WIDTH, self.PAGE
        dt = jnp.dtype(pool_dtype)
        rng = np.random.default_rng(31)
        blocks = 1 + rows * m
        shape = (2, blocks * bs, feature_width(hkv, d))
        kp = jnp.asarray(rng.standard_normal(shape), dt)
        vp = jnp.asarray(rng.standard_normal(shape), dt)
        q = jnp.asarray(rng.standard_normal((rows, hkv, g * P, d)),
                        jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((rows, hkv, P, d)),
                            jnp.float32) for _ in range(2))
        lasts = self.LENGTHS[lengths]
        tables = jnp.asarray(
            [[0] * m if last is None else
             [1 + r * m + j for j in range(m)]
             for r, last in enumerate(lasts)], jnp.int32)
        starts = [0 if last is None else last - (P - 1) for last in lasts]
        positions = (jnp.asarray(starts, jnp.int32)[:, None]
                     + jnp.arange(P, dtype=jnp.int32)[None, :])
        lens = jnp.full((rows,), P, jnp.int32)
        layer = jnp.int32(1)
        monkeypatch.setattr(attention, "WALK_KEY_BLOCK", self.KEY_BLOCK)

        def walk(q, k, v, kp, vp):
            return attention.paged_attend(
                q, k, v, (kp, vp), layer, positions, lens, tables,
                block_size=bs, scale=scale, max_diag_rows=hq * P)

        def gathered(q, k, v, kp, vp):
            pools = attention.paged_write(
                kp, vp, layer, k, v, positions, lens, block_tables=tables,
                block_size=bs)
            kr, vr = attention._gather_kv(pools, layer, None, tables,
                                          block_size=bs, head_shape=None)
            return attention._lane_diag_sdpa(
                q, kr, vr, attention._seen(positions, q, tables, bs),
                kv_heads=hkv, scale=scale), pools

        assert row_walk_calls(walk, q, k, v, kp, vp, pool_shape=shape) == 1
        assert gathered_view_gathers(walk, q, k, v, kp, vp,
                                     num_blocks=blocks, table_width=m) == 0
        got, pools = jax.jit(walk)(q, k, v, kp, vp)
        ref, pools_ref = jax.jit(gathered)(q, k, v, kp, vp)
        for a, b in zip(pools, pools_ref):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        assert got.dtype == ref.dtype == jnp.float32
        assert got.shape == ref.shape == q.shape

        slots = (np.asarray(tables)[:, :, None] * bs
                 + np.arange(bs)[None, None, :]).reshape(rows, m * bs)
        ks, vs = (np.asarray(p[1, :, :hkv * d].astype(jnp.float32),
                             np.float64).reshape(blocks * bs, hkv, d)[slots]
                  .transpose(0, 2, 1, 3) for p in pools)   # [S, Hkv, T, D]
        live = np.tile(np.arange(m * bs)[None, None, :]
                       <= np.asarray(positions)[:, :, None], (1, g, 1))
        u = 2.0 ** -(jnp.finfo(dt).nmant + 1)
        want, bound = TestLaneDiagonal._f64_attention(
            np.asarray(q, np.float64), ks, vs, live,
            1 / math.sqrt(d) if scale is None else scale, u)
        for out in (got, ref):
            gap = np.abs(np.asarray(out, np.float64) - want)
            assert (gap <= bound).all(), (gap.max(), bound.max())
        # the bound still says something where an output is a mean over
        # 128 positions of noise (the worst case adds every error with
        # one sign; the mean shrinks with the root of the count)
        assert bound.max() < 0.2 * np.abs(want).max(), bound.max()

    def test_a_row_reads_its_live_key_blocks_and_no_more(self):
        """What the kernel is asked to walk: ``last // key_block + 1``
        blocks a row, one for a row at position 0, never more than the
        table holds — read off the trip counts it is handed."""
        pa = _kernels()
        kb, bs, m = 32, 16, 8
        last = jnp.asarray([0, kb - 2, kb - 1, kb, 77, m * bs - 1])
        rows = len(last)
        qpos = jnp.stack([last - 1, last], axis=1)       # a run of two
        qpos = jnp.pad(qpos, ((0, 0), (0, 14)), constant_values=-1)
        pool = jnp.zeros((1, 3 * bs, 128), jnp.bfloat16)
        closed = jax.make_jaxpr(
            lambda qp: pa.paged_walk_attention(
                jnp.zeros((rows, 16, 128), jnp.bfloat16), qp, pool, pool,
                jnp.int32(0), jnp.zeros((rows, m), jnp.int32),
                block_size=bs, key_block=kb, head_dim=64))(qpos)
        (call,) = [e for e in closed.jaxpr.eqns
                   if e.primitive.name == "pallas_call"]
        trips = jax.core.eval_jaxpr(
            closed.jaxpr.replace(outvars=[call.invars[2]]), closed.consts,
            qpos)[0]
        np.testing.assert_array_equal(np.asarray(trips), [1, 1, 1, 2, 3, 4])


class TestLatentRowWalk:
    """The same walk over a pool of ONE row kind (a latent family's: no
    v pool, every head reads the same row as key and as value;
    nn/attention.latent_attend_absorbed) against the form it replaced
    there, the view gathered at the table's width — both inside the
    bound rounding the query and the probabilities earns against f64
    math on the stored rows."""

    WIDTH, PAGE, KEY_BLOCK = 8, 16, 32              # 128 positions a row
    #   name: (heads, rank, rope): a latent row of rank + rope features
    #   in 128-lane pool rows; "tiled": a rank of whole lane tiles, whose
    #   values' product leaves the rotary and pad lanes out
    WIDTHS = {"tiny": (4, 24, 16), "tiled": (2, 128, 16)}
    #   each row's LAST position (None: an all-null table, the run at
    #   position 0 — an inactive, mid-prefill or warm-up row)
    LASTS = (0, KEY_BLOCK - 1, KEY_BLOCK, WIDTH * PAGE - 1, None, 77)

    def _case(self, width, P, pool_dtype):
        from quintnet_tpu.serve.kv_pool import feature_width

        h, rank, rope = self.WIDTHS[width]
        m, bs = self.WIDTH, self.PAGE
        rows = len(self.LASTS)
        dt = jnp.dtype(pool_dtype)
        rng = np.random.default_rng(36)
        blocks = 1 + rows * m
        f = feature_width(1, rank + rope)
        noise = rng.standard_normal((2, blocks * bs, f))
        noise[..., rank + rope:] = 0.0              # every writer's pads
        pool = jnp.asarray(noise, dt)
        q_lat = jnp.asarray(rng.standard_normal((rows, P, h, rank)),
                            jnp.float32)
        q_rope = jnp.asarray(rng.standard_normal((rows, P, h, rope)),
                             jnp.float32)
        fresh = jnp.asarray(rng.standard_normal((rows, P, rank + rope)),
                            jnp.float32)
        tables = jnp.asarray(
            [[0] * m if last is None else
             [1 + r * m + j for j in range(m)]
             for r, last in enumerate(self.LASTS)], jnp.int32)
        # a run of P ends at the row's last position (one that would
        # start before 0 starts there)
        starts = [max((last or 0) - (P - 1), 0) for last in self.LASTS]
        positions = (jnp.asarray(starts, jnp.int32)[:, None]
                     + jnp.arange(P, dtype=jnp.int32)[None, :])
        lens = jnp.full((rows,), P, jnp.int32)
        return dict(h=h, rank=rank, rope=rope, m=m, bs=bs, rows=rows,
                    dt=dt, blocks=blocks, f=f, pool=pool, q_lat=q_lat,
                    q_rope=q_rope, fresh=fresh, tables=tables,
                    positions=positions, lens=lens)

    @staticmethod
    def _attend(c, scale=0.125):
        import quintnet_tpu.nn.attention as attention

        def fn(q_lat, q_rope, fresh, pool):
            layer = jnp.int32(1)
            pool = attention.latent_write(
                pool, layer, fresh, c["positions"], c["lens"],
                block_tables=c["tables"], block_size=c["bs"])
            return attention.latent_attend_absorbed(
                q_lat, q_rope, pool, layer, c["positions"], c["tables"],
                block_size=c["bs"], scale=scale), pool
        return fn

    @pytest.mark.parametrize("pool_dtype", ("bfloat16", "float16"))
    @pytest.mark.parametrize("P", (1, 3))
    @pytest.mark.parametrize("width", tuple(WIDTHS))
    def test_walk_equals_gathered_inside_the_rounding_bound(
            self, width, P, pool_dtype, monkeypatch):
        import quintnet_tpu.nn.attention as attention

        c = self._case(width, P, pool_dtype)
        h, rank, rope, m, bs = (c[n] for n in ("h", "rank", "rope", "m",
                                                "bs"))
        monkeypatch.setattr(attention, "WALK_KEY_BLOCK", self.KEY_BLOCK)
        fn, scale = self._attend(c), 0.125
        args = (c["q_lat"], c["q_rope"], c["fresh"], c["pool"])
        shape = c["pool"].shape

        with attention.noted_reads() as reads:
            assert row_walk_calls(fn, *args, pool_shape=shape, pools=1) == 1
        assert reads == [self.KEY_BLOCK]
        assert gathered_view_gathers(fn, *args, num_blocks=c["blocks"],
                                     table_width=m) == 0
        got, pool = jax.jit(fn)(*args)
        # no interpreter: every platform but the TPU gathers the view
        # (a new function: a trace is remembered by the function traced)
        monkeypatch.setattr(_kernels(), "INTERPRET", False)
        fn = self._attend(c)
        with attention.noted_reads() as reads:
            assert gathered_view_gathers(
                fn, *args, num_blocks=c["blocks"], table_width=m) == 1
        assert reads == [m * bs]
        ref, pool_ref = jax.jit(fn)(*args)
        np.testing.assert_array_equal(np.asarray(pool, np.float32),
                                      np.asarray(pool_ref, np.float32))
        # the rotary and pad lanes of a value row are dropped
        assert got.shape == ref.shape == (c["rows"], P, h, rank)
        assert got.dtype == ref.dtype == jnp.float32

        slots = (np.asarray(c["tables"])[:, :, None] * bs
                 + np.arange(bs)[None, None, :]).reshape(c["rows"], m * bs)
        stored = np.asarray(pool[1].astype(jnp.float32), np.float64)[slots]
        assert not stored[..., rank + rope:].any()
        keys = np.broadcast_to(stored[:, None, :, :rank + rope],
                               (c["rows"], h, m * bs, rank + rope))
        q = np.concatenate([np.asarray(c["q_lat"], np.float64),
                            np.asarray(c["q_rope"], np.float64)],
                           axis=-1).transpose(0, 2, 1, 3)   # [S, H, P, D]
        live = (np.arange(m * bs)[None, None, :]
                <= np.asarray(c["positions"])[:, :, None])
        u = 2.0 ** -(jnp.finfo(c["dt"]).nmant + 1)
        want, bound = TestLaneDiagonal._f64_attention(
            q, keys, keys[..., :rank], live, scale, u)
        for out in (got, ref):
            gap = np.abs(np.asarray(out, np.float64).transpose(0, 2, 1, 3)
                         - want)
            assert (gap <= bound).all(), (gap.max(), bound.max())
        assert bound.max() < 0.2 * np.abs(want).max(), bound.max()

    def test_the_kernels_own_vmem_sum_decides_which_rows_walk(
            self, monkeypatch):
        """A verify bucket's query rows are 128 a drafted token at the
        published widths: the walk takes as many as TWICE its own VMEM
        sum keeps inside the cap, the gathered form the rest; and an
        f32 pool, which the kernel does not take."""
        import quintnet_tpu.nn.attention as attention

        pa = _kernels()
        c = self._case("tiny", 3, "bfloat16")
        args = (c["q_lat"], c["q_rope"], c["fresh"], c["pool"])
        shape = c["pool"].shape
        need = pa.walk_vmem_bytes(
            rows=16, lanes=c["f"], kept_lanes=c["f"], key_block=128,
            pools=1, pool_dtype=c["dt"], q_dtype=c["dt"])
        for cap, walks in ((2 * need, 1), (2 * need - 1, 0)):
            monkeypatch.setattr(pa, "VMEM_CAP_BYTES", cap)
            with attention.noted_reads() as reads:
                assert row_walk_calls(self._attend(c), *args,
                                      pool_shape=shape, pools=1) == walks
            assert reads == [128]                   # the table's width
        monkeypatch.undo()
        wide = args[:3] + (c["pool"].astype(jnp.float32),)
        assert row_walk_calls(self._attend(c), *wide, pool_shape=shape,
                              pools=1) == 0
        assert gathered_view_gathers(
            self._attend(c), *wide, num_blocks=c["blocks"],
            table_width=c["m"]) == 1
        # the published decode and verify shapes: 128 heads a token on
        # 640-lane rows, 512 value lanes kept
        for tokens in (1, 5, 9):
            assert 2 * pa.walk_vmem_bytes(
                rows=128 * tokens, lanes=640, kept_lanes=512,
                key_block=256, pools=1, pool_dtype=jnp.bfloat16,
                q_dtype=jnp.bfloat16) <= pa.VMEM_CAP_BYTES


def test_ops_import_surface():
    """ops/ exports its public kernel entry points (the previously
    empty ``__init__`` belied its own docstring)."""
    import quintnet_tpu.ops as ops

    expected = {"resident_flash_attention", "blockwise_attention",
                "pallas_flash_attention", "paged_attention",
                "paged_quant_window_update", "ring_attention",
                "zigzag_ring_attention", "ulysses_attention"}
    assert expected == set(ops.__all__)
    for name in ops.__all__:
        assert callable(getattr(ops, name)), name


def test_attn_kernel_ladder_pinned():
    assert attn_kernels() == ("xla", "pallas")
