"""The one paged attention (nn/attention.paged_attend) of every serving
program, against plain attention written here, and the per-row walk
(ops/paged_attention.py) against the gathered view.

The contract ladder:

1. **Reference matrix** — the real nn/attention entry point
   (``mha_verify_paged`` at decode, verify and prefill widths), across
   every ``kv_layout_policies`` entry x verify bucket widths x chunked
   prefill offsets, twice back to back (requant on top of requant),
   against softmax attention over the pool's dequantized history plus
   the fresh run, written in the test: outputs within four f32 ulps for
   f32/fake_quant (``F32_ATOL``), within ``QUANT_ATOL`` for
   bf16/int8/fp8, and the written POOL BYTES equal to the policy's own
   ``quant`` of the fresh keys and values.
2. **GQA** — the same for the llama block's attention call on its own
   projections and rotary (4 query heads on 2 kv heads).
3. **Engine goldens** — the engine under each mechanism (prefix cache,
   speculative decode, chunked prefill, preemption, GQA, tp=2) serves
   TOKEN-IDENTICAL to the same engine without it, same policy and
   keys, greedy and sampled, f32 and int8, gpt2 and llama.
4. **Structure** — the jaxpr auditor (analysis.gathered_view_gathers)
   counts 2-4 full-row block-table gathers a layer in every gathered
   program; compile counts and sentinels are pinned; ``attn_kernel``
   takes one value.
5. **Arithmetic contract** — every gathered-view program contracts the
   view in the dtype it is STORED in (analysis.widened_view_dots reads
   zero for f32 and bf16 pools, gpt2 and llama), a bf16 pool's decode
   stays inside the bound 8 bits of ``q`` and of the probabilities
   earn, and an f32 pool's program rounds and pads nothing.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.analysis import (gathered_view_gathers, row_walk_calls,
                                   view_head_splits, widened_view_dots)
from quintnet_tpu.analysis.specs import kv_layout_policies
from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.serve import ServeEngine, SpecConfig, gpt2_family
from quintnet_tpu.serve.kv_quant import make_policy

CFG = GPT2Config.tiny(n_layer=2)

# bf16 / int8 / fp8 outputs: the reference rounds where the path states
# it rounds (a bf16 pool's q and probabilities, before f32-accumulating
# products; a scaled pool's history dequantized, the fresh run exact),
# so what is left is the order of f32 sums; the pin leaves headroom for
# a platform's lowering of ops that are not exact by construction
QUANT_ATOL = 1e-6

# f32 / fake_quant outputs: the same f32 math, compiled in two programs
# that are free to vectorise a reduction (a contraction, the softmax's
# sum) in another order each time; float addition does not associate.
# Hold the outputs to four ulps of [0.5, 1) and the POOL to bits.
F32_ATOL = 4 * 2.0 ** -24


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


# ---------------------------------------------------------------------
# 1. the reference matrix through the real mha entry point
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


def _assert_out(ya, yb, policy):
    ya, yb = np.asarray(ya), np.asarray(yb)
    atol = F32_ATOL if policy.name in ("f32", "fake_quant") else QUANT_ATOL
    np.testing.assert_allclose(ya, yb, atol=atol, rtol=0)


def _history(pool, policy, tables, h, d):
    """Each row's cached history as ``pool`` holds it, dequantized:
    (k, v) [S, T, h, d] f32, position-ordered through ``tables``."""
    out = []
    for c in range(2):
        rows = pool[c][LAYER, :, :h * d].reshape(NB, BS, h, d)[tables]
        scale = (pool[2 + c][LAYER][tables][:, :, None, :, None]
                 if policy.scaled else None)
        rows = policy.dequant(rows, scale)               # [S, M, BS, h, d]
        out.append(rows.reshape(rows.shape[0], -1, h, d))
    return out


def _dense_attention(q, k, v, pool, policy, positions, lens, tables):
    """Plain softmax attention of ``q`` [S, Hq, P, D] over each row's
    history in ``pool`` BEFORE the call, dequantized, with the fresh
    run ``k``/``v`` [S, Hkv, P, D] laid over it at its real positions —
    as the pool stores it under a passthrough policy, exact under a
    scaled one (whose scores read the f32 run). Column t is seen by the
    query at ``positions[s, i]`` iff ``t <= positions[s, i]``; kv head j
    serves query heads ``j * rep .. (j + 1) * rep - 1``. A bf16 pool's
    q and probabilities are rounded to bf16 before f32-accumulated
    products, where the path states it rounds them."""
    hkv, d = k.shape[1], k.shape[3]
    k_all, v_all = _history(pool, policy, tables, hkv, d)
    P = positions.shape[1]
    at = jnp.where(jnp.arange(P)[None, :] < lens[:, None], positions,
                   k_all.shape[1])                       # pad: dropped
    rows = jnp.arange(q.shape[0])[:, None]
    for name, fresh in (("k", k), ("v", v)):
        fresh = fresh.transpose(0, 2, 1, 3)              # [S, P, h, d]
        if not policy.scaled:
            fresh = policy.dequant(policy.quant(fresh))
        if name == "k":
            k_all = k_all.at[rows, at].set(fresh, mode="drop")
        else:
            v_all = v_all.at[rows, at].set(fresh, mode="drop")
    rep = q.shape[1] // hkv
    k_all, v_all = (jnp.repeat(a.transpose(0, 2, 1, 3), rep, axis=1)
                    for a in (k_all, v_all))             # [S, Hq, T, d]
    narrow = jnp.bfloat16 if policy.name == "bf16" else jnp.float32
    scores = jnp.einsum("shpd,shtd->shpt", q.astype(narrow),
                        k_all.astype(narrow),
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    seen = (jnp.arange(k_all.shape[2])[None, None, None, :]
            <= positions[:, None, :, None])
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("shpt,shtd->shpd", probs.astype(narrow),
                      v_all.astype(narrow),
                      preferred_element_type=jnp.float32)


def _assert_run_written(pool, policy, k, v, positions, lens, tables):
    """The pool bytes at every real position of the run are the
    policy's own ``quant`` of the fresh keys and values — under a scaled
    policy at the scale the write left on their block and head."""
    k, v, positions, lens, tables = (np.asarray(a) for a in
                                     (k, v, positions, lens, tables))
    hkv, d = k.shape[1], k.shape[3]
    for c, (arr, fresh) in enumerate(zip(pool[:2], (k, v))):
        stored = _blocks(arr, hkv, d)                    # [NB, BS, h, d]
        for s in range(positions.shape[0]):
            for i in range(int(lens[s])):
                t = int(positions[s, i])
                blk = int(tables[s, t // BS])
                scale = (np.asarray(pool[2 + c])[LAYER, blk][:, None]
                         if policy.scaled else None)
                want = policy.quant(jnp.asarray(fresh[s, :, i]), scale)
                np.testing.assert_array_equal(
                    np.asarray(stored[blk, t % BS], np.float32),
                    np.asarray(want, np.float32))


class TestMhaParityMatrix:
    """Each scenario runs the path from an empty pool twice back to back
    (history accumulates across the calls, covering requant on top of
    requant), each call against the reference from the pool it read."""

    @pytest.fixture(autouse=True)
    def _gathered(self, monkeypatch):
        """The gathered view, the form every platform but the TPU runs:
        a bf16 pool under few query rows would walk each row's live
        blocks under the kernels' interpreter (conftest.py) — another
        rounding of the probabilities, bounded in TestRowWalk, not
        here."""
        monkeypatch.setattr(_kernels(), "INTERPRET", False)

    @pytest.fixture(scope="class")
    def attn(self):
        return _mha_params(jax.random.key(1))

    @staticmethod
    def _step(attn, policy, pool, x, positions, lens, tables):
        """One call of the path and the reference from the same pool:
        (output, reference output, the pool after)."""
        from quintnet_tpu.nn.attention import _proj_out, _qkv_heads
        from quintnet_tpu.nn.attention import mha_verify_paged

        scaled = pool[2] is not None

        def path(x, kp, vp, ks, vs):
            return mha_verify_paged(
                attn, x, kp, vp, positions, lens, num_heads=H,
                layer=jnp.int32(LAYER), block_tables=tables, block_size=BS,
                kv_scales=(ks, vs) if scaled else None,
                policy=policy if scaled else None)

        def reference(x, kp, vp, ks, vs):
            q, k, v = _qkv_heads(attn, x, H)
            o = _dense_attention(q, k, v, [kp, vp, ks, vs], policy,
                                 positions, lens, tables)
            return _proj_out(attn, o, None), k, v

        out = jax.jit(path)(x, *pool)
        want, k, v = jax.jit(reference)(x, *pool)
        after = list(out[1:]) + ([] if scaled else [None, None])
        _assert_run_written(after, policy, k, v, positions, lens, tables)
        return out[0], want, after

    def _run(self, attn, policy, runs, tables, seed):
        """The path over ``runs`` [(starts [S], width P, lens [S])],
        each output's real columns against the reference."""
        rng = np.random.default_rng(seed)
        pool = _pool(policy)
        for starts, P, lens in runs:
            x = jnp.asarray(rng.standard_normal((len(starts), P, H * D)),
                            jnp.float32)
            positions = (jnp.asarray(starts, jnp.int32)[:, None]
                         + jnp.arange(P, dtype=jnp.int32)[None, :])
            lens = jnp.asarray(lens, jnp.int32)
            y, want, pool = self._step(attn, policy, pool, x, positions,
                                       lens, tables)
            for s, n in enumerate(np.asarray(lens)):
                _assert_out(y[s, :n], want[s, :n], policy)

    @pytest.mark.parametrize("policy_name", kv_layout_policies())
    @pytest.mark.parametrize("P", (1, 3, 5))
    def test_verify_and_decode_widths(self, attn, policy_name, P):
        """P=1 IS the decode shape; 3/5 are the verify buckets + 1."""
        starts = np.asarray([5, 0, 11])
        lens = [P, max(P - 1, 1), P]
        self._run(attn, make_policy(policy_name),
                  [(starts, P, lens), (starts + lens, P, lens)],
                  _tables(), seed=7)

    @pytest.mark.parametrize("policy_name", kv_layout_policies())
    def test_chunked_prefill_offsets(self, attn, policy_name):
        """Chunked prefill: one row, two chunks at dynamic offsets
        (start 0 then 8) through the SAME bucket width — the
        prefix-cache tail shape."""
        self._run(attn, make_policy(policy_name),
                  [([0], 8, [8]), ([8], 8, [5])], _tables()[:1], seed=9)


# ---------------------------------------------------------------------
# 2. GQA through the llama block (4 query heads on 2 kv heads)
# ---------------------------------------------------------------------

class TestGQAParity:
    @pytest.mark.parametrize("policy_name", ("f32", "int8"))
    @pytest.mark.parametrize("P", (1, 3))
    def test_llama_verify_gqa(self, policy_name, P):
        """The llama block's attention call on its own projections and
        rotary: 4 query heads reading 2 kv heads, each kv head serving
        its contiguous group of query heads."""
        from quintnet_tpu.models.llama import (LlamaConfig, llama_init,
                                               llama_qkv, llama_rope_tables)
        from quintnet_tpu.nn.attention import _pool_tuple, paged_attend
        from quintnet_tpu.nn.layers import rms_norm_apply

        cfg = LlamaConfig.tiny()
        assert cfg.n_heads != cfg.n_kv_heads  # the point of this test
        policy = make_policy(policy_name)
        params = llama_init(jax.random.key(2), cfg)
        blk = jax.tree.map(lambda a: a[0], params["blocks"])
        pool = _pool(policy, cfg.n_kv_heads, cfg.head_dim)
        tables = _tables()
        rng = np.random.default_rng(3)
        starts = np.asarray([5, 0, 11], np.int32)
        scaled = policy.scaled
        for _ in range(2):
            x = jnp.asarray(rng.standard_normal((S, P, cfg.dim)),
                            jnp.float32)
            positions = (jnp.asarray(starts)[:, None]
                         + jnp.arange(P, dtype=jnp.int32)[None, :])
            lens = jnp.asarray([P, max(P - 1, 1), P], jnp.int32)
            cos, sin = llama_rope_tables(positions, cfg)
            q, k, v = jax.jit(lambda x: llama_qkv(
                blk["attn"], rms_norm_apply(blk["ln1"], x, eps=cfg.rms_eps),
                cfg, cos[:, None], sin[:, None]))(x)

            def path(q, k, v, kp, vp, ks, vs):
                return paged_attend(
                    q, k, v, _pool_tuple(kp, vp, (ks, vs) if scaled
                                         else None),
                    jnp.int32(LAYER), positions, lens, tables,
                    block_size=BS, policy=policy if scaled else None)

            o, after = jax.jit(path)(q, k, v, *pool)
            want = jax.jit(lambda *a: _dense_attention(
                *a[:3], a[3:], policy, positions, lens, tables))(
                    q, k, v, *pool)
            pool = list(after) + ([] if scaled else [None, None])
            for s, n in enumerate(np.asarray(lens)):
                _assert_out(o[s, :, :n], want[s, :, :n], policy)
            _assert_run_written(pool, policy, k, v, positions, lens,
                                tables)
            starts = starts + np.asarray(lens)


# ---------------------------------------------------------------------
# 3. engine goldens: each mechanism serves what the engine without it
#    serves
# ---------------------------------------------------------------------

def _engine(params, family=None, fam_params=None, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("max_seq_len", 32)
    return ServeEngine(family or gpt2_family(CFG),
                       fam_params if fam_params is not None else params,
                       **kw)


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


def _ab(params, prompts, max_new, on, off, *, arrivals=None, **kw):
    """Serve ``prompts`` on the engine under a mechanism (``on``'s
    arguments) and on the same engine without it (``off``'s), same
    policy, same keys: the tokens must be identical. Returns the
    ``on`` engine's metrics summary, to show the mechanism ran."""
    served, summary = [], None
    for extra in (on, off):
        eng = _engine(params, **{**kw, **extra})
        served.append(_serve(eng, prompts, max_new, arrivals=arrivals))
        summary = summary or eng.metrics.summary()
    for x, y in zip(*served):
        np.testing.assert_array_equal(x, y)
    return summary


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
        # the second shared prompt arrives once the first has retired
        # and published its blocks
        summary = _ab(params, prompts, 8, dict(prefix_cache=True),
                      dict(prefix_cache=False), arrivals=[0, 1, 2, 4, 20])
        assert summary["prefix_hit_tokens"] > 0

    def test_sampled_spec_int8(self, params, prompts):
        summary = _ab(params, prompts, 8, dict(spec=SpecConfig(max_draft=4)),
                      {}, arrivals=[0, 0, 2, 3, 5], kv_dtype="int8",
                      temperature=0.8)
        assert summary["spec_steps"] > 0

    def test_chunked_prefill_fake_quant(self, params):
        rng = np.random.default_rng(13)
        long = np.asarray(rng.integers(0, CFG.vocab_size, (20,)),
                          np.int32)
        short = np.asarray(rng.integers(0, CFG.vocab_size, (4,)),
                           np.int32)
        summary = _ab(params, [long, short], 6,
                      dict(prefill_len=8, chunked_prefill=True,
                           prefill_chunk_budget=8), {},
                      kv_dtype="fake_quant", max_seq_len=32)
        assert summary["prefill_chunks"] >= 3

    def test_preemption_pressure_int8(self, params, prompts):
        # pool sized to force growth + preemption mid-trace
        summary = _ab(params, prompts, 8, dict(num_blocks=11),
                      dict(num_blocks=48), arrivals=[0, 0, 0, 1, 1],
                      kv_dtype="int8", max_slots=3)
        assert summary["preempted"] > 0

    def test_llama_gqa_engine_int8(self):
        """4 query heads reading 2 kv heads against the same model with
        each kv head's projections repeated for its 2 query heads: the
        repeat the GQA path makes of the view, made of the weights."""
        from quintnet_tpu.models.llama import LlamaConfig, llama_init
        from quintnet_tpu.serve import llama_family

        cfg = LlamaConfig.tiny()
        rep = cfg.n_heads // cfg.n_kv_heads
        assert rep > 1
        lp = llama_init(jax.random.key(4), cfg)
        mha = LlamaConfig.tiny(n_kv_heads=cfg.n_heads)
        attn = dict(lp["blocks"]["attn"])
        for name in ("k", "v"):
            w = attn[name]["w"]                          # [L, dim, kv*hd]
            w = w.reshape(*w.shape[:2], cfg.n_kv_heads, cfg.head_dim)
            attn[name] = {"w": jnp.repeat(w, rep, axis=2).reshape(
                *w.shape[:2], -1)}
        mp = {**lp, "blocks": {**lp["blocks"], "attn": attn}}
        rng = np.random.default_rng(17)
        prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (n,)),
                              np.int32) for n in (5, 9)]
        _ab(None, prompts, 6,
            dict(family=llama_family(cfg), fam_params=lp),
            dict(family=llama_family(mha), fam_params=mp),
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
            eng = _engine(params, **kw)
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
            eng = _engine(params, **kw)
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

        from quintnet_tpu.models.gpt2 import gpt2_to_tp_layout

        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        _ab(params, prompts[:3], 6,
            dict(mesh=mesh, fam_params=gpt2_to_tp_layout(params, CFG, 2)),
            {}, kv_dtype="fake_quant", max_slots=2)


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
        """THE structural census of the gathered view: every serving
        program of an f32 or scaled pool gathers the full block-table
        row (2 pools, +2 scale arrays when scaled) per layer — decode,
        the smallest prefill bucket (requant span < table width — the
        auditor's caller contract), and a verify bucket."""
        eng = _engine(params, kv_dtype=kv_dtype, num_blocks=24,
                      spec=SpecConfig(max_draft=4))
        kw = dict(num_blocks=24, table_width=eng.table_width)
        b0 = eng.prefill_buckets[0]
        counts = dict(
            decode=gathered_view_gathers(
                eng._decode.fn, *self._args(eng, params, "decode"), **kw),
            prefill=gathered_view_gathers(
                eng._prefills[b0].fn,
                *self._args(eng, params, "prefill", b0), **kw),
            verify=gathered_view_gathers(
                eng._verifies[2].fn,
                *self._args(eng, params, "verify", 2), **kw))
        per_layer = 4 if kv_dtype == "int8" else 2
        assert counts == dict.fromkeys(counts, per_layer), counts

    def test_compile_counts_unchanged_per_backend(self, params):
        """One prefill and one decode program for this traffic, inside
        the engine's compile bound."""
        rng = np.random.default_rng(5)
        prompts = [np.asarray(rng.integers(0, CFG.vocab_size, (n,)),
                              np.int32) for n in (3, 7)]
        eng = _engine(params)
        _serve(eng, prompts, 5)
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
        eng.assert_compile_count()

    def test_unknown_kernel_rejected(self, params):
        """One paged attention: any ``attn_kernel`` but ``"xla"`` is
        refused, by name."""
        for kernel in ("triton", "pallas"):
            with pytest.raises(ValueError,
                               match=f"attn_kernel='{kernel}'"):
                _engine(params, attn_kernel=kernel)


# ---------------------------------------------------------------------
# 5. the arithmetic contract: the view is contracted as it is stored
# ---------------------------------------------------------------------

def _kernels():
    """The walk's module, ops/paged_attention.py."""
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
                eng = _engine(params, family=fam, fam_params=p,
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
                _engine(None, family=fam, fam_params=p, **kw), p)
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
        eng = _engine(params, kv_dtype="bf16")
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
                "pallas_flash_attention", "ring_attention",
                "zigzag_ring_attention", "ulysses_attention"}
    assert expected == set(ops.__all__)
    for name in ops.__all__:
        assert callable(getattr(ops, name)), name
