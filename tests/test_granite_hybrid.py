"""Granite 4.0-H (Mamba-2 + GQA hybrid) at tiny widths on the CPU: two
periods of ``[m, m, a, m]``, hidden 64, state 16, chunk 8, seeded
weights with the published-range ``A_log`` / ``dt_bias`` / conv init.

Three things must agree: the chunked scan, the one-token recurrence,
and the plain reference (benchmarks/lib/reference_granite_hybrid.py: a
``lax.scan`` over time at ``precision="highest"``, nothing imported
from ``quintnet_tpu``). Then the engine: the family's contracts and
``ServeEngine`` itself against the reference's full forward.

Tolerances. Everything here is f32 on the CPU, where a matmul is exact
f32: what differs between the forms is the ORDER of the sums (a chunk's
quadratic form and its cumulative decays against one position after
another), a few ulp of values of size about 1. ``ATOL`` 2e-4 on mixer
outputs and logits is 100 times that, and 15 times under what a stale
state does to these logits (0.003 on a std of 0.02: measured with the
zeroing taken out, see the stale-state test; greedy TOKENS do not move
there, so that test compares logits).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.granite_hybrid import (ATTENTION, MAMBA,
                                                GraniteHybridConfig,
                                                granite_hybrid_init)
from quintnet_tpu.nn.ssm import mamba2_chunk, mamba2_init, mamba2_step
from quintnet_tpu.serve import ServeEngine, granite_hybrid_family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-4


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_granite_hybrid", os.path.join(
            ROOT, "benchmarks", "lib", "reference_granite_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()
CFG = GraniteHybridConfig.tiny()
CFG_DICT = {f: getattr(CFG, f) for f in CFG.__dataclass_fields__}


@pytest.fixture(scope="module")
def params():
    return granite_hybrid_init(jax.random.key(7), CFG)


def _engine(params, **kw):
    opts = dict(max_slots=3, block_size=4, num_blocks=96, max_seq_len=96,
                prefix_cache=False)
    opts.update(kw)
    return ServeEngine(granite_hybrid_family(CFG), params, **opts)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------
# the mixer: chunked scan = recurrence = reference
# ---------------------------------------------------------------------
def _mixer_case(seed, t):
    dims = CFG.mamba
    p = mamba2_init(jax.random.key(seed), CFG.hidden_size, dims)
    u = jax.random.normal(jax.random.key(seed + 1),
                          (2, t, CFG.hidden_size), jnp.float32)
    zero = (jnp.zeros((2, dims.n_heads, dims.d_head, dims.d_state)),
            jnp.zeros((2, dims.d_conv - 1, dims.d_xbc)))
    return dims, p, u, zero


def _by_steps(p, u, state, tail, dims):
    outs = []
    for i in range(u.shape[1]):
        y, state, tail = mamba2_step(p, u[:, i], state, tail, dims)
        outs.append(y)
    return jnp.stack(outs, axis=1), state, tail


@pytest.mark.parametrize("t,pad", [(5, 3), (8, 0), (13, 3), (19, 5),
                                   (24, 8)])
def test_chunked_scan_equals_recurrence_equals_reference(t, pad):
    """Lengths that are not multiples of the chunk (8), right-padded to
    one: the pad must move neither the outputs nor the state nor the
    conv tail. The pad's content is made large so that it would."""
    dims, p, u, (s0, c0) = _mixer_case(t, t)
    want, s_want = reference._mamba2(p, u, CFG_DICT, jnp.array([t, t]))
    stepped, s_step, c_step = _by_steps(p, u, s0, c0, dims)
    np.testing.assert_allclose(stepped, want, atol=ATOL)
    np.testing.assert_allclose(s_step, s_want, atol=ATOL)
    padded = jnp.concatenate(
        [u, 50.0 * jnp.ones((2, pad, CFG.hidden_size))], axis=1)
    lens = jnp.array([t, t], jnp.int32)
    got, s_chunk, c_chunk = mamba2_chunk(p, padded, s0, c0, lens, dims)
    np.testing.assert_allclose(got[:, :t], want, atol=ATOL)
    np.testing.assert_allclose(s_chunk, s_step, atol=ATOL)
    np.testing.assert_allclose(c_chunk, c_step, atol=1e-6)


@pytest.mark.parametrize("first", [3, 8, 11])
def test_chunked_scan_continues_from_an_initial_state(first):
    """The second part of a sequence from the state and tail the first
    part left equals the reference over the whole sequence; rows of one
    call may have different lengths, and one of length 0 is left as it
    was."""
    t = 21
    dims, p, u, (s0, c0) = _mixer_case(40 + first, t)
    # the reference's state of a row stands still past the row's length
    want, s_want = reference._mamba2(p, u, CFG_DICT, jnp.array([t, first]))
    _, s1, c1 = mamba2_chunk(p, u[:, :16], s0, c0,
                             jnp.array([first, first]), dims)
    rest = jnp.pad(u[:, first:], ((0, 0), (0, 3 + first), (0, 0)))
    lens = jnp.array([t - first, 0], jnp.int32)
    got, s2, c2 = mamba2_chunk(p, rest, s1, c1, lens, dims)
    np.testing.assert_allclose(got[0, :t - first], want[0, first:],
                               atol=ATOL)
    np.testing.assert_array_equal(s2[1], s1[1])       # length 0: untouched
    np.testing.assert_allclose(s2, s_want, atol=ATOL)
    np.testing.assert_array_equal(c2[1], c1[1])
    _, s_all, c_all = _by_steps(p, u, s0, c0, dims)
    np.testing.assert_allclose(s2[0], s_all[0], atol=ATOL)
    np.testing.assert_allclose(c2[0], c_all[0], atol=1e-6)


def test_published_init_keeps_state_over_hundreds_of_positions(params):
    mixer = params["blocks"]["mamba"]["mixer"]
    dt = jax.nn.softplus(mixer["dt_bias"])
    decay = jnp.exp(-dt * jnp.exp(mixer["A_log"]))
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 0.1 * 1.01
    assert 0.19 < float(decay.min()) and float(decay.max()) < 1.0
    assert float(decay.max()) > 0.97         # some heads remember long


# ---------------------------------------------------------------------
# the family's contracts against the reference's full forward
# ---------------------------------------------------------------------
def _contracts(eng):
    fam, pool = eng.family, eng.pool
    bs = pool.block_size

    def prefill(params, k, v, ssm, conv, ids, start, t0, row, slot):
        return fam.prefill_from(params, k, v, ids, start, t0, row, bs,
                                policy=pool.policy, state=(ssm, conv),
                                slot=slot)

    def decode(params, k, v, ssm, conv, tok, pos, tables):
        return fam.decode(params, k, v, tok, pos, tables, bs,
                          policy=pool.policy, state=(ssm, conv))

    def verify(params, k, v, ssm, conv, ids, starts, lens, tables):
        return fam.verify(params, k, v, ids, starts, lens, tables, bs,
                          policy=pool.policy, state=(ssm, conv))

    return tuple(jax.jit(f, donate_argnums=(1, 2, 3, 4))
                 for f in (prefill, decode, verify))


def _tables(eng, rows, width):
    tables = np.zeros((eng.max_slots, eng.table_width), np.int32)
    for s in rows:
        got = eng.pool.acquire(eng.pool.blocks_for(width))
        tables[s, :len(got)] = got
    return tables


def test_prefill_then_paged_decode_equals_the_full_forward(params):
    """Two live rows of three: prefill in two calls (a carried state,
    a chunk boundary inside the first call), then one token a step
    through the decode program, teacher-forced; every logit row against
    the reference over the same ids. The dead row's state stays 0."""
    eng = _engine(params)
    prefill, decode, _ = _contracts(eng)
    lens, split, width = {0: 37, 2: 29}, 20, 48
    rows = np.random.default_rng(3).integers(
        0, CFG.vocab_size, (3, width)).astype(np.int32)
    want = np.asarray(reference.forward(params, rows, CFG_DICT))
    tables = _tables(eng, lens, width)
    for s, n in lens.items():
        for lo, hi in ((0, split), (split, n)):
            ids = np.zeros((1, 32), np.int32)
            ids[0, :hi - lo] = rows[s, lo:hi]
            logits, *bufs = prefill(
                eng.params, *eng.pool.caches(), jnp.asarray(ids),
                jnp.int32(lo), jnp.int32(hi), jnp.asarray(tables[s]),
                jnp.int32(s))
            eng.pool.update(*bufs)
            np.testing.assert_allclose(logits[0], want[s, hi - 1],
                                       atol=ATOL)
    pos = np.array([lens[0], 0, lens[2]], np.int32)
    for _ in range(width - max(lens.values())):
        tok = rows[np.arange(3), pos] * (pos > 0)
        logits, *bufs = decode(eng.params, *eng.pool.caches(),
                               jnp.asarray(tok), jnp.asarray(pos),
                               jnp.asarray(tables))
        eng.pool.update(*bufs)
        for s in lens:
            np.testing.assert_allclose(logits[s], want[s, pos[s]],
                                       atol=ATOL)
        pos = pos + (pos > 0)
    assert not np.asarray(eng.pool.ssm[:, 1]).any()
    # what the pool holds afterwards is the reference's state after
    # each row's last position (what the benchmark's second limit reads)
    _, states = reference.forward(params, rows, CFG_DICT,
                                  lengths=[int(pos[0]), 0, int(pos[2])])
    assert np.asarray(states[:, 0]).any()
    for s in lens:
        np.testing.assert_allclose(eng.pool.ssm[:, s], states[:, s],
                                   atol=ATOL)


def test_verify_in_three_chunks_equals_one_shot_equals_reference(params):
    width = 40
    rows = np.random.default_rng(5).integers(
        0, CFG.vocab_size, (3, width)).astype(np.int32)
    want = np.asarray(reference.forward(params, rows, CFG_DICT))
    lens = np.array([width, 33, 0], np.int32)
    outs = []
    for cuts in ((0, 40), (0, 16, 24, 40)):
        eng = _engine(params)
        verify = _contracts(eng)[2]
        tables = _tables(eng, (0, 1), width)
        got = []
        for lo, hi in zip(cuts, cuts[1:]):
            ids = np.zeros((3, 40), np.int32)
            ids[:, :hi - lo] = rows[:, lo:hi]
            logits, *bufs = verify(
                eng.params, *eng.pool.caches(), jnp.asarray(ids),
                jnp.full((3,), lo, jnp.int32),
                jnp.asarray(np.clip(lens - lo, 0, hi - lo)),
                jnp.asarray(tables))
            eng.pool.update(*bufs)
            got.append(np.asarray(logits[:, :hi - lo]))
        outs.append(np.concatenate(got, axis=1))
    for s, n in enumerate(lens):
        np.testing.assert_allclose(outs[0][s, :n], want[s, :n], atol=ATOL)
        np.testing.assert_allclose(outs[1][s, :n], outs[0][s, :n],
                                   atol=ATOL)


# ---------------------------------------------------------------------
# the engine itself
# ---------------------------------------------------------------------
def _assert_greedy_by_reference(params, prompt, out):
    """``out`` (prompt + generated) is the reference's greedy decode of
    ``prompt``: one full forward over ``out``, whose argmax at every
    position from the prompt's last on is the next token of ``out``."""
    np.testing.assert_array_equal(out[:len(prompt)], prompt)
    at = list(range(len(prompt) - 1, len(out) - 1))
    logits = np.asarray(reference.forward(
        params, np.asarray([out], np.int32), CFG_DICT, positions=at))
    np.testing.assert_array_equal(np.argmax(logits[0], axis=-1),
                                  out[len(prompt):])


def test_engine_tokens_equal_the_references_greedy_decode(params):
    """submit + step through the scheduler, the prefill ladder and the
    one decode program, more requests than slots: every request's
    tokens are the reference's greedy continuation, and nothing
    compiles twice."""
    eng = _engine(params)
    eng.warmup()
    prompts = _prompts(11, (5, 17, 9, 30, 12))
    rids = [eng.submit(p, 6) for p in prompts]
    eng.run()
    for rid, prompt in zip(rids, prompts):
        _assert_greedy_by_reference(params, prompt, eng.result(rid))
    eng.assert_compile_count(prefill=len(eng.prefill_buckets), decode=1)
    last = eng.recorder.snapshot()
    assert eng.recorder.static["state_bytes_per_slot"] == \
        eng.pool.state_bytes_per_slot > 0
    assert eng.recorder.static["layer_pattern"] == list(CFG.layer_types)
    assert any(r["state_bytes"] == 2 * r["decoding"]
               * eng.pool.state_bytes_per_slot and r["decoding"]
               for r in last)


def test_chunked_prefill_across_three_chunks_equals_one_shot(params):
    prompts = _prompts(13, (40, 7, 33))
    plain = _engine(params)
    chunked = _engine(params, chunked_prefill=True, prefill_len=16,
                      prefill_chunk_budget=16)
    for eng in (plain, chunked):
        rids = [eng.submit(p, 5) for p in prompts]
        eng.run()
        eng.outs = [eng.result(r) for r in rids]
    assert chunked.metrics.summary()["prefill_chunks"] >= 3 + 1 + 3
    for a, b in zip(plain.outs, chunked.outs):
        np.testing.assert_array_equal(a, b)


def test_a_preempted_request_continues_by_re_prefilling_from_zero(params):
    """The rule (docs/serving.md, "Recurrent families"): a preempted
    slot's state is dropped, and the resume prefills prompt + generated
    from position 0 through the chunk program — correct to the
    arithmetic. On the CPU's exact f32 that is token-equal to the run
    nobody interrupted."""
    prompts = _prompts(17, (20, 22, 18))
    roomy = _engine(params)
    tight = _engine(params, num_blocks=20)        # 19 blocks of 4
    for eng in (roomy, tight):
        rids = [eng.submit(p, 16) for p in prompts]
        eng.run()
        eng.outs = [eng.result(r) for r in rids]
    assert roomy.metrics.preempted == 0 and tight.metrics.preempted > 0
    for a, b in zip(roomy.outs, tight.outs):
        np.testing.assert_array_equal(a, b)


def test_a_reused_slot_starts_from_a_zeroed_state(params):
    """One slot, two requests one after the other: the second finds the
    first's state in its row and must not see it — through the engine
    (tokens), and through the prefill contract on the stale row
    (logits: take the ``start == 0`` zeroing out of
    families.run_chunk and they are off by 0.003, 15 times ATOL)."""
    first, second = _prompts(19, (23, 14))
    eng = _engine(params, max_slots=1)
    eng.submit(first, 8)
    eng.run()
    assert np.asarray(eng.pool.ssm[:, 0]).any()       # slot 0 is stale
    rid = eng.submit(second, 8)
    eng.run()
    _assert_greedy_by_reference(params, second, eng.result(rid))
    prefill = _contracts(eng)[0]
    ids = np.zeros((1, 16), np.int32)
    ids[0, :14] = second
    logits, *_bufs = prefill(
        eng.params, *eng.pool.caches(), jnp.asarray(ids), jnp.int32(0),
        jnp.int32(14), jnp.asarray(_tables(eng, (0,), 16)[0]),
        jnp.int32(0))
    want = reference.forward(params, second[None], CFG_DICT,
                             positions=[13])
    np.testing.assert_allclose(logits[0], np.asarray(want)[0, 0],
                               atol=ATOL)


@pytest.mark.parametrize("kwargs,match", [
    (dict(prefix_cache=True), "snapshots"),
    (dict(prefix_cache=True, kv_tier_bytes=1 << 20), "prefix_cache"),
    (dict(kv_tier_bytes=1 << 20), "host tier"),
    (dict(spec=True), "rolls the recurrent state back"),
    (dict(adapters=True), "LoRA"),
    (dict(mesh="tp"), "head-sharded"),
    (dict(mesh="sp"), "ring form"),
    (dict(attn_kernel="pallas"), "attn_kernel='pallas'"),
    (dict(kv_dtype="int8"), "conv tail"),
    (dict(kv_dtype="fp8"), "conv tail"),
])
def test_what_assumes_kv_only_sequences_is_refused_at_construction(
        params, kwargs, match):
    if "mesh" in kwargs:
        from jax.sharding import Mesh

        axis = kwargs.pop("mesh")
        kwargs["mesh"] = Mesh(np.array(jax.devices()[:2]), (axis,))
        kwargs["sp_axis" if axis == "sp" else "tp_axis"] = axis
    # the engine refuses every attn_kernel but its own, by value
    refused = (ValueError if "attn_kernel" in kwargs
               else NotImplementedError)
    with pytest.raises(refused, match=match):
        _engine(params, **kwargs)


@pytest.mark.parametrize("call,match", [
    (lambda e, p: e.export_kv_chain(p), "handoff payload"),
    (lambda e, p: e.import_kv_chain({}), "handoff payload"),
    (lambda e, p: e.submit(p, 4, prefill_only=True), "prefill-phase"),
])
def test_kv_only_methods_are_refused_when_called(params, call, match):
    eng = _engine(params)
    with pytest.raises(NotImplementedError, match=match):
        call(eng, _prompts(23, (9,))[0])


def test_the_score_scale_is_an_argument_and_defaults_to_sqrt_dh():
    from quintnet_tpu.nn.attention import _masked_sdpa

    q, k, v = (jax.random.normal(jax.random.key(i), (2, 3, 5, 16))
               for i in range(3))
    valid = jnp.tril(jnp.ones((5, 5), bool))[None, None]
    plain = _masked_sdpa(q, k, v, valid)
    np.testing.assert_array_equal(
        plain, _masked_sdpa(q, k, v, valid, scale=None))
    np.testing.assert_allclose(
        plain, _masked_sdpa(q, k, v, valid, scale=0.25), atol=1e-6)
    np.testing.assert_allclose(
        _masked_sdpa(q, k, v, valid, scale=1 / 16),
        _masked_sdpa(q / 4, k, v, valid), atol=1e-6)


def test_config_reads_the_hugging_face_keys_and_finds_the_pattern():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        d = json.load(f)
    cfg = GraniteHybridConfig.from_dict(d)
    assert cfg.pattern == (4, 5, 4) and cfg.n_mamba_layers == 36
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == ATTENTION] == [5, 15, 25, 35]
    assert cfg.head_dim == 64 and cfg.attention_multiplier == 1 / 64
    assert cfg.mamba.d_xbc == 4352 and cfg.mamba.d_inner == 4096
    with pytest.raises(NotImplementedError, match="period"):
        GraniteHybridConfig.tiny(
            layer_types=(MAMBA, ATTENTION, ATTENTION, MAMBA) * 2)
    with pytest.raises(NotImplementedError, match="mamba_n_groups"):
        GraniteHybridConfig.tiny(mamba_n_groups=2)
