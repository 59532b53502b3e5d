"""``KVPool(latent=..., state=...)``: ONE latent row a position for the
layers that cache one, AND a fixed-size state a slot for the layers that
keep one, in one manager (serve/kv_pool.py, "Both at once"). The
accounting a sequence's life goes through — admit, grow, free, preempt —
gives back both kinds; through ``ServeEngine`` with the tiny
``bailing_hybrid`` family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.ling_hybrid import (LingHybridConfig,
                                             ling_hybrid_init)
from quintnet_tpu.serve import ServeEngine, ling_hybrid_family
from quintnet_tpu.serve.kv_pool import KVPool, StateShapes

STATE = StateShapes(n_layers=3, ssm=(2, 4, 4), conv=(3 * 24,))


def _pool(**kw):
    opts = dict(n_layers=2, n_kv_heads=1, head_dim=24, block_size=4,
                num_blocks=16, latent=24, state=STATE, max_slots=5,
                prefix_cache=False)
    opts.update(kw)
    return KVPool(**opts)


@pytest.mark.parametrize("dtype,conv_bytes", [(jnp.float32, 4),
                                              (jnp.bfloat16, 2)])
def test_the_pool_carries_one_latent_buffer_and_two_state_buffers(
        dtype, conv_bytes):
    pool = _pool(dtype=dtype)
    k, ssm, conv = pool.caches()
    assert pool.v is None and k is pool.k
    assert k.shape[:2] == (2, 16 * 4) and k.dtype == dtype
    # row = slot, the last row the null one; the state f32 whatever the
    # pool stores, the conv tail flat in the pool's dtype
    assert ssm.shape == (3, 6, 2, 4, 4) and ssm.dtype == jnp.float32
    assert conv.shape == (3, 6, 72) and conv.dtype == dtype
    assert pool.state_bytes_per_slot == 3 * (2 * 4 * 4 * 4
                                             + 72 * conv_bytes)
    # one row kind: half of what a k and a v row would be
    assert pool.bytes_per_block == 2 * 4 * 24 * jnp.dtype(dtype).itemsize


def test_update_takes_all_three_buffers_in_caches_order():
    pool = _pool()
    k, ssm, conv = pool.caches()
    pool.update(k + 1, ssm + 2, conv + 3)
    assert float(pool.k[0, 0, 0]) == 1 and float(pool.ssm[0, 0, 0, 0, 0]) == 2
    assert float(pool.conv[0, 0, 0]) == 3
    with pytest.raises(ValueError, match="needs all 3"):
        pool.update(k)
    with pytest.raises(ValueError, match="latent buffer and the recurrent"):
        pool.update(k, ssm)


@pytest.mark.parametrize("kw,error,match", [
    (dict(policy="int8"), NotImplementedError, "scaled policy"),
    (dict(policy="fp8"), NotImplementedError, "conv tail"),
    (dict(n_kv_heads=2, head_dim=12), ValueError, "ONE row"),
])
def test_what_the_two_kinds_do_not_compose_with_is_refused(kw, error, match):
    with pytest.raises(error, match=match):
        _pool(**kw)


def test_blocks_are_the_only_thing_allocated():
    """The state has no blocks: acquiring and releasing move the free
    list alone, and the state buffers keep their shape."""
    pool = _pool()
    free0 = pool.num_free
    got = pool.acquire(pool.blocks_for(9))
    assert len(got) == 3 and pool.num_free == free0 - 3
    pool.release(got)
    assert pool.num_free == free0
    assert pool.caches()[1].shape == (3, 6, 2, 4, 4)


# ---------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------
CFG = LingHybridConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return ling_hybrid_init(jax.random.key(3), CFG)


def _engine(params, **kw):
    opts = dict(max_slots=3, block_size=4, num_blocks=40, max_seq_len=64,
                prefix_cache=False)
    opts.update(kw)
    return ServeEngine(ling_hybrid_family(CFG), params, **opts)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
            for n in lens]


@pytest.mark.parametrize("num_blocks,preempts", [(40, False), (16, True)])
def test_admit_free_and_preempt_give_back_both_kinds(params, num_blocks,
                                                     preempts):
    """Five requests through three slots. While a request runs it holds
    blocks for its latent rows and its slot's row of the state; when it
    finishes, or is preempted, the blocks go back to the free list and
    the slot to whoever is admitted next — who starts from a ZERO state
    (tests/test_ling_hybrid.py holds the tokens to the reference). At
    the end nothing is held."""
    eng = _engine(params, num_blocks=num_blocks)
    assert eng.pool.caches()[1].shape[1] == 3 + 1
    for p in _prompts(5, (9, 14, 7, 11, 10)):
        eng.submit(p, 12)
    used = []
    while eng.has_work:
        eng.step()
        used.append(eng.metrics.kv_blocks_used)
    assert max(used) > 0
    assert (eng.metrics.preempted > 0) == preempts
    assert eng.pool.num_free == eng.pool.usable_blocks
    assert eng.metrics.summary()["finished"] == 5
    # the ring's accounting: the state's bytes a step, by slot
    per_slot = eng.pool.state_bytes_per_slot
    assert per_slot == eng.recorder.static["state_bytes_per_slot"]
    for r in eng.recorder.snapshot():
        assert r["state_bytes"] % (2 * per_slot) == 0
        assert r["state_bytes"] >= 2 * per_slot * r["decoding"]


def test_a_request_too_long_for_the_pool_is_refused_at_submit(params):
    eng = _engine(params, num_blocks=8)           # 7 blocks of 4
    with pytest.raises(ValueError, match="KV pool too small"):
        eng.submit(_prompts(7, (20,))[0], 20)
