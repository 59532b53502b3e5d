"""The sliding-window layers' store in ``KVPool`` alone (no model): a
ring of ``window + block_size`` positions a slot beside the blocks of
the global layers (serve/kv_pool.py, "Window families";
nn/attention.py, "The WINDOW store"). Bytes a slot are bounded by the
window whatever the sequence's length; admission, free and preemption
keep the blocks and the rings consistent; ``AdmitPlan`` counts both.
The existing ``test_kv_pool*`` files cover the blocks and stay as they
are."""

import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.nn.attention import (_ring_positions, window_gather,
                                       window_write)
from quintnet_tpu.serve.kv_pool import KVPool, StateShapes, WindowShapes

W = WindowShapes(n_layers=3, window=8, ring=12)
H, D, BS = 2, 16, 4


def _pool(max_slots=3, num_blocks=32, **kw):
    opts = dict(n_layers=2, n_kv_heads=H, head_dim=D, block_size=BS,
                num_blocks=num_blocks, prefix_cache=False, window=W,
                max_slots=max_slots)
    opts.update(kw)
    return KVPool(**opts)


def test_bytes_a_slot_are_bounded_by_the_window_whatever_the_length():
    pool = _pool()
    # 3 layers x 12 positions x (k + v) x 2 heads x 16 features x 4 bytes
    assert pool.window_bytes_per_slot == 3 * 12 * 2 * 2 * 16 * 4
    # the rings: one a slot and the null one, F = 128 lanes (32 padded)
    assert pool.wk.shape == pool.wv.shape == (3, 4 * 12, 128)
    assert pool.caches() == (pool.k, pool.v, pool.wk, pool.wv)
    # the global layers' bytes grow with the length, the rings' do not
    per_block = pool.bytes_per_block
    assert per_block == 2 * BS * 2 * H * D * 4
    assert pool.bytes_per_token == per_block / BS
    for n in (1, 8, 9, 100, 10_000):
        plan = pool.plan_admission(np.arange(min(n, 50)), n)
        assert plan.n_new_blocks == pool.blocks_for(n)
        assert plan.window_bytes == pool.window_bytes_per_slot
    # a pool without a window store has neither
    plain = KVPool(n_layers=2, n_kv_heads=H, head_dim=D, block_size=BS,
                   num_blocks=8)
    assert plain.window_bytes_per_slot == 0 and plain.wk is None
    assert plain.plan_admission(np.arange(5), 6).window_bytes == 0


def test_a_long_sequence_holds_the_last_ring_positions_only():
    """200 positions written through a ring of 12, in runs of every
    width (1, 5, wider than the ring): the ring's rows hold exactly the
    last 12, each where ``p % ring`` says, and ``_ring_positions``
    names them; the other slots' rings are untouched."""
    pool = _pool()
    wk, wv = pool.wk, pool.wv
    before = np.asarray(wk)
    pos, rng = 0, np.random.default_rng(0)
    while pos < 200:
        n = int(rng.choice([1, 5, 30]))
        width = 32
        positions = (pos + jnp.arange(width))[None]
        vals = jnp.broadcast_to(
            (positions[0].astype(jnp.float32) + 1)[None, None, :, None],
            (1, H, width, D))
        wk, wv = window_write(wk, wv, 1, vals, -vals, positions,
                              jnp.asarray([n]), 1, ring=W.ring)
        pos += n
    held = np.asarray(_ring_positions(jnp.asarray([pos - 1]), W.ring))[0]
    assert sorted(held) == list(range(pos - 12, pos))
    ring = np.asarray(window_gather(wk, 1, 1, 1, ring=W.ring))[0]
    np.testing.assert_array_equal(ring[:, 0], held + 1)
    np.testing.assert_array_equal(ring[:, H * D:], 0)      # pad lanes
    np.testing.assert_array_equal(
        np.asarray(window_gather(wv, 1, 1, 1, ring=W.ring))[0, :, 0],
        -(held + 1))
    after = np.asarray(wk)
    # layer 1, ring 1 and the null ring (pads) changed; nothing else
    changed = np.argwhere((after != before).any(axis=-1))
    assert set(changed[:, 0]) == {1}
    assert set(changed[:, 1] // W.ring) <= {1, 3}
    # a sequence shorter than the ring: the rows it has not reached
    # read NEGATIVE positions (an earlier owner's rows, always masked)
    short = np.asarray(_ring_positions(jnp.asarray([4]), W.ring))[0]
    assert list(short[:5]) == [0, 1, 2, 3, 4] and (short[5:] < 0).all()


def test_admission_counts_both_kinds():
    pool = _pool(max_slots=2, num_blocks=9)            # 8 usable blocks
    tokens = np.arange(10, dtype=np.int32)
    plan = pool.plan_admission(tokens, 11)
    assert plan.n_new_blocks == 3
    assert plan.window_bytes == pool.window_bytes_per_slot > 0
    assert pool.can_admit(plan) and pool.window_slots_free == 2
    # blocks enough, rings none: not admissible
    pool.window_acquire(0, owner=7)
    pool.window_acquire(1, owner=8)
    assert pool.window_owner == [7, 8] and pool.window_slots_free == 0
    assert not pool.can_admit(plan)
    with pytest.raises(ValueError, match="owned by 7"):
        pool.window_acquire(0, owner=9)
    # a ring free, blocks none: not admissible either
    pool.window_release(1)
    held = pool.acquire(6)
    assert pool.window_slots_free == 1 and not pool.can_admit(plan)
    pool.release(held)
    assert pool.can_admit(plan)


def test_admit_free_and_preempt_leave_both_kinds_consistent():
    """The engine's own sequence of calls (``_allocate_slot``,
    ``_grow_or_preempt``, ``_clear_slot``) on the pool alone: a ring is
    owned exactly while its slot holds blocks."""
    pool = _pool(max_slots=2, num_blocks=9)
    slots = {}

    def admit(slot, rid, n_tokens):
        plan = pool.plan_admission(np.arange(n_tokens), n_tokens + 1)
        assert pool.can_admit(plan)
        pool.window_acquire(slot, rid)
        slots[slot] = pool.acquire(plan.n_new_blocks)

    def clear(slot):
        pool.release(slots.pop(slot))
        pool.window_release(slot)

    def consistent():
        assert [o is not None for o in pool.window_owner] == [
            s in slots for s in range(2)]
        assert pool.num_used == sum(len(b) for b in slots.values())

    admit(0, 100, 10)
    admit(1, 101, 14)
    consistent()
    assert pool.num_used == 3 + 4 and pool.window_slots_free == 0
    # growth takes blocks, never a ring
    slots[0] += pool.acquire(1)
    consistent()
    assert pool.acquire(1) is None                      # the pool is dry
    clear(1)                                            # preempt the youngest
    consistent()
    assert pool.window_owner == [100, None]
    admit(1, 101, 14)                                   # re-prefilled later
    clear(0)
    clear(1)
    consistent()
    assert pool.num_used == 0 and pool.window_slots_free == 2
    # releasing a ring nobody owns, or any ring of a plain pool, is a no-op
    pool.window_release(0)
    KVPool(n_layers=1, n_kv_heads=1, head_dim=8, block_size=4,
           num_blocks=4).window_release(0)


def test_update_takes_all_four_buffers():
    pool = _pool()
    k, v, wk, wv = pool.caches()
    pool.update(k, v, wk + 1, wv)
    assert float(pool.wk[0, 0, 0]) == 1.0
    with pytest.raises(ValueError, match="window store's buffers"):
        pool.update(k, v)


@pytest.mark.parametrize("kw,err", [
    (dict(policy="int8"), NotImplementedError),
    (dict(policy="fp8"), NotImplementedError),
    (dict(prefix_cache=True), NotImplementedError),
    (dict(state=StateShapes(n_layers=1, ssm=(2, 2, 2), conv=(4,))),
     NotImplementedError),
    (dict(max_slots=0), ValueError),
    (dict(window=WindowShapes(n_layers=1, window=8, ring=6)), ValueError),
])
def test_what_a_window_store_does_not_sit_beside_is_refused(kw, err):
    with pytest.raises(err):
        _pool(**kw)
