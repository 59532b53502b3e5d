"""The sliding-window layers' decode and verify walk each row's OWN ring
of the window store in place (nn/attention._window_attend_walk over
ops/paged_attention.paged_walk_attention with a ``window``), against
the form it replaced — every ring sliced out whole, heads on the lane
diagonal (``_lane_diag_sdpa``) — in the kernel's interpreter, at both
window cells' ring geometries: SmallThinker's 4,112 rows x 512 lanes (7
query heads on each of 4 kv heads) and Laguna's 528 x 1,024 (8 on each
of 8).

Every ring row a query must NOT see holds poison: values of 100,000
where a seen row's are unit normal. The rows an earlier owner left, the
spare rows behind a query's window, a page re-read past the ring's end
— any of them read unmasked at a tenth of a fair share of the softmax
moves an output by 100,000 / (10 x 4,096), ten times the largest
rounding bound. Both forms are
held to that bound (q and the probabilities rounded to the store's
bf16, against f64 math on the stored keys and values), and so to each
other up to the order of a sum.
"""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.analysis import row_walk_calls, store_reads
from quintnet_tpu.nn import attention

pa = importlib.import_module("quintnet_tpu.ops.paged_attention")

#   name: (window, page, kv heads, query heads a kv head)
GEOMETRY = {"smallthinker": (4096, 16, 4, 7), "laguna": (512, 16, 8, 8)}
POISON = 1e5
DH = 128


def _lasts(window: int, ring: int):
    """Each row's last position: the first, the window's edges, the
    ring's, and far past, where the ring has wrapped many times; None a
    row that writes nothing (an idle or mid-prefill slot: ``lens`` 0)."""
    return (0, window - 1, window, ring - 1, ring, 9 * ring + 123, None)


def _case(geometry: str, P: int, seed: int = 40):
    """A batch at every last position of :func:`_lasts`, its runs of
    ``P`` tokens, and a store whose rings hold each row's keys from
    ``window`` positions before its run — rows ``1 ..`` of the store,
    ``row0`` 1 — and poison everywhere else."""
    window, bs, hkv, g = GEOMETRY[geometry]
    ring = window + bs
    lasts = _lasts(window, ring)
    S = len(lasts)
    f = hkv * DH
    rng = np.random.default_rng(seed)
    wk = rng.standard_normal((2, (S + 2) * ring, f))
    wv = np.full_like(wk, POISON)
    starts = np.array([0 if t is None else max(t - P + 1, 0) for t in lasts])
    lens = np.array([0 if t is None else P for t in lasts])
    for s, (start, n) in enumerate(zip(starts, lens)):
        if n:          # the keys the run's first query can see
            seen = np.arange(max(start - window + 1, 0), start)
            wv[1, (1 + s) * ring + seen % ring] = rng.standard_normal(
                (len(seen), f))
    positions = starts[:, None] + np.arange(P)[None, :]
    q = rng.standard_normal((S, hkv, g * P, DH))
    k, v = (rng.standard_normal((S, hkv, P, DH)) for _ in range(2))
    as_dt = lambda x, dt: jnp.asarray(x, dt)                  # noqa: E731
    return dict(
        q=as_dt(q, jnp.float32), k=as_dt(k, jnp.float32),
        v=as_dt(v, jnp.float32), wk=as_dt(wk, jnp.bfloat16),
        wv=as_dt(wv, jnp.bfloat16), positions=as_dt(positions, jnp.int32),
        lens=as_dt(lens, jnp.int32), window=window, ring=ring, hkv=hkv)


def _attend(c):
    """``window_attend`` of the case's run (write, then read), at layer
    1 of the store, rings ``1 + arange(S)``."""
    def fn(q, k, v, wk, wv):
        return attention.window_attend(
            q, k, v, (wk, wv), 1, c["positions"], c["lens"], 1,
            window=c["window"], ring=c["ring"],
            max_diag_rows=q.shape[1] * q.shape[2])
    return fn


def _f64_window(c, wk, wv):
    """f64 attention of every live row's queries over the keys and
    values its window holds in the WRITTEN store, read by position (not
    by ring row), and the first-order bound rounding q and the
    probabilities to bf16 may move it by."""
    window, ring, hkv = c["window"], c["ring"], c["hkv"]
    q = np.asarray(c["q"], np.float64)
    S, _, GP, _ = q.shape
    pos = np.asarray(c["positions"])
    P = pos.shape[1]
    wk = np.asarray(wk[1].astype(jnp.float32), np.float64)
    wv = np.asarray(wv[1].astype(jnp.float32), np.float64)
    u = 2.0 ** -8
    want, bound = np.zeros_like(q), np.zeros_like(q)
    for s in range(S):
        if not int(c["lens"][s]):
            continue
        t = np.arange(pos[s, 0] - window + 1, pos[s, -1] + 1)
        t = t[t >= 0]
        rows = (1 + s) * ring + t % ring
        for h in range(hkv):
            lanes = slice(h * DH, (h + 1) * DH)
            kk, vv = wk[rows, lanes], wv[rows, lanes]
            qp = np.tile(pos[s], GP // P)[:, None]                 # [GP, 1]
            live = (t[None, :] <= qp) & (t[None, :] > qp - window)
            sc = np.where(live, q[s, h] @ kk.T / math.sqrt(DH), -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            want[s, h] = pr @ vv
            e = np.where(live, u * (np.abs(q[s, h]) @ np.abs(kk).T)
                         / math.sqrt(DH), 0.0)
            dp = pr * (e + (pr * e).sum(-1, keepdims=True) + u)
            bound[s, h] = 1.1 * dp @ np.abs(vv)
    return want, bound


@pytest.mark.parametrize("P", (1, 2, 17))
@pytest.mark.parametrize("geometry", tuple(GEOMETRY))
def test_ring_walk_equals_gathered_inside_the_rounding_bound(
        geometry, P, monkeypatch):
    """Rows at positions 0, ``window - 1``, ``window``, ``ring - 1``,
    ``ring`` and far past the ring's wraps in ONE batch, beside a row
    that writes nothing, for a decode step and verify runs of 2 and
    ``block_size + 1`` tokens: the walk (one call, no slice of the
    store) and the rings sliced out whole (the CPU's own form: the
    interpreter off) write the same store bytes and both stay inside
    the rounding bound of the f64 window attention; the row that wrote
    nothing gives finite outputs."""
    c = _case(geometry, P)
    fn = _attend(c)
    args = (c["q"], c["k"], c["v"], c["wk"], c["wv"])
    shape = c["wk"].shape
    assert row_walk_calls(fn, *args, pool_shape=shape) == 1
    assert store_reads(fn, *args, store_shape=shape) == []
    got, bufs = jax.jit(fn)(*args)
    # a new function: the switch is read when a program is traced
    monkeypatch.setattr(pa, "INTERPRET", False)
    fn = _attend(c)
    S = c["q"].shape[0]
    assert store_reads(fn, *args, store_shape=shape) == 2 * [
        (1, S * c["ring"], shape[-1])]
    ref, bufs_ref = jax.jit(fn)(*args)
    for a, b in zip(bufs, bufs_ref):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert got.dtype == ref.dtype == jnp.float32
    want, bound = _f64_window(c, *bufs)
    live = np.asarray(c["lens"]) > 0
    for out in (got, ref):
        gap = np.abs(np.asarray(out, np.float64) - want)[live]
        assert (gap <= bound[live]).all(), (gap.max(), bound.max())
    # a poisoned row read unmasked at a tenth of a fair share of the
    # softmax would move an output far past the bound
    assert bound.max() < POISON / (10 * c["window"]) / 10
    assert bool(np.isfinite(np.asarray(got)).all())


def test_a_row_reads_its_ring_once_and_no_more():
    """What the kernel is asked to walk over a ring of 257 pages of 16
    in trips of 528 positions: ``ceil(min(last + 1, ring) / 528)`` key
    blocks a row — what it wrote while younger than its ring, 8 for a
    full ring however far past — and one for a row that wrote nothing;
    the rows' last positions ride as the fifth scalar prefetch."""
    window, bs = 4096, 16
    ring, pages = window + bs, (window + bs) // bs
    kb = attention._ring_key_block(pages, bs)
    assert kb == 528
    last = jnp.asarray([-1, 0, 527, 528, 4095, 4111, 4112, 50_000])
    rows = len(last)
    qpos = jnp.pad(jnp.stack([last - 1, last], axis=1), ((0, 0), (0, 14)),
                   constant_values=-1)
    qpos = qpos.at[0].set(-1)
    store = jnp.zeros((1, 2 * ring, 128), jnp.bfloat16)
    closed = jax.make_jaxpr(
        lambda qp: pa.paged_walk_attention(
            jnp.zeros((rows, 16, 128), jnp.bfloat16), qp, store, store,
            jnp.int32(0), jnp.zeros((rows, pages), jnp.int32),
            block_size=bs, key_block=kb, head_dim=64, window=window))(qpos)
    (call,) = [e for e in closed.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    trips, last_in = jax.core.eval_jaxpr(
        closed.jaxpr.replace(outvars=[call.invars[2], call.invars[4]]),
        closed.consts, qpos)
    np.testing.assert_array_equal(np.asarray(trips),
                                  [1, 1, 1, 2, 8, 8, 8, 8])
    np.testing.assert_array_equal(np.asarray(last_in),
                                  np.maximum(np.asarray(last), 0))


@pytest.mark.parametrize("pages,block,want", [
    (257, 16, 528),     # smallthinker: 4,112 rows (a prime count of
                        # pages) in eight trips, the last re-reading 7
    (33, 16, 528),      # laguna: 528 rows in one trip
    (5, 8, 40),         # the tiny presets' 40-row rings: one trip
])
def test_the_key_block_spreads_a_full_rings_trips_evenly(pages, block, want):
    """A whole ring takes as many trips as it holds ``RING_KEY_BLOCK``-s,
    to the nearest and at least one, its pages spread evenly over
    them."""
    assert attention._ring_key_block(pages, block) == want


def test_a_block_that_does_not_fit_takes_more_trips():
    """Where a key block of the ring's trips does not fit (a verify run
    of ``block_size + 1`` tokens is 1,088 query rows of Laguna's: a
    528-row block asks 107 MiB of VMEM), the fewest trips whose block
    fits — two of 272 — and a page a trip at the least."""
    assert attention._ring_key_block(33, 16, lambda kb: kb <= 300) == 272
    assert attention._ring_key_block(33, 16, lambda kb: False) == 16
