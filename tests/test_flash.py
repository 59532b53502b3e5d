"""Fused-attention tests: the exact blockwise jnp path and the Pallas
kernel in both geometries (interpreter mode on CPU) against plain SDPA,
and the chooser that sends a local attention call to one of them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.nn import attention
from quintnet_tpu.nn.attention import (local_attention,
                                       local_attention_path, sdpa)
from quintnet_tpu.ops.flash_attention import blockwise_attention
from quintnet_tpu.ops.pallas_attention import (pallas_flash_attention,
                                               resident_flash_attention)


def _qkv(b=2, h=2, s=64, d=32, keyseed=0):
    ks = jax.random.split(jax.random.key(keyseed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d)) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_sdpa(causal):
    q, k, v = _qkv()
    ref = sdpa(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_blockwise_ragged_seq():
    q, k, v = _qkv(s=50)  # not a block multiple -> padding path
    ref = sdpa(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_interpret_matches_sdpa(causal):
    q, k, v = _qkv(s=128, d=64)
    ref = sdpa(q, k, v, causal=causal)
    out = pallas_flash_attention(q, k, v, causal, 64, 64, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_grads(causal):
    """Hand-tiled Pallas dQ/dK/dV kernels (interpret mode) == autodiff
    through plain SDPA, incl. the causally-pruned grid."""
    q, k, v = _qkv(s=64, d=32)
    w = jax.random.normal(jax.random.key(9), q.shape)

    def ref_loss(q_, k_, v_):
        return jnp.sum(sdpa(q_, k_, v_, causal=causal) * w)

    def fa_loss(q_, k_, v_):
        return jnp.sum(
            pallas_flash_attention(q_, k_, v_, causal, 32, 32, True) * w)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(fa_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_pallas_kernel_grads_rectangular_blocks():
    """block_q != block_k exercises the _block_live pruning geometry off
    the square-block fast path."""
    q, k, v = _qkv(s=128, d=32)
    w = jax.random.normal(jax.random.key(9), q.shape)

    def ref_loss(q_, k_, v_):
        return jnp.sum(sdpa(q_, k_, v_, causal=True) * w)

    def fa_loss(q_, k_, v_):
        return jnp.sum(
            pallas_flash_attention(q_, k_, v_, True, 32, 64, True) * w)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(fa_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


# ---------------------------------------------------------------------
# the resident geometry (whole heads in VMEM, one backward pass)
# ---------------------------------------------------------------------
def _sorted_segments(b, s, n, seed=0):
    """Monotone packed-document ids [b, s], ``n`` documents a row."""
    ids = np.random.default_rng(seed).integers(0, n, (b, s))
    return jnp.asarray(np.sort(ids, axis=1), jnp.int32)


def _fwd_and_grads(attn, q, k, v):
    w = jax.random.normal(jax.random.key(9), q.shape)
    loss = lambda q_, k_, v_: jnp.sum(attn(q_, k_, v_) * w)  # noqa: E731
    return (attn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))


@pytest.mark.parametrize("segments", [False, True],
                         ids=["causal", "causal+segments"])
def test_resident_kernel_at_the_cells_shape(segments):
    """S = 1,024, Dh = 64, causal, the chooser's own tile: output and all
    three gradients against ``sdpa`` (f32 in, so only the order of the
    sums differs)."""
    q, k, v = _qkv(b=1, h=2, s=1024, d=64)
    seg = _sorted_segments(1, 1024, 5) if segments else None
    tile = attention.RESIDENT_TILE
    got = _fwd_and_grads(
        lambda q_, k_, v_: resident_flash_attention(
            q_, k_, v_, True, tile, tile, True, segment_ids=seg), q, k, v)
    want = _fwd_and_grads(
        lambda q_, k_, v_: sdpa(q_, k_, v_, causal=True, segment_ids=seg),
        q, k, v)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


@pytest.mark.parametrize("causal,blocks", [
    (True, (32, 32)), (False, (32, 32)), (True, (32, 64)),
    (True, (64, 32)), (True, (128, 128)),
], ids=["causal", "full", "bq<bk", "bq>bk", "one-tile"])
def test_resident_kernel_tilings(causal, blocks):
    """The static tile walk off the square path: every live pair is
    visited once, the pruned ones hold nothing, with packed documents
    that leave whole tiles masked."""
    q, k, v = _qkv(b=2, h=2, s=128, d=32)
    seg = _sorted_segments(2, 128, 3)
    got = _fwd_and_grads(
        lambda q_, k_, v_: resident_flash_attention(
            q_, k_, v_, causal, *blocks, True, segment_ids=seg), q, k, v)
    want = _fwd_and_grads(
        lambda q_, k_, v_: sdpa(q_, k_, v_, causal=causal, segment_ids=seg),
        q, k, v)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


def test_resident_kernel_bf16_rounds_as_sdpa_does():
    """bf16 in: f32 scores and statistics, probabilities cast to bf16 for
    the value matmul — within bf16 rounding of ``sdpa`` on the same
    inputs, output in the inputs' dtype."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(b=1, h=2, s=256, d=64))
    got = resident_flash_attention(q, k, v, True, 128, 128, True)
    assert got.dtype == jnp.bfloat16
    want = sdpa(q, k, v, causal=True).astype(jnp.float32)
    gap = jnp.max(jnp.abs(got.astype(jnp.float32) - want))
    assert float(gap) <= 0.02 * float(jnp.max(jnp.abs(want)))


# ---------------------------------------------------------------------
# the chooser
# ---------------------------------------------------------------------
def _case(backend, seq, head_dim, dropout=False):
    return dict(backend=backend, seq=seq, head_dim=head_dim,
                dropout=dropout)


@pytest.mark.parametrize("observed,path", [
    # the two training cells' local shapes ([32, 12, ..], [8, 10, ..])
    (_case("tpu", 1024, 64), "resident"),
    (_case("tpu", 1024, 128), "resident"),
    (_case("tpu", 512, 64), "resident"),
    (_case("tpu", 3072, 64), "resident"),
    (_case("tpu", 4096, 64), "resident"),
    # past what stays resident in VMEM: the streamed geometry
    (_case("tpu", 8192, 64), "streamed"),
    (_case("tpu", 16384, 128), "streamed"),
    # ViT's 65 positions; short; ragged; a head width never measured
    (_case("tpu", 65, 64), "sdpa"),
    (_case("tpu", 256, 64), "sdpa"),
    (_case("tpu", 1000, 64), "sdpa"),
    (_case("tpu", 1024, 80), "sdpa"),
    (_case("tpu", 4100, 64), "blockwise"),
    (_case("tpu", 8448, 64), "blockwise"),     # 33 x 256: no 512-wide tile
    # probability dropout: the kernels carry no PRNG
    (_case("tpu", 1024, 64, dropout=True), "sdpa"),
    (_case("tpu", 4096, 64, dropout=True), "blockwise"),
    # no chip: never a kernel
    (_case("cpu", 1024, 64), "sdpa"),
    (_case("cpu", 128, 32), "sdpa"),
    (_case("cpu", 4096, 64), "blockwise"),
], ids=lambda x: x if isinstance(x, str) else
    "{backend}-s{seq}-d{head_dim}{dr}".format(
        dr="-dropout" if x["dropout"] else "", **x))
def test_local_attention_path(observed, path):
    assert local_attention_path(**observed) == path


def test_local_attention_on_the_cpu_is_sdpa():
    """At the shapes the suite trains at, ``local_attention`` traces to
    exactly ``sdpa``'s jaxpr — dropout included — so every program the
    other tests build is the one they built before the chooser."""
    q, k, v = _qkv(s=64, d=32)
    key = jax.random.key(3)
    for kw in (dict(causal=True), dict(causal=False),
               dict(causal=True, pdrop=0.1, key=key),
               dict(causal=True, segment_ids=_sorted_segments(2, 64, 3))):
        got = jax.make_jaxpr(lambda *a: local_attention(*a, **kw))(q, k, v)
        want = jax.make_jaxpr(lambda *a: sdpa(*a, **kw))(q, k, v)
        assert str(got) == str(want)
        assert "pallas_call" not in str(got)


def test_local_attention_traces_both_where_a_chip_would_differ():
    """At a cell's shape a CPU process traces the kernel AND ``sdpa``
    under one platform switch; executed here it is ``sdpa``."""
    q, k, v = _qkv(b=1, h=1, s=1024, d=64)
    text = str(jax.make_jaxpr(
        lambda *a: local_attention(*a, causal=True))(q, k, v))
    assert "platform_index" in text and "pallas_call" in text
    np.testing.assert_array_equal(
        np.asarray(local_attention(q, k, v, causal=True)),
        np.asarray(sdpa(q, k, v, causal=True)))
