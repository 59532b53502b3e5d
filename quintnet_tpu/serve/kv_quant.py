"""KV-pool layout policies: what dtype a paged block is stored in, and
how it gets there.

KV memory bounds ``num_blocks``, which bounds concurrent users,
admission, and the prefix-cache hit rate — capacity IS concurrency.
KIVI (Liu et al., 2024) and
KVQuant (Hooper et al., 2024) show low-bit KV caches with fine-grained
scales preserve quality while 2-4x-ing resident context; this module
makes the pool's block dtype/layout a POLICY OBJECT so the same pool
bytes hold ~4x the blocks under int8 (f32's 4-byte slots shrink to 1
byte + a small per-block scale row; the CI gate asserts >= 1.8x)
without forking any kernel:

- ``f32`` / ``bf16`` — PASSTHROUGH: the pool arrays simply carry that
  dtype and every kernel runs its original scatter/gather code.
  Byte-identical to the pre-policy engine.
- ``int8`` — int8 storage with PER-BLOCK-PER-HEAD absmax scales
  (``scale[b, h] = max |block b, head h| / 127``) stored in f32 beside
  the k/v pools, one ``[L, num_blocks, H_kv]`` array each. The scale
  granularity is the paged unit itself: a block is written by exactly
  one request (shared prefix blocks are read-only by the COW
  discipline), so requantization on append touches only private
  blocks and a published chain's bytes never change underneath a
  reader. Under tp the scales shard on the head dim exactly like the
  pool.
- ``fp8`` — UNSCALED narrow-float storage (``float8_e4m3fn``, same
  1 byte/slot as int8 with NO scale arrays): writes narrow through
  the existing ``astype(cache.dtype)`` scatter, reads upcast once in
  the gathered view (``dequant(q, None)``). e4m3's ~2 mantissa-bit
  dynamic range absorbs KV outliers without per-block bookkeeping —
  the cheapest rung between bf16 and int8 on the quality ladder.
- ``fake_quant`` — the PROOF policy: f32 storage, the scale arrays
  exist and are all-ones, and every kernel runs the full scaled code
  path (gather -> dequantize -> insert -> requantize -> scatter) with
  quantization mathematically the identity (multiplying an f32 by
  exactly 1.0 is bit-exact, and the identity policy skips rounding).
  An engine on ``fake_quant`` is therefore BIT-IDENTICAL to the f32
  engine — which pins the restructured kernels as numerically inert,
  leaving the int8 rounding itself as the only quality variable
  (gated separately by the paged-ppl delta and the per-block
  dequant-error bound, tests/test_kv_quant.py).

Dequantization happens INSIDE the gathered-view attention kernels
(nn/attention.py): ``paged_attend`` (every decode, verify and prefill
program) and ``ring_paged_prefill`` gather int8 slots + their block scales, dequantize into the existing
f32-softmax math, and quantize on scatter. The pool stores int8; the
math never sees it.

The kernels receive the policy as a plain argument and call its
methods — nn/ keeps its no-serve-imports layering (this module is
imported by serve/, never by nn/).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class LayoutPolicy:
    """The shared quantize/dequant/scale-layout contract for paged KV
    blocks AND packed weights (serve/weight_quant.py subclasses this).

    ``scaled`` selects the code path: False = the original passthrough
    scatter/gather (no scale arrays exist), True = absmax scale arrays
    ride beside the stored data and every consumer runs
    gather->dequant / requant->scatter. ``qmax`` = 0 marks the
    identity (fake-quant) policy: no rounding, no clipping, scales
    pinned at 1.0 — the bit-exactness proof of the scaled path.
    Scales are OPTIONAL at dequant time: ``dequant(q, None)`` is the
    plain f32 upcast, which is what lets an UNSCALED narrow-float
    layout (fp8) share the contract — future formats (int4 groups, MX)
    are policy objects, not kernel forks."""

    name: str
    store_dtype: Any
    scaled: bool
    qmax: float = 0.0

    # ---- quant math (traced inside the serving programs) ------------
    def compute_scale(self, x, axes: Tuple[int, ...]):
        """Absmax scale of one quantization group: reduce ``axes`` (the
        slot and head-feature dims of a KV block; the in-features dim
        of a weight) of f32 ``x``. Identity policy: exactly 1.0
        everywhere. The floor keeps an all-zero group's scale finite —
        its dequant is exactly 0.0."""
        if self.qmax == 0.0:
            return jnp.ones(
                tuple(d for i, d in enumerate(x.shape) if i not in
                      tuple(a % x.ndim for a in axes)), jnp.float32)
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes)
        return jnp.maximum(amax / self.qmax, 1e-8)

    def quant(self, x, scale=None):
        """f32 data -> stored data. ``scale`` broadcastable to x;
        None (unscaled policies) is the plain narrowing cast. Integer
        storage rounds to the grid; float storage (scaled fp8 weights)
        keeps the fraction — the narrowing cast IS the rounding."""
        if scale is None or self.qmax == 0.0:
            return x.astype(self.store_dtype)
        q = x.astype(jnp.float32) / scale
        if jnp.issubdtype(jnp.dtype(self.store_dtype), jnp.integer):
            q = jnp.round(q)
        return jnp.clip(q, -self.qmax, self.qmax).astype(self.store_dtype)

    def dequant(self, q, scale=None):
        """Stored data -> f32. With the identity policy this is
        ``x * 1.0`` — bit-exact for every finite f32. ``scale=None``
        (unscaled policies, e.g. fp8) is the plain upcast."""
        if scale is None:
            return q.astype(jnp.float32)
        return q.astype(jnp.float32) * scale


@dataclass(frozen=True)
class KVLayoutPolicy(LayoutPolicy):
    """How paged KV blocks are laid out on device (the KV face of
    :class:`LayoutPolicy`, plus the pool capacity equation)."""

    # ---- capacity math (host-side) -----------------------------------
    def bytes_per_block(self, *, n_layers: int, n_kv_heads: int,
                        head_dim: int, block_size: int) -> int:
        """Device bytes one pool block costs under this policy: k + v
        slot data across layers, plus the two f32 per-block-per-head
        scale rows when scaled. THE capacity equation: at equal pool
        bytes, ``num_blocks`` scales inversely with this number."""
        item = int(np.dtype(self.store_dtype).itemsize)
        data = 2 * n_layers * block_size * n_kv_heads * head_dim * item
        scale = 2 * n_layers * n_kv_heads * 4 if self.scaled else 0
        return data + scale


# float8_e4m3fn where the backend ships it (ml_dtypes); the ladder
# entry exists either way so the pinned policy list stays static —
# make_policy raises a clear error if the dtype is actually missing.
FLOAT8_DTYPE = getattr(jnp, "float8_e4m3fn", None)

_POLICIES = {
    "f32": KVLayoutPolicy("f32", jnp.float32, scaled=False),
    "bf16": KVLayoutPolicy("bf16", jnp.bfloat16, scaled=False),
    "int8": KVLayoutPolicy("int8", jnp.int8, scaled=True, qmax=127.0),
    "fp8": KVLayoutPolicy("fp8", FLOAT8_DTYPE, scaled=False),
    "fake_quant": KVLayoutPolicy("fake_quant", jnp.float32, scaled=True,
                                 qmax=0.0),
}


def policy_names() -> Tuple[str, ...]:
    """The canonical policy ladder (also pinned in analysis/specs.py —
    compile counts are UNCHANGED per policy)."""
    return tuple(_POLICIES)


def make_policy(kv_dtype) -> KVLayoutPolicy:
    """Resolve ``ServeEngine(kv_dtype=...)`` / ``KVPool(...)`` input to
    a policy: a policy passes through, a name looks up the ladder, a
    raw dtype maps to its passthrough policy (the pre-policy
    surface — ``KVPool(dtype=jnp.bfloat16)`` keeps working)."""
    if kv_dtype is None:
        return _POLICIES["f32"]
    if isinstance(kv_dtype, KVLayoutPolicy):
        return kv_dtype
    if isinstance(kv_dtype, str):
        if kv_dtype not in _POLICIES:
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}; expected one of "
                f"{policy_names()}")
        pol = _POLICIES[kv_dtype]
        if pol.store_dtype is None:
            raise ValueError(
                f"kv_dtype {kv_dtype!r} needs jnp.float8_e4m3fn, which "
                "this jax build does not provide")
        return pol
    dt = jnp.dtype(kv_dtype)
    if dt == jnp.dtype(jnp.float32):
        return _POLICIES["f32"]
    if dt == jnp.dtype(jnp.bfloat16):
        return _POLICIES["bf16"]
    if FLOAT8_DTYPE is not None and dt == jnp.dtype(FLOAT8_DTYPE):
        return _POLICIES["fp8"]
    raise ValueError(
        f"no passthrough policy for dtype {dt}; use one of "
        f"{policy_names()}")


# ---------------------------------------------------------------------
# quality gates (tests/test_kv_quant.py)
# ---------------------------------------------------------------------

def dequant_roundtrip_error(policy: KVLayoutPolicy, x,
                            axes: Tuple[int, ...] = (-2, -1)):
    """(max |dequant(quant(x)) - x| per block, the block scales).

    The provable bound the int8 gate asserts: absmax quantization to
    qmax levels makes the round-trip error of every element at most
    ``scale / 2`` (round-to-nearest within a covered range — clipping
    never triggers because the scale IS the absmax). The identity
    policy's error is exactly zero."""
    x = jnp.asarray(x, jnp.float32)
    sc = policy.compute_scale(x, axes)
    sc_b = jnp.expand_dims(sc, tuple(a % x.ndim for a in axes))
    dq = policy.dequant(policy.quant(x, sc_b), sc_b)
    return jnp.max(jnp.abs(dq - x), axis=axes), sc


def paged_eval_nll(family, params, pool, rows, *, tp_axis=None) -> float:
    """Mean next-token NLL of ``rows`` [S, P] evaluated THROUGH the
    paged pool: each row's tokens are written into freshly acquired
    blocks and teacher-force scored in ONE verify call (the verify
    contract returns logits at every run position), so the number
    measures perplexity as the quantized pool actually serves it —
    dequantized gathered-view attention included — not as the dense
    forward computes it. ``exp(nll)`` is the ppl; the int8 quality
    gate asserts ``nll(int8) - nll(f32)`` under a threshold.

    Pool state is restored (blocks released) before returning; the
    scoring writes land in blocks nothing else references."""
    rows = np.asarray(rows, np.int32)
    S, P = rows.shape
    need = pool.blocks_for(P)
    tables = np.zeros((S, need), np.int32)
    held = []
    for s in range(S):
        got = pool.acquire(need)
        if got is None:
            for b in held:
                pool.release(b)
            raise ValueError(
                f"pool too small to score {S} rows of {P} tokens "
                f"({need} blocks each, {pool.num_available} available)")
        tables[s] = got
        held.append(got)
    caches = pool.caches()
    kv_scales = caches[2:] if pool.policy.scaled else None
    out = family.verify(
        params, caches[0], caches[1], jnp.asarray(rows),
        jnp.zeros((S,), jnp.int32), jnp.full((S,), P, jnp.int32),
        jnp.asarray(tables), pool.block_size, tp_axis=tp_axis,
        kv_scales=kv_scales, policy=pool.policy)
    logits = out[0]                                   # [S, P, V]
    pool.update(*out[1:])
    for b in held:
        pool.release(b)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = rows[:, 1:]
    picked = np.take_along_axis(np.asarray(logp), tgt[:, :, None],
                                axis=-1)[..., 0]
    return float(-picked.mean())
