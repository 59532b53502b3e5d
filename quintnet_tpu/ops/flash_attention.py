"""Blockwise attention: the online-softmax recurrence in pure jnp.

Numerically identical to softmax(QK^T)V with O(S) live memory under
scan: the path ``nn/attention.local_attention`` takes where the [S, S]
scores do not fit and the Pallas kernels (ops/pallas_attention.py)
cannot run — a ragged sequence, probability dropout (the kernels carry
no PRNG: ``pltpu.prng_*`` has no interpret-mode lowering in this jax,
so an in-kernel mask could not be tested here) — and the reference the
kernels' tests compare with.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _one_query_block(q_blk, qi, key_qb, seg_q, k_blocks, v_blocks, kv_valid,
                     seg_k, *, causal: bool, block_q: int, block_k: int,
                     scale: float, pdrop: float, has_seg: bool):
    """Online-softmax over all KV blocks for one query block.

    q_blk: [bq, d]; k_blocks/v_blocks: [nk, bk, d]; kv_valid: [nk, bk];
    key_qb: per-(batch, head, q-block) PRNG key (or None) for
    attention-probability dropout; seg_q [bq] / seg_k [nk, bk]:
    packed-segment ids (``has_seg``) — cross-segment pairs are masked.

    Dropout semantics match sdpa's drop-after-softmax: the normaliser
    ``l`` accumulates the UNdropped probs while the numerator ``acc``
    accumulates dropped ones — exp(s)·mask/keep divided by Σ exp(s)
    equals dropout(softmax(s)) since the 1/keep scaling commutes.
    """
    d = q_blk.shape[-1]
    nk = k_blocks.shape[0]
    q_pos = qi * block_q + jnp.arange(block_q)
    qf = q_blk.astype(jnp.float32)

    def kv_step(carry, inp):
        m, l, acc = carry
        ki, k_blk, v_blk, valid, sk = inp
        scores = jnp.einsum("qd,kd->qk", qf, k_blk.astype(jnp.float32)) * scale
        mask = valid[None, :]
        if causal:
            k_pos = ki * block_k + jnp.arange(block_k)
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if has_seg:
            mask = mask & (seg_q[:, None] == sk[None, :])
        scores = jnp.where(mask, scores, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(scores, -1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)  # fully-masked rows
        p = jnp.where(mask, jnp.exp(scores - m_safe[:, None]), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, -1)
        p_num = p
        if key_qb is not None and pdrop > 0.0:
            keep = jax.random.bernoulli(
                jax.random.fold_in(key_qb, ki), 1.0 - pdrop, p.shape)
            p_num = jnp.where(keep, p / (1.0 - pdrop), 0.0)
        acc_new = acc * corr[:, None] + jnp.einsum(
            "qk,kd->qd", p_num, v_blk.astype(jnp.float32))
        return (m_safe, l_new, acc_new), None

    init = (
        jnp.full((block_q,), -jnp.inf, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
        jnp.zeros((block_q, d), jnp.float32),
    )
    (_, l, acc), _ = lax.scan(kv_step, init,
                              (jnp.arange(nk), k_blocks, v_blocks, kv_valid,
                               seg_k))
    return acc / jnp.maximum(l, 1e-30)[:, None]


def blockwise_attention(q, k, v, *, causal: bool,
                        block_q: int = 128, block_k: int = 128,
                        pdrop: float = 0.0, key=None, segment_ids=None):
    """Exact blockwise attention [B,H,S,D] -> [B,H,S,D] (jnp reference for
    the Pallas kernel; also the long-context-safe fallback).

    ``pdrop``/``key``: attention-probability dropout (training only) —
    the reference gets this from sdpa's dropout_p in every config
    (gpt2_attention.py:156-161); here the fused paths support it too.
    ``segment_ids``: [B, S] packed-document ids; cross-segment
    attention is masked."""
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = -(-s // block_q)
    nk = -(-s // block_k)
    pad_q = nq * block_q - s
    pad_k = nk * block_k - s
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))).reshape(b, h, nq, block_q, d)
    kb = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))).reshape(b, h, nk, block_k, d)
    vb = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))).reshape(b, h, nk, block_k, d)
    kv_valid = (jnp.arange(nk * block_k) < s).reshape(nk, block_k)

    has_seg = segment_ids is not None
    if has_seg:
        seg = segment_ids.astype(jnp.int32)
        # pad with -1: never equal to a real id, so pad cols stay masked
        seg_qb = jnp.pad(seg, ((0, 0), (0, pad_q)),
                         constant_values=-1).reshape(b, nq, block_q)
        seg_kb = jnp.pad(seg, ((0, 0), (0, pad_k)),
                         constant_values=-1).reshape(b, nk, block_k)
    else:  # dummies that only shape the vmaps
        seg_qb = jnp.zeros((b, nq, block_q), jnp.int32)
        seg_kb = jnp.zeros((b, nk, block_k), jnp.int32)

    use_drop = key is not None and pdrop > 0.0
    # one key per (batch, head, q-block) cell; the k-block index is
    # folded inside the scan so every (q, k) pair draws an iid mask
    keys = (jax.random.split(key, (b, h, nq)) if use_drop else
            jnp.zeros((b, h, nq), jnp.uint32))  # dummy, vmap shape only

    def one(q_blk, qi, kq, sq, k_all, v_all, sk):
        return _one_query_block(q_blk, qi, kq if use_drop else None, sq,
                                k_all, v_all, kv_valid, sk,
                                causal=causal, block_q=block_q,
                                block_k=block_k, scale=scale, pdrop=pdrop,
                                has_seg=has_seg)

    f = jax.vmap(one, in_axes=(0, 0, 0, 0, None, None, None))  # q blocks
    f = jax.vmap(f, in_axes=(0, None, 0, None, 0, 0, None))    # heads
    f = jax.vmap(f, in_axes=(0, None, 0, 0, 0, 0, 0))          # batch
    out = f(qb, jnp.arange(nq), keys, seg_qb, kb, vb, seg_kb)
    return out.reshape(b, h, nq * block_q, d)[:, :, :s].astype(q.dtype)
