"""Ulysses attention: all-to-all head-scatter over a sequence axis.

Second long-context mode alongside ring attention (the reference has NO
sequence/context parallelism — SURVEY §5.7). Where ring attention keeps
queries resident and rotates K/V chunks around the ``sp`` ring in sp
steps, Ulysses (DeepSpeed-Ulysses, Jacobs et al. 2023) pays exactly two
all-to-alls: one to exchange the head dim for the sequence dim (each
device ends up with H_local/sp heads but the FULL sequence), one to swap
back after attention. In between, attention is an ordinary local call —
so it composes with the Pallas flash kernel, which the ring formulation
cannot use across chunks.

Trade-off (scaling-book mental model): ring moves O(S·D) K/V bytes per
step for sp steps but overlaps them with compute; Ulysses moves
O(S·D·3/sp) once per direction on the fast ICI all-to-all and needs
``local_heads % sp == 0``. For head-rich models at moderate sp, Ulysses
is usually faster; ring scales to sp > n_heads.

TPU mapping: ``lax.all_to_all(tiled=True)`` lowers to a single XLA
AllToAll riding ICI; both collectives are differentiable by
construction (the transpose of an all-to-all is the reverse
all-to-all), so this file contains no custom VJP.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from quintnet_tpu.core import collectives as cc
from quintnet_tpu.nn import attention as _attn


def ulysses_attention(q, k, v, *, axis: str, causal: bool = False,
                      pdrop: float = 0.0, key=None, segment_ids=None):
    """Attention over sequence-sharded inputs via two all-to-alls.

    q/k/v: [B, H_local, S_local, Dh] with the sequence dim sharded over
    mesh axis ``axis``. Requires H_local divisible by the axis size.
    Returns [B, H_local, S_local, Dh], numerically equal to full-sequence
    attention on the gathered sequence (tests/test_sp.py golden checks).

    ``pdrop``/``key``: attention-prob dropout on the inner (full-
    sequence, local-head-subset) attention; each rank folds its axis
    index since it owns a disjoint head subset after the scatter.

    ``segment_ids`` [B, S_local]: this rank's slice of the GLOBAL
    packed-segment ids — after the head-scatter every rank holds the
    full sequence, so one cheap [B, S] all-gather reassembles the id
    vector and the inner attention (``nn/attention.local_attention``)
    masks cross-segment pairs natively.
    """
    sp = lax.axis_size(axis)
    h_local = q.shape[1]
    if h_local % sp != 0:
        raise ValueError(
            f"ulysses attention needs local heads ({h_local}) divisible by "
            f"sp axis size ({sp}); use ring attention (sp_mode='ring') for "
            "sp larger than the head count")

    # scatter heads, gather sequence: [B, H/sp, S_full, Dh]. Source-rank
    # order == sequence-chunk order, so the concat reassembles the
    # sequence correctly. q/k/v ride ONE collective (stacked on a leading
    # axis) so the whole layer costs two all-to-all dispatches, fwd+bwd.
    qkv = jnp.stack([q, k, v])  # [3, B, H_local, S_local, Dh]
    qkv = cc.all_to_all(qkv, axis, split_dim=2, concat_dim=3)
    qf, kf, vf = qkv[0], qkv[1], qkv[2]

    seg_full = None
    if segment_ids is not None:
        seg_full = cc.all_gather(segment_ids.astype(jnp.int32), axis,
                                 gather_dim=1)   # [B, S_full]

    k_local = None
    if key is not None and pdrop > 0.0:
        k_local = jax.random.fold_in(key, lax.axis_index(axis))

    of = _attn.local_attention(qf, kf, vf, causal=causal, pdrop=pdrop,
                               key=k_local, segment_ids=seg_full)

    # gather heads back, re-scatter sequence: [B, H_local, S_local, Dh]
    return cc.all_to_all(of, axis, split_dim=2, concat_dim=1)
