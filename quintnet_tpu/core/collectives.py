"""Named-axis collective primitives.

The TPU-native replacement for the reference's L0 layer: hand-written
autograd Functions around NCCL calls with a 3-message shape protocol for
P2P (reference: core/communication.py:46-600). Under ``shard_map`` every
``jax.lax`` collective is differentiable by construction and shapes are
static under jit, so each reference primitive collapses to one call:

- ``All_Reduce``   (communication.py:478-535)  -> :func:`all_reduce` (psum)
- ``All_Gather``   (communication.py:374-475)  -> :func:`all_gather`
- ``ReduceScatter``(communication.py:538-600)  -> :func:`reduce_scatter`
- ``Send``/``Recv``/``pipeline_communicate``
  (communication.py:46-371)                    -> :func:`ppermute_shift`

The gradient relationships the reference hand-codes (all_gather.bwd =
slice-or-reduce_scatter, all_reduce.bwd = identity, reduce_scatter.bwd =
all_gather, send.bwd = recv) fall out of JAX's transpose rules — see
tests/test_collectives.py for the golden checks.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

AxisName = Union[str, Sequence[str]]


def _scope(wrapper: str, axis: AxisName):
    """``jax.named_scope("<wrapper>_<axis>")`` (``all_reduce_tp``,
    ``reduce_scatter_dp``): metadata only, so a device trace names a
    collective by the wrapper that issued it and the mesh axis it
    crosses."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    return jax.named_scope("_".join((wrapper, *names)))


def all_reduce(x, axis: AxisName):
    """Sum-all-reduce over a named mesh axis (reference All_Reduce forward:
    communication.py:509-518; backward identity comes from psum's transpose)."""
    with _scope("all_reduce", axis):
        return lax.psum(x, axis)


def all_reduce_mean(x, axis: AxisName):
    """Mean-all-reduce — the DP gradient average the reference's DDP bucket
    path intends (gradient_reducer.py:64-99 + mean in ddp.py:125)."""
    with _scope("all_reduce_mean", axis):
        return lax.pmean(x, axis)


def all_gather(x, axis: AxisName, *, gather_dim: int = -1, tiled: bool = True):
    """Gather shards along ``gather_dim`` from all members of ``axis``.

    ``tiled=True`` concatenates (the reference's all_gather+cat on dim -1,
    communication.py:407-424); ``tiled=False`` stacks a new leading axis.
    """
    with _scope("all_gather", axis):
        return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, scatter_dim: int = -1):
    """Sum-reduce then scatter chunks along ``scatter_dim``
    (reference ReduceScatter forward: communication.py:565-580)."""
    with _scope("reduce_scatter", axis):
        return lax.psum_scatter(
            x, axis, scatter_dimension=_canon(scatter_dim, x.ndim),
            tiled=True)


def all_to_all(x, axis: AxisName, *, split_dim: int, concat_dim: int):
    """Transpose data across ``axis``: split ``split_dim`` into one chunk
    per member, exchange, concatenate received chunks along ``concat_dim``
    (source-rank order). No reference analogue — torch.distributed
    all_to_all is never used there; here it powers Ulysses sequence
    parallelism (ops/ulysses_attention.py) and MoE expert dispatch
    (nn/moe.py)."""
    with _scope("all_to_all", axis):
        return lax.all_to_all(x, axis, _canon(split_dim, x.ndim),
                              _canon(concat_dim, x.ndim), tiled=True)


def _canon(dim: int, ndim: int) -> int:
    return dim % ndim


def axis_index(axis: str):
    """This device's coordinate along ``axis`` (reference: coordinate
    lookup mesh.py:268-294)."""
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    return lax.axis_size(axis)


def ppermute_shift(x, axis: str, *, shift: int = 1, wrap: bool = True):
    """Shift values along a named axis: device i sends to i+shift.

    This is the pipeline P2P primitive — the reference's
    ``pipeline_communicate('send_forward'/'recv_forward')`` pair with its
    ndims/shape/data message protocol and cuda synchronize
    (communication.py:207-296) reduces to one differentiable ppermute.
    With ``wrap=False`` the edge devices receive zeros (matching the
    boundary no-ops at first/last stage, communication.py:219-226).
    """
    n = lax.axis_size(axis)
    if wrap:
        perm = [(i, (i + shift) % n) for i in range(n)]
    else:
        perm = [(i, i + shift) for i in range(n) if 0 <= i + shift < n]
    with _scope("ppermute_shift", axis):
        return lax.ppermute(x, axis, perm)


def send_forward(x, axis: str = "pp"):
    """Stage i -> stage i+1; first stage receives zeros
    (reference: communication.py:207-296 'send_forward'/'recv_forward')."""
    return ppermute_shift(x, axis, shift=1, wrap=False)


def send_backward(x, axis: str = "pp"):
    """Stage i -> stage i-1 (gradient direction); last stage receives zeros
    (reference: 'send_backward'/'recv_backward')."""
    return ppermute_shift(x, axis, shift=-1, wrap=False)


def broadcast_from(x, axis: str, *, src: int = 0):
    """Every member of ``axis`` gets src's value (reference DP param
    broadcast: parameter_broadcaster.py:30-79). Implemented as a masked
    psum so it stays differentiable and jit-friendly."""
    idx = lax.axis_index(axis)
    # jnp.where (not multiply-by-mask) so NaN/Inf garbage on non-src ranks
    # cannot poison the psum — e.g. pipeline outputs that are only
    # meaningful on the last stage.
    with _scope("broadcast_from", axis):
        return lax.psum(jnp.where(idx == src, x, jnp.zeros_like(x)), axis)


def tree_all_reduce(tree, axis: AxisName):
    """psum every leaf — the whole DDP bucketing machinery
    (bucket.py/bucket_manager.py/gradient_reducer.py, ~470 LoC) in one line;
    XLA fuses/buckets collectives itself."""
    with _scope("all_reduce", axis):
        return jax.tree.map(lambda g: lax.psum(g, axis), tree)


def tree_all_reduce_mean(tree, axis: AxisName):
    with _scope("all_reduce_mean", axis):
        return jax.tree.map(lambda g: lax.pmean(g, axis), tree)


def shard_map_fn(
    fn: Callable,
    mesh: Mesh,
    in_specs,
    out_specs,
    *,
    check_vma: bool = False,
):
    """Wrap ``fn`` in ``jax.shard_map`` on ``mesh``.

    Central chokepoint so schedules/layers do not import the (still
    moving) shard_map API directly. ``check_vma=False`` because pipeline
    schedules legitimately produce values that are only meaningful on a
    subset of stages (e.g. loss on the last pp stage — the situation the
    reference handles by re-reading labels on the last stage,
    pipeline_parallel/trainer.py:222-253).
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
