"""Weights from the seed, on the device, in one jitted call.

The program's own initialisers build a model layer by layer (48 blocks
of GPT-2 XL are 48 traced initialisers and a stack: 4 minutes to
compile cold, 18 s to run warm — my chip run, PR 24). Speed and
agreement with the reference need only seeded weights of the right
shapes and scale, so the benchmark fills the program's parameter TREE
(taken from ``jax.eval_shape`` of its initialiser, never hand-written)
leaf by name: ``scale`` ones, ``b``/``bias`` zeros, the position table
normal(0, 0.01), every other leaf normal(0, 0.02) — GPT-2's published
initialisation without its depth scaling of the residual projections.
The bits come from the ``rbg`` generator (the chip's own), keyed by the
seed and the leaf's path.
"""

from __future__ import annotations

import zlib
from typing import Callable, Optional


def seeded_params(init: Callable, seed: int,
                  finish: Optional[Callable] = None):
    """Parameters shaped like ``init(key)``'s, filled from ``seed``.
    ``finish`` (say, the engine's weight packing) runs inside the same
    jitted call, so a weight is never held in a wider type than it is
    served in."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(init, jax.random.key(0))

    def fill(path, leaf, key):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            return jnp.ones(leaf.shape, leaf.dtype)
        if name in ("b", "bias"):
            return jnp.zeros(leaf.shape, leaf.dtype)
        std = 0.01 if name == "wpe" else 0.02
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
        return (std * jax.random.normal(k, leaf.shape, jnp.float32)
                ).astype(leaf.dtype)

    def make(key):
        tree = jax.tree_util.tree_map_with_path(
            lambda p, leaf: fill(p, leaf, key), shapes)
        return tree if finish is None else finish(tree)

    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)
    return jax.jit(make)(key)
