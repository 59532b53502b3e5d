"""TPU kernels (Pallas) and kernel-backed ops with reference jnp
fallbacks.

The public surface, re-exported here (tests/test_paged_attention.py
pins it):

- :func:`resident_flash_attention` / :func:`pallas_flash_attention` —
  the fused training attention kernel in its two geometries (whole
  heads resident in VMEM; tiles streamed through a grid), and
  :func:`blockwise_attention`, their exact jnp twin. Which one a model
  runs is ``nn/attention.local_attention``'s choice, from the shape;
- :func:`ring_attention` / :func:`zigzag_ring_attention` /
  :func:`ulysses_attention` — the sequence-parallel inner attentions.

Beside it, imported as modules by the one layer that runs them:
``ops.paged_attention.paged_walk_attention`` (the per-row walk of decode
and verify, nn/attention.py) and ``ops.grouped_matmul`` (the dropless
mixture's grouped matmul over touched experts only, nn/moe.py).
"""

from quintnet_tpu.ops.flash_attention import blockwise_attention
from quintnet_tpu.ops.pallas_attention import (pallas_flash_attention,
                                               resident_flash_attention)
from quintnet_tpu.ops.ring_attention import (ring_attention,
                                             zigzag_ring_attention)
from quintnet_tpu.ops.ulysses_attention import ulysses_attention

__all__ = [
    "blockwise_attention",
    "pallas_flash_attention",
    "resident_flash_attention",
    "ring_attention",
    "ulysses_attention",
    "zigzag_ring_attention",
]
