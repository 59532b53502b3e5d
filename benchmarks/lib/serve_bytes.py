"""Bytes a decode step of a GPT-2 serving engine has to move, from its
shapes: the numerator of ``decode_hbm_roofline_pct``.

One decode step reads every parameter once (the batch shares them) and,
for each row, the keys and values of every position the row already
holds. Activations, the sampled tokens and the one new key/value row
are thousands of times smaller and are left out, so the count is a
floor: a share of the roofline computed from it can only be too low,
never over 100% for a step that really moved these bytes.

The reader takes ``param_bytes`` and ``kv_bytes_per_token`` from the
engine's own ring (``lib/step_ring.ring_static``); the two shape
formulas here are what the benchmark's tests hold those numbers to.
"""

from __future__ import annotations


def kv_bytes_per_token(n_layer: int, n_embd: int, itemsize: int) -> int:
    """Keys and values of one position over all layers (multi-head
    attention: as many KV heads as query heads). GPT-2 XL in bf16:
    2 x 48 x 1600 x 2 = 307,200 B."""
    return 2 * n_layer * n_embd * itemsize


def gpt2_param_bytes(n_layer: int, n_embd: int, vocab_size: int,
                     n_positions: int, *, weight_itemsize: int,
                     other_itemsize: int = 4) -> int:
    """The whole GPT-2 parameter tree as the engine holds it: the four
    block matmul weights (qkv, proj, fc, proj: 12 d^2 a layer) in the
    served type, everything else — their biases, the layer norms, the
    token and position tables, the final norm — in ``other_itemsize``.
    """
    d = n_embd
    weights = n_layer * 12 * d * d
    biases = n_layer * (3 * d + d + 4 * d + d)
    norms = n_layer * 4 * d + 2 * d
    tables = (vocab_size + n_positions) * d
    return (weights * weight_itemsize
            + (biases + norms + tables) * other_itemsize)


def decode_step_bytes(param_bytes: float, context_tokens: float,
                      kv_bytes_a_token: float) -> float:
    """Least bytes of one decode step: the parameters once, and the
    cache of every position the decoding rows hold."""
    return param_bytes + context_tokens * kv_bytes_a_token
