"""Model FLOPs of one GPT-2 training token, counted from the shapes.

The count (forward and backward, recomputation NOT counted — a remat
step executes more than this and that is the point of a *model* FLOPs
utilization):

- every matmul weight that a token passes through costs 2 FLOPs per
  parameter forward and 4 backward: per layer ``12 d^2`` parameters
  (qkv ``3 d^2``, attention out ``d^2``, MLP ``8 d^2``), and the tied
  output head ``V d``;
- attention itself: per layer and token, QK^T and PV are each
  ``2 S d`` FLOPs forward over a full ``S x S`` square, halved by
  causality: ``2 S d`` forward, ``6 S d`` with the backward pass (the
  issue's ``12 L S d`` halved);
- the token and position tables are gathers, not matmuls: 0 FLOPs.
  Layer norms, GELU, softmax and the optimizer are elementwise and left
  out, as is usual.

How this differs from ``bench.flops_per_token_gpt2`` (repo root): that
function is ``6 N`` over ALL parameters — it counts the position table
``n_positions x d`` as if it were a matmul and the biases and layer-norm
gains too (``13 d`` per layer), and it has no attention term at all. At
GPT-2 124M and S = 1024 this count is 0.7978e9 a token (0.7412e9 of
matmuls plus 0.0566e9 of attention, +7.6%) against bench.py's 0.7466e9.
"""

from __future__ import annotations


def gpt2_train_flops_per_token(n_layer: int, n_embd: int, vocab_size: int,
                               seq_len: int) -> float:
    d = n_embd
    matmul_params = n_layer * 12 * d * d + vocab_size * d
    attention = n_layer * 6 * seq_len * d
    return 6.0 * matmul_params + attention
