"""GPT-2, plainly: the forward pass and the next-token loss in
``jax.numpy``, float32, every matmul at ``precision="highest"`` (on a
TPU a float32 matmul otherwise runs in bf16 passes). No kernel, no
cache, no scan, no sharding; nothing is imported from ``quintnet_tpu``.

Written from the published description (Radford et al. 2019; the
reference TensorFlow ``model.py``): learned token and position tables
summed; per layer a pre-layer-norm causal multi-head attention (one
fused ``qkv`` projection laid out ``[q | k | v]``, heads contiguous,
scores scaled by ``1/sqrt(head_dim)``) and a pre-layer-norm MLP of
width ``4 d`` with the tanh GELU, each added to the residual; a final
layer norm; logits against the transposed token table. Layer-norm
epsilon 1e-5.

The parameter TREE is the program's (it is the same weights that are
compared): ``embedding.{wte,wpe}``, ``blocks.{ln1,ln2}.{scale,bias}``,
``blocks.attn.{qkv,proj}.{w,b}``, ``blocks.mlp.{fc,proj}.{w,b}`` with a
leading layer axis, ``head.ln_f``. Leaves may be stored in bf16 (the
serving cells); each layer's are cast up as it is used, one layer at a
time in a Python loop, so a 1.5B-parameter model is never held twice.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _layer_norm(p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _linear(p, x):
    return jnp.matmul(x, p["w"], precision=HI) + p["b"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(blk, h, n_head: int):
    blk = _f32(blk)
    B, T, D = h.shape
    dh = D // n_head
    qkv = _linear(blk["attn"]["qkv"], _layer_norm(blk["ln1"], h))
    q, k, v = (t.reshape(B, T, n_head, dh).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=HI) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1),
                     v, precision=HI)
    att = att.transpose(0, 2, 1, 3).reshape(B, T, D)
    h = h + _linear(blk["attn"]["proj"], att)
    m = _gelu_tanh(_linear(blk["mlp"]["fc"], _layer_norm(blk["ln2"], h)))
    return h + _linear(blk["mlp"]["proj"], m)


_block_jit = jax.jit(_block, static_argnames=("n_head",))


@jax.jit
def _embed(emb, ids):
    T = ids.shape[1]
    return (emb["wte"][ids].astype(jnp.float32)
            + emb["wpe"][:T].astype(jnp.float32)[None])


@jax.jit
def _head(ln_f, wte, h):
    h = _layer_norm(_f32(ln_f), h)
    return jnp.matmul(h, wte.astype(jnp.float32).T, precision=HI)


def forward(params, ids, *, n_head: int, vocab_size: int):
    """``ids`` [B, T] int32 -> logits [B, T, vocab_size] float32."""
    h = _embed(params["embedding"], ids)
    n_layer = jax.tree.leaves(params["blocks"])[0].shape[0]
    for i in range(n_layer):
        blk = jax.tree.map(lambda x: x[i], params["blocks"])
        h = _block_jit(blk, h, n_head=n_head)
    logits = _head(params["head"]["ln_f"], params["embedding"]["wte"], h)
    return logits[..., :vocab_size]


@jax.jit
def _nll(logits, ids):
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss(params, ids, *, n_head: int, vocab_size: int,
         rows_at_once: int = 2) -> float:
    """Mean next-token cross-entropy over ``ids`` [B, T] (labels are the
    inputs shifted by one; every row has T - 1 targets, so the mean of
    the chunks' means is the mean). Walks the batch ``rows_at_once``
    rows at a time so the [rows, T, V] logits stay small."""
    total, n = 0.0, 0
    for i in range(0, ids.shape[0], rows_at_once):
        rows = ids[i:i + rows_at_once]
        part = _nll(forward(params, rows, n_head=n_head,
                            vocab_size=vocab_size), rows)
        total += float(part) * rows.shape[0]
        n += rows.shape[0]
    return total / n
