"""Core layers as init/apply function pairs over plain pytrees.

Conventions:
- Params are dicts of jnp arrays with FULL (global) shapes; under
  shard_map a device sees its local shard and the apply functions take
  named-axis arguments where a collective is required.
- Weights are stored [in_features, out_features] so forward is ``x @ w``
  (no transpose; feeds the MXU directly). The reference stores torch's
  [out, in] and the GPT-2 loader transposes Conv1D weights
  (core/distributed_loading.py:295-306); our checkpoint importer does
  that transpose once at load time instead of every step.
- dtype policy: params kept in ``param_dtype`` (default f32), compute
  optionally in bfloat16 — the TPU-native mixed-precision default.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from quintnet_tpu.core import collectives as cc


def cast_floating(tree, dtype, *, exclude=None):
    """Cast floating-point leaves to ``dtype`` (None -> no-op).

    The mixed-precision cast-at-use policy: storage stays f32 master
    copies; astype's transpose accumulates grads back in f32. Integer
    leaves (e.g. token ids living inside a batch pytree) pass through.

    ``exclude(path) -> bool`` keeps matching leaves at their stored
    dtype — used to pin precision-critical leaves (the MoE router, whose
    gate ORDERING changes under bf16 rounding — nn/moe.py) at f32.
    """
    if dtype is None:
        return tree

    def cast(x):
        return (x.astype(dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x)

    if exclude is None:
        return jax.tree.map(cast, tree)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x if exclude(path) else cast(x), tree)


def _path_has_key(path, name: str) -> bool:
    """True if any pytree path element is a dict key == name."""
    return any(getattr(p, "key", None) == name for p in path)


def keep_router_f32(path) -> bool:
    """cast_floating exclude-predicate pinning MoE router weights to f32."""
    return _path_has_key(path, "router")


def _uniform_init(key, shape, scale, dtype):
    return jax.random.uniform(key, shape, dtype, minval=-scale, maxval=scale)


def linear_init(key, in_features: int, out_features: int, *,
                use_bias: bool = True, dtype=jnp.float32):
    """Kaiming-uniform fan-in init, matching torch.nn.Linear defaults so
    convergence curves are comparable with the reference."""
    kw, kb = jax.random.split(key)
    scale = 1.0 / math.sqrt(in_features)
    p = {"w": _uniform_init(kw, (in_features, out_features), scale, dtype)}
    if use_bias:
        p["b"] = _uniform_init(kb, (out_features,), scale, dtype)
    return p


def _is_packed(dtype) -> bool:
    """True for quantized weight storage dtypes (int8 / float8) that
    must be upcast EXPLICITLY before the dot — float8 has no implicit
    promotion path in jax, and an integer dot is not what weight-only
    quantization means."""
    return (jnp.dtype(dtype) == jnp.dtype(jnp.int8)
            or str(jnp.dtype(dtype)).startswith("float8"))


def quantized_matmul(x, node, *, precision=None):
    """``x @ dequant(node)`` — THE weight-only-quantization seam every
    serving matmul routes through (serve/weight_quant.py).

    ``node`` is a linear param node ``{"w": [.., in, out]}`` that MAY
    carry a packed weight (int8/fp8 storage) and a per-output-channel
    ``"w_scale"`` [.., out] f32 leaf. The per-channel scale commutes
    out of the contraction, so dequant is one multiply on the OUTPUT —
    ``(x @ w_q) * scale`` — and the wide weight is never materialized.
    Without ``w_scale`` and without a packed dtype this IS
    ``jnp.dot(x, node["w"])``, byte-identical to the pre-policy
    programs; with the fake_quant policy (f32 storage, all-ones scale)
    the result is BIT-identical (``y * 1.0``). Bias and LoRA deltas are
    the caller's job — both stay full-precision on top."""
    w = node["w"]
    if w.dtype != x.dtype and _is_packed(w.dtype):
        w = w.astype(x.dtype)
    y = jnp.dot(x, w, precision=precision)
    if "w_scale" in node:
        y = y * node["w_scale"]
    return y


def linear_apply(p, x, *, precision=None):
    y = quantized_matmul(x, p, precision=precision)
    if "b" in p:
        y = y + p["b"]
    return y


def lora_delta(x, node, scale):
    """Per-slot batched low-rank delta for multi-tenant LoRA serving
    (serve/adapters.py; the Punica/S-LoRA batched-gather matmul): each
    row of the batch applies ITS OWN adapter.

    ``x``: [S, T, in] per-slot activations; ``node``: packed adapters
    ``{"a": [S, in, r], "b": [S, r, out]}`` (zero rows for base-model
    slots — the KV pool's null-object trick applied to weights: a zero
    adapter contributes an exactly-zero delta); ``scale``: [S] per-slot
    ``alpha / rank``. Returns ``scale_s * (x_s @ a_s) @ b_s`` as
    [S, T, out], cast back to ``x.dtype`` so the targeted matmul's
    dtype story is unchanged.

    Under tp the delta composes with the Megatron sharding exactly like
    models/lora.py's merge: for a column-parallel target ``b`` arrives
    out-sharded (the delta is the local columns' delta); for a
    row-parallel target ``a`` arrives in-sharded and the local delta is
    a PARTIAL sum that rides the layer's existing RowParallel psum — no
    new collectives either way."""
    h = jnp.einsum("std,sdr->str", x, node["a"])
    return (jnp.einsum("str,sro->sto", h, node["b"])
            * scale[:, None, None]).astype(x.dtype)


def layer_norm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layer_norm_apply(p, x, *, eps: float = 1e-5):
    # Always normalise in f32 for stability, cast back to input dtype.
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dtype)


def rms_norm_init(dim: int, dtype=jnp.float32):
    """RMSNorm (Llama-family): scale only, no bias/centering."""
    return {"scale": jnp.ones((dim,), dtype)}


def rms_norm_apply(p, x, *, eps: float = 1e-6):
    """x * rsqrt(mean(x^2)+eps) * scale — f32 accumulation, HF Llama
    semantics (scale multiplies AFTER the cast back in HF; kept in f32
    here then cast once, equivalent to float tolerance)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                 keepdims=True) + eps)
    return (y * p["scale"]).astype(dtype)


def swiglu_init(key, dim: int, hidden: int, *, dtype=jnp.float32):
    """Llama MLP: gate/up column-shardable [D, H/tp], down row-shardable
    [H/tp, D]; no biases."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": linear_init(k1, dim, hidden, use_bias=False, dtype=dtype),
        "up": linear_init(k2, dim, hidden, use_bias=False, dtype=dtype),
        "down": linear_init(k3, hidden, dim, use_bias=False, dtype=dtype),
    }


def swiglu_apply(p, x, *, tp_axis: Optional[str] = None, lora=None,
                 lora_scale=None):
    """silu(x@gate) * (x@up) @ down, one psum after down under tp
    (same ColumnParallel->RowParallel shape as mlp_apply).

    ``lora``/``lora_scale``: per-slot packed adapters for the serving
    multi-LoRA path (:func:`lora_delta`) — each present target
    (gate/up/down) adds its low-rank delta on that matmul, before the
    activation/psum, exactly where a merged weight would land."""
    g = quantized_matmul(x, p["gate"])
    u = quantized_matmul(x, p["up"])
    if lora is not None and "gate" in lora:
        g = g + lora_delta(x, lora["gate"], lora_scale)
    if lora is not None and "up" in lora:
        u = u + lora_delta(x, lora["up"], lora_scale)
    h = jax.nn.silu(g) * u
    y = quantized_matmul(h, p["down"])
    if lora is not None and "down" in lora:
        y = y + lora_delta(h, lora["down"], lora_scale)
    if tp_axis is not None:
        y = cc.all_reduce(y, tp_axis)
    return y


def embedding_init(key, num_embeddings: int, features: int, *,
                   scale: float = 0.02, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (num_embeddings, features), dtype) * scale}


def embedding_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def dropout(key, x, rate: float, *, deterministic: bool):
    if deterministic or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def gelu(x):
    # tanh approximation — what GPT-2 uses (reference: gpt2_mlp GELU)
    return jax.nn.gelu(x, approximate=True)


def patchify(images, patch_size: int):
    """[B, H, W, C] -> [B, (H/p)*(W/p), p*p*C].

    The reference patch-embeds with Conv2d(kernel=stride=p)
    (utils/model.py:150-195); on TPU a reshape + one big matmul is the
    same linear map and lands straight on the MXU with no conv lowering.
    """
    b, h, w, c = images.shape
    p = patch_size
    assert h % p == 0 and w % p == 0, (h, w, p)
    x = images.reshape(b, h // p, p, w // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # B, H/p, W/p, p, p, C
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def mlp_init(key, dim: int, hidden: int, *, dtype=jnp.float32):
    """Transformer MLP: fc (column-shardable) -> act -> proj (row-shardable).
    Reference: utils/model.py:112-148 (ViT, ReLU), utils/GPT2/gpt2_mlp.py
    (GPT-2, GELU)."""
    k1, k2 = jax.random.split(key)
    return {
        "fc": linear_init(k1, dim, hidden, dtype=dtype),
        "proj": linear_init(k2, hidden, dim, dtype=dtype),
    }


def mlp_apply(p, x, *, act=gelu, tp_axis: Optional[str] = None,
              pdrop: float = 0.0, key=None, lora=None, lora_scale=None):
    """With ``tp_axis``: fc weight is column-sharded [D, hidden/tp] and proj
    row-sharded [hidden/tp, D]; the single psum after proj reproduces the
    reference's ColumnParallel->RowParallel pair (gpt2_mlp.py:98-125).

    ``pdrop``/``key``: output dropout after the projection — the
    reference's post-c_proj Dropout (gpt2_mlp.py:124-160). Applied after
    the psum so the mask is identical on every tp rank (required: the
    output is replicated).

    ``lora``/``lora_scale``: per-slot packed adapters (serving
    multi-LoRA, :func:`lora_delta`) — fc's delta lands before the
    activation, proj's before the psum, exactly where merged weights
    would put them."""
    # fc bias is sharded with the columns, so it adds locally (no collective)
    h = linear_apply(p["fc"], x)
    if lora is not None and "fc" in lora:
        h = h + lora_delta(x, lora["fc"], lora_scale)
    h = act(h)
    y = quantized_matmul(h, p["proj"])
    if lora is not None and "proj" in lora:
        y = y + lora_delta(h, lora["proj"], lora_scale)
    if tp_axis is not None:
        y = cc.all_reduce(y, tp_axis)
    if "b" in p["proj"]:
        y = y + p["proj"]["b"]
    if key is not None and pdrop > 0.0:
        y = dropout(key, y, pdrop, deterministic=False)
    return y
