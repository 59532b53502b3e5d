"""Weight layout policies: what dtype the serving matmul weights are
stored in, and how they get there.

KV capacity is solved (int8 blocks: ~4x the blocks at equal pool
bytes), which leaves decode WEIGHT-bandwidth-bound — at serving batch sizes
the weights dominate bytes moved per token (the KVQuant framing;
AWQ/LLM.int8 attack the same bottleneck from the weights side). This
module makes the packed-weight dtype a POLICY OBJECT on the shared
:class:`~quintnet_tpu.serve.kv_quant.LayoutPolicy` contract, so
weights and KV consume ONE quantize/dequant/scale-layout protocol:

- ``f32`` — the identity: ``quantize_params`` returns the tree
  UNTOUCHED (same arrays, same bytes — the pre-policy engine).
- ``bf16`` — passthrough narrowing: weights stored bf16, upcast by
  jax's native promotion inside the dot. Half the bytes, no scales.
- ``int8`` — PER-OUTPUT-CHANNEL absmax (``scale[l, o] = max_i
  |w[l, i, o]| / 127``, f32, stored as a ``w_scale`` leaf BESIDE the
  packed ``w``). The channel is the quantization group because the
  scale then commutes out of the contraction: ``x @ dq(w) = (x @ q)
  * scale`` — dequant happens INSIDE the matmul
  (nn/layers.quantized_matmul) as one cheap per-column multiply, and
  the packed weight is never materialized wide.
- ``fp8`` — scaled ``float8_e4m3fn`` storage (qmax 448, the e4m3
  finite max): same per-channel scales, but the narrowing cast keeps
  the fraction (no integer rounding) — e4m3's mantissa does the
  rounding. Same 4x byte ratio as int8 with a float-shaped error.
- ``fake_quant`` — the PROOF policy: f32 storage, all-ones scales,
  the full scaled code path (pack -> quantized_matmul -> per-channel
  multiply) with quantization mathematically the identity. An engine
  on ``fake_quant`` weights is BIT-IDENTICAL to the f32 engine, which
  pins the quantized-matmul seam as numerically inert and leaves the
  rounding itself as the only quality variable (gated by the
  paged_eval_nll ppl delta + the per-channel round-trip bound).

Quantization happens ONCE at engine build (``ServeEngine(
weights_dtype=...)``), host-side, AFTER adapter setup — the LoRA
delta path stays full-precision on top (nn/layers.lora_delta computes
from activations and adds after the scaled dot, exactly where a
merged weight would land). Under tp the ``w_scale`` leaf shards
exactly like the out-dim of the weight it scales
(:func:`augment_weight_specs`: column-parallel scales shard with the
columns, row-parallel scales replicate), so zero new collectives and
ZERO new compiled programs per policy — the policy is baked into the
param tree before the first trace (ladder pinned in
analysis/specs.weight_layout_policies, compile bound unchanged).

The targeted nodes are the family's ``weight_targets``
(serve/families.py; gpt2: qkv/proj/fc, llama: q/k/v/o/gate/up/down;
pangu_ultra_moe: the latent attention's five projections, the dense
and shared SwiGLUs and the routed experts it holds, ``[held, in,
out]`` nodes — unscaled policies only there: the grouped matmul
applies no per-channel scale). Embeddings, logits head, norms, the
router and the capacity router's raw expert leaves (the tiny MoE
families) stay full-precision — they are either bandwidth-cheap per
token or precision-critical (the router-ordering lesson,
nn/layers.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import jax.numpy as jnp

from quintnet_tpu.serve.kv_quant import FLOAT8_DTYPE, LayoutPolicy


@dataclass(frozen=True)
class WeightLayoutPolicy(LayoutPolicy):
    """The weights face of :class:`LayoutPolicy`: per-output-channel
    absmax groups (axes = the in-features dim) instead of per-block
    KV groups. All quant math is inherited — one contract."""


_WEIGHT_POLICIES = {
    "f32": WeightLayoutPolicy("f32", jnp.float32, scaled=False),
    "bf16": WeightLayoutPolicy("bf16", jnp.bfloat16, scaled=False),
    "int8": WeightLayoutPolicy("int8", jnp.int8, scaled=True,
                               qmax=127.0),
    "fp8": WeightLayoutPolicy("fp8", FLOAT8_DTYPE, scaled=True,
                              qmax=448.0),
    "fake_quant": WeightLayoutPolicy("fake_quant", jnp.float32,
                                     scaled=True, qmax=0.0),
}


def weight_policy_names() -> Tuple[str, ...]:
    """The canonical weight-policy ladder (pinned in analysis/specs.py —
    compile counts are UNCHANGED per policy)."""
    return tuple(_WEIGHT_POLICIES)


def make_weight_policy(weights_dtype) -> WeightLayoutPolicy:
    """Resolve ``ServeEngine(weights_dtype=...)`` input to a policy: a
    policy passes through, a name looks up the ladder, a raw
    f32/bf16 dtype maps to its passthrough policy, None is f32 (the
    pre-policy engine, byte-identical)."""
    if weights_dtype is None:
        return _WEIGHT_POLICIES["f32"]
    if isinstance(weights_dtype, WeightLayoutPolicy):
        return weights_dtype
    if isinstance(weights_dtype, str):
        if weights_dtype not in _WEIGHT_POLICIES:
            raise ValueError(
                f"unknown weights_dtype {weights_dtype!r}; expected one "
                f"of {weight_policy_names()}")
        pol = _WEIGHT_POLICIES[weights_dtype]
        if pol.store_dtype is None:
            raise ValueError(
                f"weights_dtype {weights_dtype!r} needs "
                "jnp.float8_e4m3fn, which this jax build does not "
                "provide")
        return pol
    dt = jnp.dtype(weights_dtype)
    if dt == jnp.dtype(jnp.float32):
        return _WEIGHT_POLICIES["f32"]
    if dt == jnp.dtype(jnp.bfloat16):
        return _WEIGHT_POLICIES["bf16"]
    raise ValueError(
        f"no weight policy for dtype {dt}; use one of "
        f"{weight_policy_names()}")


# ---------------------------------------------------------------------
# tree surgery (host-side, once at engine build)
# ---------------------------------------------------------------------

def _node_at(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _with_node(tree, path, node):
    """Functional path-replace: shallow-copies dicts along ``path``
    only — untouched siblings keep their identity (and their device
    buffers)."""
    if not path:
        return node
    out = dict(tree)
    out[path[0]] = _with_node(tree[path[0]], path[1:], node)
    return out


def present_targets(params, targets) -> Tuple[Tuple[str, ...], ...]:
    """Filter a family's ``weight_targets`` to the paths that actually
    exist in THIS param tree — an MoE block swaps ``mlp`` for ``moe``
    (the capacity router's raw expert leaves stay full-precision), so
    the dense-mlp targets simply drop out instead of KeyError-ing."""
    out = []
    for path in targets:
        node = params["blocks"]
        for k in path:
            if not isinstance(node, dict) or k not in node:
                node = None
                break
            node = node[k]
        if isinstance(node, dict) and "w" in node:
            out.append(path)
    return tuple(out)


def _quantize_node(node, policy):
    """One targeted linear node {w: [L, in, out](, b)} -> its packed
    form: ``w`` narrowed to the store dtype, plus a per-output-channel
    ``w_scale`` [L, out] f32 leaf when scaled. Bias (and any LoRA
    machinery outside the tree) stays full-precision."""
    w = node["w"]
    out = dict(node)
    if policy.scaled:
        scale = policy.compute_scale(w, axes=(-2,))        # [L, out]
        out["w"] = policy.quant(w, jnp.expand_dims(scale, -2))
        out["w_scale"] = scale
    else:
        out["w"] = w.astype(policy.store_dtype)
    return out


def quantize_params(params, targets, policy: WeightLayoutPolicy):
    """Pack every ``targets`` path under ``params["blocks"]`` per the
    policy. The f32 policy returns ``params`` UNCHANGED (same object:
    the byte-identical pre-policy engine); every other policy replaces
    only the targeted nodes."""
    if policy.name == "f32":
        return params
    blocks = params["blocks"]
    for path in targets:
        node = _node_at(blocks, path)
        blocks = _with_node(blocks, path, _quantize_node(node, policy))
    return {**params, "blocks": blocks}


def weight_bytes(params, targets) -> int:
    """Device bytes of the TARGETED weight nodes (packed ``w`` +
    ``w_scale`` where present) — the number the int8 A/B gate ratios
    (>= 3.5x vs f32 on the same targets; whole-tree bytes would be
    embedding-diluted on tiny configs)."""
    total = 0
    blocks = params["blocks"]
    for path in targets:
        node = _node_at(blocks, path)
        total += int(node["w"].size) * jnp.dtype(node["w"].dtype).itemsize
        if "w_scale" in node:
            total += (int(node["w_scale"].size)
                      * jnp.dtype(node["w_scale"].dtype).itemsize)
    return int(total)


def augment_weight_specs(specs, targets):
    """Mirror :func:`quantize_params`'s tree surgery on a partition-spec
    tree: each targeted node gains a ``w_scale`` spec sharded exactly
    like the OUT dim of its weight — ``P(lead, out)`` from the weight's
    ``P(lead, in, out)``. Column-parallel scales shard with their
    columns; row-parallel scales replicate (their psum-side out dim is
    unsharded). Call only when the policy is scaled (the spec tree must
    match the param tree leaf-for-leaf under shard_map)."""
    from jax.sharding import PartitionSpec as P

    blocks = specs["blocks"]
    for path in targets:
        node = _node_at(blocks, path)
        w = tuple(node["w"])
        w = w + (None,) * (3 - len(w))
        blocks = _with_node(blocks, path, {**node,
                                           "w_scale": P(w[0], w[2])})
    return {**specs, "blocks": blocks}
