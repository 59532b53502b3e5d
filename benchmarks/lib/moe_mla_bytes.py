"""Bytes a decode step and FLOPs a token of a latent-attention (MLA)
mixture-of-experts model (openPangu-Ultra-MoE) have to move and do, from
the configuration's shapes: the numerators of
``decode_latent_hbm_roofline_pct``, ``prefill_moe_flops_roofline_pct``
and ``serve_mfu_pct``.

One decode step reads every NON-EXPERT parameter once (the batch shares
them) except the token table, of which it gathers one row a decoding
row; the routed experts HELD here that received at least one row (an
expert nobody was routed to is not read); and, for each decoding row,
the latent row ``[c | k_rope]`` of every position it holds, in every
layer. Activations and the new latent row are left out, so the count is
a floor: a share of the roofline computed from it can only be too low.

A token's FLOPs are the model's, whichever form the program runs: two a
parameter of every matmul it passes through (latent attention's five
projections, the dense SwiGLU or the shared expert and the router, and
the routed experts HELD HERE that it was routed to — counted, not
assumed: on average a sixteenth of its eight), the head where its
logits are read, and the scores and values of attention against its
context at the per-head widths the model states (``qk_nope + qk_rope``
a score, ``v_head_dim`` a value). The absorbed form's wider products,
the rebuild of cached keys and values in the materialized form, pad
columns of a bucket and the full-width gather are the program's, not
the algorithm's, and are not counted.

``c`` is the configuration file's dict (the Hugging Face keys and the
share: ``n_routed_experts`` held of ``n_routed_experts_published``).
"""

from __future__ import annotations

from typing import Dict


def _shape(c: Dict) -> Dict[str, int]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    latent = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    mla = (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk + d * latent
           + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                      + c["v_head_dim"])
           + h * c["v_head_dim"] * d)
    n_dense = c["first_k_dense_replace"]
    return {"d": d, "h": h, "qk": qk, "latent": latent, "mla": mla,
            "expert": 3 * d * c["moe_intermediate_size"],
            "dense_mlp": 3 * d * c["intermediate_size"],
            "router": d * c.get("n_routed_experts_published",
                                c["n_routed_experts"]),
            "norms": 4 * d + c["q_lora_rank"] + c["kv_lora_rank"],
            "n_dense": n_dense,
            "n_moe": c["num_hidden_layers"] - n_dense,
            "layers": c["num_hidden_layers"]}


def param_counts(c: Dict) -> Dict[str, int]:
    """Parameters by kind. ``experts``: the routed experts held;
    ``matmul``: every block matmul a weight policy packs, the held
    experts among them; ``other``: token table, head, norms and router
    (kept f32). The benchmark's cut: 621.3M the dense layer, 1,000.7M
    an MoE layer (MLA 196.6M + shared 47.2M + router 2.0M + 16 experts
    755.0M), 294.9M table and head: 4.92B."""
    s = _shape(c)
    experts = s["n_moe"] * c["n_routed_experts"] * s["expert"]
    matmul = (s["layers"] * s["mla"] + s["n_dense"] * s["dense_mlp"]
              + s["n_moe"] * c["n_shared_experts"] * s["expert"] + experts)
    other = (2 * c["vocab_size"] * s["d"] + s["d"]
             + s["layers"] * s["norms"] + s["n_moe"] * s["router"])
    return {"matmul": matmul, "experts": experts, "other": other,
            "total": matmul + other}


def param_bytes(c: Dict, *, weight_itemsize: int,
                other_itemsize: int = 4) -> int:
    n = param_counts(c)
    return n["matmul"] * weight_itemsize + n["other"] * other_itemsize


def expert_param_bytes(c: Dict, weight_itemsize: int) -> int:
    return param_counts(c)["experts"] * weight_itemsize


def token_table_bytes(c: Dict, itemsize: int = 4) -> int:
    return c["vocab_size"] * c["hidden_size"] * itemsize


def kv_bytes_per_token(c: Dict, itemsize: int) -> int:
    """One latent row ``[c | k_rope]`` a layer: 5 x 576 x 2 B = 5,760 B
    in bf16 (full heads would be 128 x (192 + 128) x 2 B = 81,920 B a
    layer)."""
    s = _shape(c)
    return s["layers"] * s["latent"] * itemsize


def decode_step_bytes(c: Dict, param_bytes: float,
                      expert_param_bytes: float, experts_touched: float,
                      context_tokens: float, kv_bytes_a_token: float,
                      decoding: float) -> Dict[str, float]:
    """Least bytes of one decode step, by term. ``experts_touched``:
    (layer, held expert) pairs that received a row, of ``MoE layers x
    experts held``."""
    s = _shape(c)
    slots = s["n_moe"] * c["n_routed_experts"]
    table = token_table_bytes(c)
    terms = {
        "weights": float(param_bytes) - float(expert_param_bytes) - table
        + decoding * s["d"] * 4,
        "experts": float(expert_param_bytes) * experts_touched / slots,
        "latent": context_tokens * kv_bytes_a_token}
    terms["total"] = sum(terms.values())
    return terms


def flops_per_token(c: Dict, *, context: float, held_routings: float,
                    head: float) -> float:
    """FLOPs the model needs for ONE token: ``context`` the positions
    its queries score (a decoded token: all it holds; a prefilled one:
    on average half its prompt), ``held_routings`` the
    routings to experts held here it had over all MoE layers (counted),
    ``head`` the share of a head's product it pays (1 where its logits
    are read: every decoded token, one token of a prefill call)."""
    s = _shape(c)
    # a score is qk products, a value v_head_dim, a head and a position
    per_layer = s["mla"] + s["h"] * (s["qk"] + c["v_head_dim"]) * context
    return 2.0 * (
        s["layers"] * per_layer + s["n_dense"] * s["dense_mlp"]
        + s["n_moe"] * (c["n_shared_experts"] * s["expert"] + s["router"])
        + held_routings * s["expert"]
        + head * c["vocab_size"] * s["d"])
