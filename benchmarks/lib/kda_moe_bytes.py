"""Bytes a decode step and FLOPs a token of a linear-attention (KDA) +
latent-attention (MLA) mixture-of-experts model (Ling 3.0,
``bailing_hybrid``) have to move and do, from the configuration's
shapes: the numerators of ``decode_kda_hbm_roofline_pct``,
``prefill_kda_flops_roofline_pct`` and ``serve_kda_moe_mfu_pct``.

One decode step reads every NON-EXPERT parameter once (the batch shares
them) except the token table, of which it gathers one row a decoding
row; the routed experts HELD here that received at least one row (an
expert nobody was routed to is not read); for each decoding row its
recurrent state ONCE EACH WAY (read, written: 2 x the slot's bytes over
the KDA layers); and the latent row ``[c | k_rope]`` of every position
it holds in the latent layers. Activations and the new latent row are
left out, and a program that passes over the state twice (one pass to
form ``S'^T k``, one to write ``S`` and read ``S^T q``) reads it twice:
the count is a floor, and a share of the roofline computed from it can
only be too low.

A token's FLOPs are the model's, whichever form the program runs: two a
parameter of every matmul it passes through (the KDA layers' seven
projections, the latent layer's five, the dense SwiGLU or the shared
expert and the router, and the routed experts HELD HERE that it was
routed to — counted, not assumed), the head where its logits are read,
the latent layer's scores and values against its context at the
per-head widths the model states, the short conv, and the delta rule:

- one token a step (decode): ``S'^T k``, ``k u^T`` and ``S^T q``, 3 x
  ``dk x dv`` products a head;
- a chunk of ``C`` positions (prefill; ``C`` = ``nn/kda.CHUNK``, the
  chunk the code uses): per token and head the triangular halves of
  ``A_kk`` and ``A_qk`` (``C/2 x dk`` each), the forward substitution
  for ``[U_v | W_k]`` (``C/2 x (dv + dk)``), ``W_k S_0``, ``Q S_0`` and
  ``K^T U`` (``dk x dv`` each) and ``A_qk U`` (``C/2 x dv``).

The absorbed form's wider products, the rebuild of cached keys and
values in the materialized form, the masked halves of ``A``, the extra
passes of an f32 matmul at ``highest`` precision and pad columns of a
bucket are the program's, not the algorithm's, and are not counted.

``c`` is the configuration file's dict (the Hugging Face keys and the
share: ``num_experts`` held of ``num_experts_published``).
"""

from __future__ import annotations

from typing import Dict, Optional


def _shape(c: Dict) -> Dict[str, int]:
    d, h, dk = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    latent = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    layers, group = c["num_hidden_layers"], c["layer_group_size"]
    n_mla = layers // group
    n_dense = c["first_k_dense_replace"]
    return {
        "d": d, "h": h, "dk": dk, "qk": qk, "latent": latent,
        # q, k, v, decay, o; beta and gate (one column a head)
        "kda": 5 * d * h * dk + 2 * d * h,
        # conv weight, A_log, dt_bias, the per-head norm's scale
        "kda_other": (c["short_conv_kernel_size"] * 3 * h * dk + h
                      + h * dk + dk),
        "mla": (d * h * qk + d * latent + c["kv_lora_rank"] * h * (
            c["qk_nope_head_dim"] + c["v_head_dim"]) + d * h
            + h * c["v_head_dim"] * d),
        "mla_other": c["kv_lora_rank"],
        "expert": 3 * d * c["moe_intermediate_size"],
        "shared": 3 * d * c["num_shared_experts"]
        * c["moe_shared_expert_intermediate_size"],
        "dense_mlp": 3 * d * c["intermediate_size"],
        # the router's matrix and its selection bias
        "router": (d + 1) * c.get("num_experts_published",
                                  c["num_experts"]),
        "n_kda": layers - n_mla, "n_mla": n_mla, "n_dense": n_dense,
        "n_moe": layers - n_dense, "layers": layers}


def param_counts(c: Dict) -> Dict[str, int]:
    """Parameters by kind. ``experts``: the routed experts held;
    ``matmul``: every block matmul a weight policy packs, the held
    experts among them; ``other``: token table, head, norms, router,
    conv weights and the decay's vectors (kept f32). The benchmark's
    cut: 5 KDA mixers 263.0M, the MLA mixer 32.0M, 2 dense SwiGLU
    94.4M, 4 x (128 experts 755.0M + shared 5.9M + router 1.3M), table
    and head 201.2M: 3.64B."""
    s = _shape(c)
    experts = s["n_moe"] * c["num_experts"] * s["expert"]
    matmul = (s["n_kda"] * s["kda"] + s["n_mla"] * s["mla"]
              + s["n_dense"] * s["dense_mlp"] + s["n_moe"] * s["shared"]
              + experts)
    other = (2 * c["vocab_size"] * s["d"] + s["d"]
             + s["layers"] * 2 * s["d"] + s["n_kda"] * s["kda_other"]
             + s["n_mla"] * s["mla_other"] + s["n_moe"] * s["router"])
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
    """One latent row ``[c | k_rope]`` a LATENT layer: 1 x 576 x 2 B =
    1,152 B in bf16 at the benchmark's cut. The KDA layers cache
    nothing a position."""
    s = _shape(c)
    return s["n_mla"] * s["latent"] * itemsize


def state_bytes_per_slot(c: Dict, conv_itemsize: int) -> int:
    """What one slot keeps over the KDA layers: the f32 state ``[H, dk,
    dv]`` and the conv tail's ``d_conv - 1`` rows of the 3 x H x dk
    channels. 5 x (2,097,152 + 73,728) B = 10.85 MB at the benchmark's
    cut with a bf16 tail."""
    s = _shape(c)
    return s["n_kda"] * (
        s["h"] * s["dk"] * s["dk"] * 4
        + (c["short_conv_kernel_size"] - 1) * 3 * s["h"] * s["dk"]
        * conv_itemsize)


def decode_step_bytes(c: Dict, param_bytes: float,
                      expert_param_bytes: float, experts_touched: float,
                      context_tokens: float, kv_bytes_a_token: float,
                      decoding: float,
                      state_bytes_a_slot: float) -> Dict[str, float]:
    """Least bytes of one decode step, by term. ``experts_touched``:
    (layer, held expert) pairs that received a row, of ``MoE layers x
    experts held``."""
    s = _shape(c)
    slots = s["n_moe"] * c["num_experts"]
    terms = {
        "weights": float(param_bytes) - float(expert_param_bytes)
        - token_table_bytes(c) + decoding * s["d"] * 4,
        "experts": float(expert_param_bytes) * experts_touched / slots,
        "state": 2.0 * state_bytes_a_slot * decoding,
        "latent": context_tokens * kv_bytes_a_token}
    terms["total"] = sum(terms.values())
    return terms


def delta_rule_products(c: Dict, chunk: Optional[int]) -> float:
    """Multiply-adds of the delta rule for ONE token in ONE KDA layer
    (all heads): the recurrence's (``chunk`` None) or the chunked
    form's at ``chunk`` positions a chunk (module docstring)."""
    s = _shape(c)
    dk = dv = s["dk"]
    if chunk is None:
        return s["h"] * 3.0 * dk * dv
    half = chunk / 2.0
    return s["h"] * (2 * half * dk + half * (dv + dk) + 3.0 * dk * dv
                     + half * dv)


def flops_per_token(c: Dict, *, context: float, held_routings: float,
                    head: float, chunk: Optional[int] = None) -> float:
    """FLOPs the model needs for ONE token: ``context`` the positions
    its latent-layer queries score (a decoded token: all it holds; a
    prefilled one: on average half its prompt), ``held_routings`` the
    routings to experts held here it had over all MoE layers (counted),
    ``head`` the share of a head's product it pays (1 where its logits
    are read), ``chunk`` the delta rule's form (None: one token a
    step)."""
    s = _shape(c)
    mla_layer = s["mla"] + s["h"] * (s["qk"] + c["v_head_dim"]) * context
    kda_layer = (s["kda"] + c["short_conv_kernel_size"] * 3 * s["h"]
                 * s["dk"] + delta_rule_products(c, chunk))
    return 2.0 * (
        s["n_kda"] * kda_layer + s["n_mla"] * mla_layer
        + s["n_dense"] * s["dense_mlp"]
        + s["n_moe"] * (s["shared"] + s["router"])
        + held_routings * s["expert"]
        + head * c["vocab_size"] * s["d"])
