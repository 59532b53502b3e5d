"""Bytes a decode step and FLOPs a token of a model of sliding-window
and global attention layers with a mixture of experts (Laguna) have to
move and do, from the configuration's shapes: the numerators of
``decode_window_hbm_roofline_pct``, ``prefill_window_moe_flops_roofline_pct``
and ``serve_window_moe_mfu_pct``.

One decode step reads every NON-EXPERT parameter once (the batch shares
them) except the token table, of which it gathers one row a decoding
row; the routed experts that received at least one row (an expert
nobody was routed to is not read); and, for each decoding row, the k
and v rows it ATTENDS: every position it holds in each global layer,
at most ``sliding_window`` in each sliding layer — counted by the
engine in rows x layers (the ring's ``global_rows`` and
``window_rows``). Activations and the new rows are left out, and so is
everything the PROGRAM reads beyond what the model needs (a table's
full width, a ring's spare rows, idle slots' rings), so the count is a
floor: a share of the roofline computed from it can only be too low.

A token's FLOPs are the model's: two a parameter of every matmul it
passes through (q, k, v, o and the gate at its layer kind's head
count, the dense SwiGLU or the shared expert and the router, and the
routed experts it was ROUTED to — counted, not assumed), the head where
its logits are read, and the scores and values of attention against
its context, window-limited on the sliding layers. The lane-diagonal
form's extra products, pad columns of a bucket, masked keys and the
full-width gather are the program's, not the algorithm's, and are not
counted.

``c`` is the configuration file's dict (the Hugging Face keys; the
layers run are the first ``num_hidden_layers`` entries of its per-layer
lists).
"""

from __future__ import annotations

from typing import Dict


def layer_shapes(c: Dict) -> Dict:
    n = c["num_hidden_layers"]
    d, hd, hkv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    kinds = list(c["layer_types"])[:n]
    heads = list(c["num_attention_heads_per_layer"])[:n]
    mlps = list(c["mlp_layer_types"])[:n]
    # q, o and the gate at the layer's own head count; k, v at the kv's
    attn = [2 * d * h * hd + 2 * d * hkv * hd + d * h for h in heads]
    return {"d": d, "hd": hd, "hkv": hkv, "layers": n, "kinds": kinds,
            "heads": heads, "attn": attn,
            "full": [i for i, k in enumerate(kinds)
                     if k == "full_attention"],
            "sliding": [i for i, k in enumerate(kinds)
                        if k == "sliding_attention"],
            "n_dense": mlps.count("dense"), "n_sparse": mlps.count("sparse"),
            "expert": 3 * d * c["moe_intermediate_size"],
            "shared": 3 * d * c["shared_expert_intermediate_size"],
            "dense_mlp": 3 * d * c["intermediate_size"],
            "router": d * c["num_experts"], "norms": 2 * d}


def param_counts(c: Dict) -> Dict[str, int]:
    """Parameters by kind. ``experts``: the routed experts; ``matmul``:
    every block matmul a weight policy packs, the experts among them;
    ``other``: token table, head, norms and router (kept f32). The
    benchmark's cut: 79.8M layer 0 (full attention 29.46M + dense
    SwiGLU 50.33M), 846.3M a sliding sparse layer (attention 37.88M +
    shared 3.15M + 256 experts 805.3M; router 0.52M), 837.9M the full
    sparse layer, 411.0M table and head: 3.87B."""
    s = layer_shapes(c)
    experts = s["n_sparse"] * c["num_experts"] * s["expert"]
    matmul = (sum(s["attn"]) + s["n_dense"] * s["dense_mlp"]
              + s["n_sparse"] * s["shared"] + experts)
    other = (2 * c["vocab_size"] * s["d"] + s["d"]
             + s["layers"] * s["norms"] + s["n_sparse"] * s["router"])
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


def row_bytes(c: Dict, itemsize: int) -> int:
    """One position's k AND v rows of one layer: 8 x 128 x 2 x 2 B =
    4,096 B in bf16."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def kv_bytes_per_token(c: Dict, itemsize: int) -> int:
    """What a position costs for as long as the sequence lives: its
    rows in the GLOBAL layers (2 x 4,096 B here; all-global it would be
    5 x 4,096)."""
    return len(layer_shapes(c)["full"]) * row_bytes(c, itemsize)


def window_bytes_per_slot(c: Dict, itemsize: int, ring: int) -> int:
    """A sequence's fixed store in the SLIDING layers: ``ring`` rows a
    layer (528 x 4,096 B x 3 = 6.5 MB), whatever its length."""
    return len(layer_shapes(c)["sliding"]) * ring * row_bytes(c, itemsize)


def decode_step_bytes(c: Dict, param_bytes: float,
                      expert_param_bytes: float, experts_touched: float,
                      global_rows: float, window_rows: float,
                      row_bytes: float, decoding: float) -> Dict[str, float]:
    """Least bytes of one decode step, by term. ``experts_touched``:
    (layer, expert) pairs that received a row, of ``sparse layers x
    experts``; ``global_rows`` / ``window_rows``: positions attended x
    layers, by layer kind."""
    s = layer_shapes(c)
    table = token_table_bytes(c)
    terms = {
        "weights": float(param_bytes) - float(expert_param_bytes) - table
        + decoding * s["d"] * 4,
        "experts": float(expert_param_bytes) * experts_touched / (
            s["n_sparse"] * c["num_experts"]),
        "global_kv": global_rows * row_bytes,
        "window_kv": window_rows * row_bytes}
    terms["total"] = sum(terms.values())
    return terms


def windowed_context(call: float, window: int) -> float:
    """Mean number of keys a token of a ``call``-token run that starts
    at position 0 scores on a sliding layer: ``min(i + 1, window)``
    averaged over the run (a floor for a later chunk, whose tokens all
    find a full window)."""
    if call <= window:
        return (call + 1.0) / 2.0
    return (window * (window + 1.0) / 2.0 + (call - window) * window) / call


def flops_per_token(c: Dict, *, context: float, window_context: float,
                    routings: float, head: float) -> float:
    """FLOPs the model needs for ONE token: ``context`` the positions
    its queries score on a GLOBAL layer (a decoded token: all it holds;
    a prefilled one: on average half its call), ``window_context`` on a
    SLIDING one (never more than the window), ``routings`` the routings
    it had over all sparse layers (counted), ``head`` the share of a
    head's product it pays (1 where its logits are read: every decoded
    token, one token of a prefill call)."""
    s = layer_shapes(c)
    # a score is head_dim products and a value head_dim, a head and a key
    scores = sum(
        2 * s["heads"][i] * s["hd"]
        * (context if i in s["full"] else window_context)
        for i in range(s["layers"]))
    return 2.0 * (
        sum(s["attn"]) + scores + s["n_dense"] * s["dense_mlp"]
        + s["n_sparse"] * (s["shared"] + s["router"])
        + routings * s["expert"] + head * c["vocab_size"] * s["d"])
