"""Bytes a decode step and FLOPs a prefilled token of a Mamba-2 + GQA
hybrid (Granite 4.0-H) have to move and do, from the configuration's
shapes: the numerators of ``decode_state_hbm_roofline_pct``,
``state_share_of_decode_bytes_pct`` and ``prefill_flops_roofline_pct``.

One decode step reads every parameter once (the batch shares them),
for each decoding row the keys and values of every position it holds
IN THE ATTENTION LAYERS ONLY, and reads AND writes each decoding row's
recurrent state — a fixed size, whatever the row's length. Activations,
the conv's arithmetic and the one new key/value row are left out, so
the count is a floor: a share of the roofline computed from it can only
be too low.

The readers take ``param_bytes``, ``kv_bytes_per_token`` and
``state_bytes_per_slot`` from the engine's own ring
(``lib/step_ring.ring_static``); the shape formulas here are what the
benchmark's tests hold those numbers to, with the configuration file's
dict (the Hugging Face keys) as ``c``.
"""

from __future__ import annotations

from typing import Dict


def _dims(c: Dict) -> Dict[str, int]:
    d_inner = c["mamba_n_heads"] * c["mamba_d_head"]
    d_xbc = d_inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    n_attn = sum(k == "attention" for k in c["layer_types"])
    return {"d": c["hidden_size"], "d_inner": d_inner, "d_xbc": d_xbc,
            "d_in_proj": d_inner + d_xbc + c["mamba_n_heads"],
            "n_attn": n_attn, "n_mamba": len(c["layer_types"]) - n_attn,
            "hd": c["hidden_size"] // c["num_attention_heads"]}


def param_counts(c: Dict) -> Dict[str, int]:
    """Parameters by kind. ``matmul``: the block matmuls a weight
    policy packs (in_proj's three parts, out_proj, q, k, v, o, gate,
    up, down);
    ``other``: the token table (tied head), the norms, the conv and the
    per-head vectors. granite-4.0-h-micro: 2,984.7M + 207.0M = 3.19B."""
    m = _dims(c)
    d, ff = m["d"], c["shared_intermediate_size"]
    mlp = 3 * d * ff
    mamba_mm = d * m["d_in_proj"] + m["d_inner"] * d + mlp
    mamba_other = (c["mamba_d_conv"] * m["d_xbc"] + m["d_xbc"]
                   + 3 * c["mamba_n_heads"] + m["d_inner"] + 2 * d)
    kv = c["num_key_value_heads"] * m["hd"]
    attn_mm = 2 * d * d + 2 * d * kv + mlp
    matmul = m["n_mamba"] * mamba_mm + m["n_attn"] * attn_mm
    other = (m["n_mamba"] * mamba_other + m["n_attn"] * 2 * d
             + c["vocab_size"] * d + d)
    return {"matmul": matmul, "other": other, "total": matmul + other}


def param_bytes(c: Dict, *, weight_itemsize: int,
                other_itemsize: int = 4) -> int:
    n = param_counts(c)
    return n["matmul"] * weight_itemsize + n["other"] * other_itemsize


def kv_bytes_per_token(c: Dict, itemsize: int) -> int:
    """Keys and values of one position over the ATTENTION layers alone:
    2 x 4 x 8 x 64 x 2 B = 8,192 B in bf16."""
    m = _dims(c)
    return 2 * m["n_attn"] * c["num_key_value_heads"] * m["hd"] * itemsize


def state_bytes_per_slot(c: Dict, conv_itemsize: int) -> int:
    """One slot's recurrent state over the Mamba-2 layers: the f32 SSM
    state (heads x head size x state size) and the conv tail (kernel - 1
    rows of the conv's channels). 36 x (2,097,152 + 26,112) B = 76.4 MB
    with a bf16 tail."""
    m = _dims(c)
    ssm = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * 4
    tail = (c["mamba_d_conv"] - 1) * m["d_xbc"] * conv_itemsize
    return m["n_mamba"] * (ssm + tail)


def decode_step_bytes(param_bytes: float, context_tokens: float,
                      kv_bytes_a_token: float, decoding: float,
                      state_bytes_a_slot: float) -> Dict[str, float]:
    """Least bytes of one decode step, by term: the parameters once,
    the attention layers' cache of every position the decoding rows
    hold, each decoding row's state read and written."""
    terms = {"params": float(param_bytes),
             "kv": context_tokens * kv_bytes_a_token,
             "state": 2.0 * decoding * state_bytes_a_slot}
    terms["total"] = sum(terms.values())
    return terms


def prefill_flops_per_token(c: Dict, mean_len: float) -> float:
    """FLOPs the algorithm needs for one prompt token of a prefill of
    ``mean_len`` tokens (pad columns of a bucket, the full-width gather
    of the paged view and the one head row a prefill computes are not
    the algorithm's and are not counted):

    - 2 x the block matmul parameters;
    - a Mamba-2 layer's chunked scan at chunk ``Q = min(chunk size,
      mean_len)``: the chunk's scores ``C B^T`` (2 Q N), the masked
      quadratic form against ``dt x`` (2 Q d_inner), what the chunk
      adds to the state and what the entering state adds to it
      (2 d_inner N each);
    - an attention layer's ``q k^T`` and ``p v`` against the causal
      half of the context (2 x 2 x heads x head size x mean_len / 2).
    """
    m = _dims(c)
    q = min(float(c["mamba_chunk_size"]), float(mean_len))
    n = c["mamba_d_state"]
    ssd = 2 * q * n + 2 * q * m["d_inner"] + 4 * m["d_inner"] * n
    attn = 2 * c["num_attention_heads"] * m["hd"] * float(mean_len)
    return (2.0 * param_counts(c)["matmul"] + m["n_mamba"] * ssd
            + m["n_attn"] * attn)
