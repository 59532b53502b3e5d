"""Share of its HBM roofline the decode program of a LATENT-attention
mixture-of-experts family reaches, %: the least time one decode step
could take — its bytes (lib/moe_mla_bytes.decode_step_bytes: the
non-expert parameters once, less the token table it only gathers rows
of; the held routed experts that RECEIVED a row, by the ring's counter
``decode_experts_touched``; the latent rows of every position the
decoding rows hold, at the ring's ``kv_bytes_per_token``; means over
the traced steps that decoded) over the chip's published HBM bandwidth
(lib/peaks.py) — over ``decode_device_ms``. Bytes-bound: at 64 rows
the step's matmuls need a twentieth of the time its weights do. The
count is a floor; over 100% would mean the bytes are counted too high,
never a fast program. None where the engine's ring carries no
``expert_param_bytes`` or no such counter (every other family)."""

from benchmarks.lib.moe_mla_bytes import decode_step_bytes
from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import (program_seconds, ring_static,
                                      traced_records)


def read(ctx):
    model = ctx.get("model")
    count, seconds = program_seconds(ctx, "jit_serve_decode")
    records = traced_records(ctx)
    facts = [ring_static(k) for k in ("param_bytes", "expert_param_bytes",
                                      "kv_bytes_per_token")]
    if (not model or "kv_lora_rank" not in model or not count
            or not records or not all(facts)):
        return None
    decoded = [r for r in records if r["decoding"]
               and "decode_experts_touched" in r.get("attrs", {})]
    if not decoded:
        return None
    n = len(decoded)
    least_s = decode_step_bytes(
        model, facts[0], facts[1],
        sum(r["attrs"]["decode_experts_touched"] for r in decoded) / n,
        sum(r["context_tokens"] for r in decoded) / n, facts[2],
        sum(r["decoding"] for r in decoded) / n)["total"] / peak(
            ctx["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (seconds / count)
