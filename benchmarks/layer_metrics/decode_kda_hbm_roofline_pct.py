"""Share of its HBM roofline the decode program of a linear-attention +
latent-attention mixture-of-experts family reaches, %: the least time
one decode step could take — its bytes (lib/kda_moe_bytes.
decode_step_bytes: the non-expert parameters once, less the token table
it only gathers rows of; the held routed experts that RECEIVED a row,
by the ring's counter ``decode_experts_touched``; each decoding row's
recurrent state once each way, 2 x the ring's ``state_bytes_per_slot``;
the latent layers' rows of every position the decoding rows hold, at
the ring's ``kv_bytes_per_token``; means over the traced steps that
decoded) over the chip's published HBM bandwidth (lib/peaks.py) — over
``decode_device_ms``. Bytes-bound: at 128 rows the step's matmuls need
a tenth of the time its bytes do. The count is a floor (a program that
passes over the state twice reads it twice); over 100% would mean the
bytes are counted too high, never a fast program. None where the run's
model has no KDA layers or the engine's ring carries no
``state_bytes_per_slot``, ``expert_param_bytes`` or expert counter
(every other family)."""

from benchmarks.lib.kda_moe_bytes import decode_step_bytes
from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import (program_seconds, ring_static,
                                      traced_records)


def read(ctx):
    model = ctx.get("model")
    count, seconds = program_seconds(ctx, "jit_serve_decode")
    records = traced_records(ctx)
    facts = [ring_static(k) for k in (
        "param_bytes", "expert_param_bytes", "kv_bytes_per_token",
        "state_bytes_per_slot")]
    if (not model or "kda_lower_bound" not in model or not count
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
        sum(r["decoding"] for r in decoded) / n, facts[3])["total"] / peak(
            ctx["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (seconds / count)
