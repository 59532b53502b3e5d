"""Share of its HBM roofline the decode program of a family of
sliding-window and global layers with a mixture of experts reaches, %:
the least time one decode step could take — its bytes
(lib/window_moe_bytes.decode_step_bytes: the non-expert parameters
once, less the token table it only gathers rows of; the routed experts
that RECEIVED a row, by the ring's counter ``decode_experts_touched``;
the k and v rows the decoding rows attend, ``global_rows`` +
``window_rows`` in rows x layers at the ring's bytes a row; means over
the traced steps that decoded) over the chip's published HBM bandwidth
(lib/peaks.py) — over ``decode_device_ms``. Bytes-bound: at 32 rows
the step's matmuls need a fortieth of the time its weights do. The
count is a floor (the program gathers the table's full width and the
idle slots' rings too); over 100% would mean the bytes are counted too
high, never a fast program. None where the run's model has no
``sliding_window`` or the engine's ring no ``window_rows`` (every other
family, and the parent of the PR that added the counters)."""

from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import (program_seconds, ring_static,
                                      traced_records)
from benchmarks.lib.window_moe_bytes import decode_step_bytes, layer_shapes


def read(ctx):
    model = ctx.get("model")
    count, seconds = program_seconds(ctx, "jit_serve_decode")
    records = traced_records(ctx)
    facts = [ring_static(k) for k in ("param_bytes", "expert_param_bytes",
                                      "kv_bytes_per_token")]
    if (not model or "sliding_window" not in model or not count
            or not records or not all(facts)):
        return None
    decoded = [r for r in records if r["decoding"]
               and "window_rows" in r.get("attrs", {})
               and "decode_experts_touched" in r["attrs"]]
    if not decoded:
        return None
    n = len(decoded)

    def mean(key):
        return sum(r["attrs"][key] for r in decoded) / n

    least_s = decode_step_bytes(
        model, facts[0], facts[1], mean("decode_experts_touched"),
        mean("global_rows"), mean("window_rows"),
        facts[2] / len(layer_shapes(model)["full"]),
        sum(r["decoding"] for r in decoded) / n)["total"] / peak(
            ctx["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (seconds / count)
