"""Share of its HBM roofline the decode program of a RECURRENT family
reaches, %: the least time one decode step could take — its bytes
(lib/hybrid_bytes.py: the parameters once, the attention layers' cache
of every position the decoding rows hold, and each decoding row's
recurrent state read and written; means over the traced steps that
decoded) over the chip's published HBM bandwidth (lib/peaks.py) — over
``decode_device_ms``. Bytes-bound: at 64 rows a step the matmuls need a
tenth of the time the bytes do. The count is a floor; over 100% would
mean the bytes are counted too high, never a fast program. None where
the engine keeps no recurrent state (``decode_hbm_roofline_pct`` reads
those cells)."""

from benchmarks.lib.hybrid_bytes import decode_step_bytes
from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import (program_seconds, ring_static,
                                      traced_records)


def read(ctx):
    count, seconds = program_seconds(ctx, "jit_serve_decode")
    records = traced_records(ctx)
    facts = [ring_static(k) for k in ("param_bytes", "kv_bytes_per_token",
                                      "state_bytes_per_slot")]
    if not count or not records or not all(facts):
        return None
    decoded = [r for r in records if r["decoding"]]
    if not decoded:
        return None
    n = len(decoded)
    least_s = decode_step_bytes(
        facts[0], sum(r["context_tokens"] for r in decoded) / n, facts[1],
        sum(r["decoding"] for r in decoded) / n, facts[2])["total"] / peak(
            ctx["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (seconds / count)
