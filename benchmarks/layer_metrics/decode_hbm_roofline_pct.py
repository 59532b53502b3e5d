"""Share of its HBM roofline the decode program reaches, %: the least
time one decode step could take — its bytes (lib/serve_bytes.py: the
parameters once plus the cache of every position the decoding rows
hold, mean over the traced steps that decoded) over the chip's
published HBM bandwidth (lib/peaks.py) — over ``decode_device_ms``.
Bytes-bound: at 12 rows a step the matmuls need a hundredth of the
time the bytes do. Over 100% would mean the bytes are counted too
high, never a fast program."""

from benchmarks.lib.peaks import peak
from benchmarks.lib.serve_bytes import decode_step_bytes
from benchmarks.lib.step_ring import (program_seconds, ring_static,
                                      traced_records)


def read(ctx):
    count, seconds = program_seconds(ctx, "jit_serve_decode")
    records = traced_records(ctx)
    param_bytes = ring_static("param_bytes")
    kv_bytes = ring_static("kv_bytes_per_token")
    if not count or not records or not param_bytes or not kv_bytes:
        return None
    decoded = [r["context_tokens"] for r in records if r["decoding"]]
    if not decoded:
        return None
    least_s = decode_step_bytes(
        param_bytes, sum(decoded) / len(decoded), kv_bytes) / peak(
            ctx["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (seconds / count)
