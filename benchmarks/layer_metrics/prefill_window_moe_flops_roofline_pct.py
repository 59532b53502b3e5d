"""Share of the matrix unit's peak the prefill programs of a family of
sliding-window and global layers with a mixture of experts reach, %:
the FLOPs of the prompt tokens prefilled in the traced stretch (the
ring's ``prefill_tokens`` x lib/window_moe_bytes.flops_per_token — the
routed experts' term from the COUNTED routings of prefill programs,
``expert_rows - decode_expert_rows``; the global layers' scores against
half the stretch's mean prefill call, the sliding layers' against the
window-limited mean of such a call — floors under chunked prefill,
whose later chunks see the earlier ones; the head once a call) over
the chip's published bf16 peak (lib/peaks.py) over the device seconds
of ``jit_serve_prefill_*``. Compute-bound. Pad columns of a bucket,
masked keys and the full-width gather are not counted, so short tails
in wide buckets read low; over 100% would mean the FLOPs are counted
too high. None where the run's model has no ``sliding_window``, the
ring no expert counters, or no prefill ran in the stretch."""

from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import program_seconds, traced_records
from benchmarks.lib.window_moe_bytes import flops_per_token, windowed_context


def read(ctx):
    model = ctx.get("model")
    count, seconds = program_seconds(ctx, "jit_serve_prefill_")
    records = traced_records(ctx)
    if (not model or "sliding_window" not in model or not count
            or not records):
        return None
    fed = [r for r in records if r["prefill_tokens"]
           and "expert_rows" in r.get("attrs", {})]
    tokens = sum(r["prefill_tokens"] for r in fed)
    if not tokens:
        return None
    rows = sum(r["attrs"]["expert_rows"] - r["attrs"]["decode_expert_rows"]
               for r in fed)
    call = tokens / count
    flops = tokens * flops_per_token(
        model, context=call / 2.0,
        window_context=windowed_context(call, model["sliding_window"]),
        routings=rows / tokens, head=count / tokens)
    return 100.0 * flops / peak(ctx["device_kind"], "bf16_flops") / seconds
