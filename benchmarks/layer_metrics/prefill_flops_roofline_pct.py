"""Share of the matrix unit's peak the prefill programs of a RECURRENT
family reach, %: the FLOPs of the prompt tokens prefilled in the traced
stretch (the ring's ``prefill_tokens`` x lib/hybrid_bytes.
prefill_flops_per_token at the stretch's mean prefill length: 2 x the
block matmul parameters, the chunked scan's and the attention layers'
terms) over the chip's published bf16 peak (lib/peaks.py) over the
device seconds of ``jit_serve_prefill_*``. Compute-bound: the chunked
scan is matmuls. Pad columns of a bucket are not counted, so short
prompts in wide buckets read low; over 100% would mean the FLOPs are
counted too high. None where the run's context carries no model
configuration with a layer pattern, or no prefill ran in the stretch."""

from benchmarks.lib.hybrid_bytes import prefill_flops_per_token
from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import program_seconds, traced_records


def read(ctx):
    model = ctx.get("model")
    count, seconds = program_seconds(ctx, "jit_serve_prefill_")
    records = traced_records(ctx)
    if (not model or "layer_types" not in model or not count
            or not records):
        return None
    tokens = sum(r["prefill_tokens"] for r in records)
    if not tokens:
        return None
    flops = tokens * prefill_flops_per_token(model, tokens / count)
    return 100.0 * flops / peak(ctx["device_kind"], "bf16_flops") / seconds
