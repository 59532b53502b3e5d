"""Share of the matrix unit's peak the prefill programs of a linear-
attention + latent-attention mixture-of-experts family reach, %: the
FLOPs of the prompt tokens prefilled in the traced stretch (the ring's
``prefill_tokens`` x lib/kda_moe_bytes.flops_per_token — two a block
matmul parameter with the routed experts' term from the COUNTED
routings that landed on experts held here in prefill programs,
``expert_rows - decode_expert_rows``; the chunked delta rule's ``A``,
solve, output and state terms at the chunk the code uses,
``nn/kda.CHUNK``; the latent layer's scores against half the
stretch's mean prefill call, a floor under chunked prefill, whose later
chunks see the earlier ones; the head once a call) over the chip's
published bf16 peak (lib/peaks.py) over the device seconds of
``jit_serve_prefill_*``. Compute-bound. Pad columns of a bucket, the
masked halves of ``A``, the extra passes of the delta rule's f32
products and the rebuild of cached keys and values are not counted, so
short tails in wide buckets read low; over 100% would mean the FLOPs
are counted too high. None where the run's model has no KDA layers,
the ring no expert counters, or no prefill ran in the stretch."""

from benchmarks.lib.kda_moe_bytes import flops_per_token
from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import program_seconds, traced_records


def read(ctx):
    model = ctx.get("model")
    count, seconds = program_seconds(ctx, "jit_serve_prefill_")
    records = traced_records(ctx)
    if (not model or "kda_lower_bound" not in model or not count
            or not records):
        return None
    fed = [r for r in records if r["prefill_tokens"]
           and "expert_rows" in r.get("attrs", {})]
    tokens = sum(r["prefill_tokens"] for r in fed)
    if not tokens:
        return None
    from quintnet_tpu.nn.kda import CHUNK

    rows = sum(r["attrs"]["expert_rows"] - r["attrs"]["decode_expert_rows"]
               for r in fed)
    flops = tokens * flops_per_token(
        model, context=tokens / count / 2.0, held_routings=rows / tokens,
        head=count / tokens, chunk=CHUNK)
    return 100.0 * flops / peak(ctx["device_kind"], "bf16_flops") / seconds
