"""Model FLOP/s utilization of a serving window of a linear-attention +
latent-attention mixture-of-experts family, %: the model's FLOPs
(lib/kda_moe_bytes.flops_per_token) for EVERY token prefilled and
decoded in the window — a decoded token runs the delta rule's one-token
recurrence and scores its whole context in the latent layers (the
ring's ``context_tokens``), a prefilled one the chunked form at
``nn/kda.CHUNK`` and half its step's mean call, the routed experts'
term from the ring's COUNTED routings on experts held here, the head
where logits are read (every decoded token, once a prefill call) — over
the window times the chip's published bf16 peak (lib/peaks.py). The
share of the WHOLE step: host time, idle gaps, pad columns and the
program's own extra products all lower it, and a decode-heavy window is
bound by bytes, so it reads low by nature. Host clock and program
counters; no trace. None where the run's model has no KDA layers or the
ring has no expert counters (every other family)."""

from benchmarks.lib.kda_moe_bytes import flops_per_token
from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import window_records


def read(ctx):
    model, records = ctx.get("model"), window_records(ctx)
    if (not model or "kda_lower_bound" not in model or not records
            or not ctx.get("window_s")):
        return None
    from quintnet_tpu.nn.kda import CHUNK

    flops = 0.0
    for r in records:
        a = r.get("attrs", {})
        if "expert_rows" not in a:
            continue
        dec, pre = r["decode_tokens"], r["prefill_tokens"]
        if dec:
            flops += dec * flops_per_token(
                model, context=r["context_tokens"] / dec + 1.0,
                held_routings=a["decode_expert_rows"] / dec, head=1.0)
        if pre:
            calls = max(r["prefill_chunks"], r["admitted"], 1)
            flops += pre * flops_per_token(
                model, context=pre / calls / 2.0,
                held_routings=(a["expert_rows"]
                               - a["decode_expert_rows"]) / pre,
                head=calls / pre, chunk=CHUNK)
    if not flops:
        return None
    return 100.0 * flops / (ctx["window_s"] * peak(ctx["device_kind"],
                                                   "bf16_flops"))
