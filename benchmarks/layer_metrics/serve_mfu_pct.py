"""Model FLOP/s utilization of a serving window, %: the model's FLOPs
(lib/moe_mla_bytes.flops_per_token) for EVERY token prefilled and
decoded in the window — a decoded token scores its whole context (the
ring's ``context_tokens``), a prefilled one half its step's mean call,
the routed experts' term from the ring's COUNTED routings on experts
held here, the head where logits are read (every decoded token, once a
prefill call) — over the window times the chip's published bf16 peak
(lib/peaks.py). The share of the WHOLE step a claim in such a cell is
bounded by: host time, idle gaps, pad columns and the program's own
extra products all lower it. Host clock and program counters; no
trace. None where the run's model has no latent attention or the ring
has no expert counters (every other family)."""

from benchmarks.lib.moe_mla_bytes import flops_per_token
from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import window_records


def read(ctx):
    model, records = ctx.get("model"), window_records(ctx)
    if (not model or "kv_lora_rank" not in model or not records
            or not ctx.get("window_s")):
        return None
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
                head=calls / pre)
    if not flops:
        return None
    return 100.0 * flops / (ctx["window_s"] * peak(ctx["device_kind"],
                                                   "bf16_flops"))
