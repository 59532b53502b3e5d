"""Model FLOP/s utilization of a serving window of a family of
sliding-window and global layers with a mixture of experts, %: the
model's FLOPs (lib/window_moe_bytes.flops_per_token) for EVERY token
prefilled and decoded in the window — a decoded token scores its whole
context on the global layers and at most the window on the sliding ones
(the ring's ``global_rows`` and ``window_rows``, rows x layers, over
each kind's layers), a prefilled one half its step's mean call (window-
limited on the sliding layers), the routed experts' term from the
ring's COUNTED routings, the head where logits are read (every decoded
token, once a prefill call) — over the window times the chip's
published bf16 peak (lib/peaks.py). The share of the WHOLE step: host
time, idle gaps, pad columns and the program's own extra products all
lower it. Host clock and program counters; no trace. None where the
run's model has no ``sliding_window`` or the ring no ``window_rows``
(every other family; ``serve_mfu_pct`` reads the latent family's)."""

from benchmarks.lib.peaks import peak
from benchmarks.lib.step_ring import window_records
from benchmarks.lib.window_moe_bytes import (flops_per_token, layer_shapes,
                                             windowed_context)


def read(ctx):
    model, records = ctx.get("model"), window_records(ctx)
    if (not model or "sliding_window" not in model or not records
            or not ctx.get("window_s")):
        return None
    s = layer_shapes(model)
    n_full, n_sliding = len(s["full"]), len(s["sliding"])
    flops = 0.0
    for r in records:
        a = r.get("attrs", {})
        if "expert_rows" not in a or "window_rows" not in a:
            continue
        dec, pre = r["decode_tokens"], r["prefill_tokens"]
        if dec:
            # the rows are counted before the step's own token: + 1
            flops += dec * flops_per_token(
                model, context=a["global_rows"] / n_full / dec + 1.0,
                window_context=min(
                    a["window_rows"] / max(n_sliding, 1) / dec + 1.0,
                    model["sliding_window"]),
                routings=a["decode_expert_rows"] / dec, head=1.0)
        if pre:
            calls = max(r["prefill_chunks"], r["admitted"], 1)
            flops += pre * flops_per_token(
                model, context=pre / calls / 2.0,
                window_context=windowed_context(
                    pre / calls, model["sliding_window"]),
                routings=(a["expert_rows"] - a["decode_expert_rows"]) / pre,
                head=calls / pre)
    if not flops:
        return None
    return 100.0 * flops / (ctx["window_s"] * peak(ctx["device_kind"],
                                                   "bf16_flops"))
