"""Model FLOP/s utilization of a training cell, % of the chip's
published bf16 peak: the benchmark's own FLOPs per token (lib/flops.py:
forward and backward, attention counted, recomputation not) times
tokens per second per chip, over lib/peaks.py's peak for the device
kind."""

from benchmarks.lib.flops import gpt2_train_flops_per_token
from benchmarks.lib.peaks import peak


def read(ctx):
    if "tokens_per_step" not in ctx or not ctx.get("steps"):
        return None
    c = ctx["config"]
    per_token = gpt2_train_flops_per_token(
        c["n_layer"], c["n_embd"], c["vocab_size"], ctx["seq_len"])
    tok_s_chip = (ctx["steps"] * ctx["tokens_per_step"]
                  / ctx["window_s"] / ctx["chips"])
    return 100.0 * per_token * tok_s_chip / peak(ctx["device_kind"],
                                                 "bf16_flops")
