"""Wall time of the window over the optimizer steps completed in it,
ms (host clock between two drained points, profiler off)."""


def read(ctx):
    if "tokens_per_step" not in ctx or not ctx.get("steps"):
        return None
    return 1e3 * ctx["window_s"] / ctx["steps"]
