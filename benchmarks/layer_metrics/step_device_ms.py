"""Device busy time of the traced stretch (union of the XLA operation
intervals, mean over chips) over the steps completed in it, ms."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("traced_steps"):
        return None
    return 1e3 * trace["busy_s"] / ctx["traced_steps"]
