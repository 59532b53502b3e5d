"""Share of the traced stretch in which no operation ran on the
device, %: 1 minus the union of the ``XLA Ops`` intervals over the span
from the first operation's start to the last one's end."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
