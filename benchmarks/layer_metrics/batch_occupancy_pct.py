"""Mean over the window's engine steps of the requests holding a slot
after the step (``ServeMetrics.running``) over ``max_slots``, %."""


def read(ctx):
    steps = ctx.get("engine_steps")
    if not steps:
        return None
    mean = sum(running for _s, _e, running in steps) / len(steps)
    return 100.0 * mean / ctx["max_slots"]
