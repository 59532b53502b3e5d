"""Median wall time of ``engine.step()``, ms: the benchmark's span
around the call, profiler off. Less the device time per step
(step_device_ms) it is the host's share of a step."""

import statistics


def read(ctx):
    steps = ctx.get("engine_steps")
    if not steps:
        return None
    return 1e3 * statistics.median(e - s for s, e, _running in steps)
