"""The host's share of an engine step, ms: median over the window's
steps (profiler off) of the step's wall time less its ``wait`` phase —
the time the engine's own clock saw it blocked on a device-to-host
read. Both from the engine's flight-recorder ring."""

import statistics

from benchmarks.lib.step_ring import window_records


def read(ctx):
    records = window_records(ctx)
    if not records:
        return None
    return 1e3 * statistics.median(
        r["t1"] - r["t0"] - r["phases"].get("wait", 0.0) for r in records)
