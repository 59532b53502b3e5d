"""Share of the cached rows a decode step attends that are the
sliding-window layers', %: the ring's per-step counters ``window_rows``
(for each decoding row ``min(context, sliding_window)`` x sliding
layers) over ``window_rows + global_rows`` (context x global layers),
summed over the window's steps that decoded. Every row is the same
bytes, so this is the share of the step's least KV bytes the window
store serves: with every layer global it would be the sliding layers'
share of the layers (60% here); the smaller it reads, the more of the
cache traffic the window has taken away. Says how much of the cache
traffic the mechanism is, not how fast the step is. None where the
engine's ring has no such counters (every family without a window
store)."""

from benchmarks.lib.step_ring import window_records


def read(ctx):
    records = window_records(ctx)
    if not records:
        return None
    window = rows = 0.0
    for r in records:
        a = r.get("attrs", {})
        if r["decoding"] and "window_rows" in a:
            window += a["window_rows"]
            rows += a["window_rows"] + a["global_rows"]
    return 100.0 * window / rows if rows else None
