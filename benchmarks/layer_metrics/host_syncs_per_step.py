"""Blocking device-to-host reads per engine step: mean of the ring's
``host_syncs`` over the window's steps. Two on a pure decode step (the
sampled tokens and the keys), two more per admission."""

from benchmarks.lib.step_ring import window_records


def read(ctx):
    records = window_records(ctx)
    if not records:
        return None
    return sum(r["host_syncs"] for r in records) / len(records)
