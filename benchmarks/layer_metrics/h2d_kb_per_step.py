"""Bytes the engine uploads per step, kB (1e3 bytes): mean of the
ring's ``h2d_bytes`` (``nbytes`` of the host arrays handed to
``jnp.asarray``: tokens, positions, block tables, keys, and a prefill's
ids and scalars) over the window's steps."""

from benchmarks.lib.step_ring import window_records


def read(ctx):
    records = window_records(ctx)
    if not records:
        return None
    return sum(r["h2d_bytes"] for r in records) / len(records) / 1e3
