"""How late the benchmark's own generator ran: 95th percentile of
submit time minus due time over the sampled requests, ms. A starved
generator must not be read as a fast server."""

from benchmarks.lib.stats import percentile


def read(ctx):
    late = (ctx.get("latencies") or {}).get("late")
    if not late:
        return None
    return 1e3 * percentile(late, 95)
