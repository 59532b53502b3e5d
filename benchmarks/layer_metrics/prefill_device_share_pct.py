"""Share of the engine programs' device time that went to prefill, %:
seconds of ``jit_serve_prefill_*`` over seconds of all ``jit_serve_*``
on the traced stretch's ``XLA Modules`` line (chip 0)."""

from benchmarks.lib.step_ring import program_seconds


def read(ctx):
    _n, total = program_seconds(ctx, "jit_serve_")
    _n, prefill = program_seconds(ctx, "jit_serve_prefill_")
    return 100.0 * prefill / total if total else None
