"""Device time of one execution of the engine's decode program, ms:
seconds over count of the ``jit_serve_decode*`` entries on the traced
stretch's ``XLA Modules`` line (chip 0). Unlike ``step_device_ms``, no
prefill is mixed in: the engine names its programs."""

from benchmarks.lib.step_ring import program_seconds


def read(ctx):
    count, seconds = program_seconds(ctx, "jit_serve_decode")
    return 1e3 * seconds / count if count else None
