"""Seconds of warm-up: the program's ``qn.setup.warmup`` spans, their
children (one a program, from its first call until that returns)
included. ``ServeEngine.warmup()``, or a trainer's first step. One
reader, an entry a kind of cell (``.serve``, ``.train``)."""

from benchmarks.lib.startup import exclusive_seconds


def read(ctx):
    return exclusive_seconds("warmup", children=True)
