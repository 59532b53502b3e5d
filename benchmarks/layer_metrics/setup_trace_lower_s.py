"""Seconds JAX spent tracing the process's programs in Python and
lowering them (``trace_s + lower_s`` of every span of the program's
start-up record and of its ``unattributed``): the Python-bound part of
start-up, which a warm compile cache cannot save and which every new
kernel adds to."""

from benchmarks.lib.startup import charged


def read(ctx):
    return charged("trace_s", "lower_s")
