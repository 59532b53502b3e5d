"""Programs the persistent compile cache did not hold
(``cache_misses`` of every span of the program's start-up record and of
its ``unattributed``): 0 on a warm run; above it THIS process
compiled."""

from benchmarks.lib.startup import charged


def read(ctx):
    return charged("cache_misses")
