"""Seconds JAX spent in backend compile-or-load
(``compile_or_load_s`` of every span of the program's start-up record
and of its ``unattributed``): loading executables where the compile
cache is warm, compiling them where it is not."""

from benchmarks.lib.startup import charged


def read(ctx):
    return charged("compile_or_load_s")
