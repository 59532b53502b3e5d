"""Seconds of construction: the exclusive time of the program's
``qn.setup.build`` spans (``ServeEngine.__init__``; ``get_strategy``,
``Trainer.__init__``, ``Strategy.init_opt_state``). One reader, an
entry a kind of cell (``.serve``, ``.train``)."""

from benchmarks.lib.startup import exclusive_seconds


def read(ctx):
    return exclusive_seconds("build")
