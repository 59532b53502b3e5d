"""Seconds of ``import quintnet_tpu``, JAX's import included: the
program's ``qn.setup.import`` span, from the package's first line to its
last. What of the driver's ``to_driver`` is the package's; the rest of
that part is the interpreter's start and the TPU's bring-up, which no
code of the repository runs."""

from benchmarks.lib.startup import exclusive_seconds


def read(ctx):
    return exclusive_seconds("import")
