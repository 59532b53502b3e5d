"""qtcheck: static analysis for QuintNet-TPU's compiled programs.

Four passes, one CI gate (``python -m quintnet_tpu.tools.qtcheck``):

- :mod:`~quintnet_tpu.analysis.jaxpr_audit` — lower any jitted function
  and walk its jaxpr: per-axis collective census, dtype-promotion
  report, buffer-donation report;
- :mod:`~quintnet_tpu.analysis.recompile` — count lowerings by abstract
  signature; enforce "exactly N compiled programs" (the serve engine's
  one-prefill-one-decode promise, the trainer's one-step promise);
- :mod:`~quintnet_tpu.analysis.lint` — AST rules for JAX footguns
  (host numpy / Python RNG in traced code, tracer branching, step-loop
  host syncs, array defaults, unsynced wall-clock timing) with a
  committed baseline (tools/qtcheck_baseline.json);
- :mod:`~quintnet_tpu.analysis.threads` — AST concurrency rules for the
  serving fleet (lock-order cycles, guarded-by inference, thread-spawn
  census vs the declarative spec) with its own committed baseline
  (tools/qtcheck_threads_baseline.json). Its runtime twin,
  :mod:`~quintnet_tpu.analysis.lockrt`, wraps ``threading`` locks with
  order/hold/contention instrumentation behind the fleets'
  ``lock_audit=`` flag.

Expected-census specs for the shipped programs (and the thread-spawn
spec) live in :mod:`~quintnet_tpu.analysis.specs`; tests/test_qtcheck.py
and tests/test_qtcheck_threads.py pin them.
"""

from quintnet_tpu.analysis.jaxpr_audit import (
    Census,
    collective_census,
    donation_report,
    dtype_report,
    gathered_view_gathers,
    pool_scan_operands,
    row_walk_calls,
    store_reads,
    view_head_splits,
    widened_view_dots,
)
from quintnet_tpu.analysis.lint import (
    RULES,
    Violation,
    collect_sources,
    compare_baseline,
    lint_parsed,
    lint_paths,
    lint_source,
    load_baseline,
    violations_to_baseline,
)
from quintnet_tpu.analysis.lockrt import (
    InstrumentedLock,
    LockAudit,
    LockOrderError,
)
from quintnet_tpu.analysis.recompile import (
    RecompileError,
    RecompileSentinel,
    abstract_signature,
    assert_compile_count,
    check_serving_compile_counts,
)
from quintnet_tpu.analysis.threads import (
    RULES as THREAD_RULES,
    THREAD_PATHS,
    audit_parsed,
    audit_paths,
    audit_sources,
    load_thread_specs,
    thread_spawn_census,
)

__all__ = [
    "Census",
    "collective_census",
    "donation_report",
    "dtype_report",
    "gathered_view_gathers",
    "pool_scan_operands",
    "row_walk_calls",
    "store_reads",
    "view_head_splits",
    "widened_view_dots",
    "RULES",
    "Violation",
    "collect_sources",
    "compare_baseline",
    "lint_parsed",
    "lint_paths",
    "lint_source",
    "load_baseline",
    "violations_to_baseline",
    "InstrumentedLock",
    "LockAudit",
    "LockOrderError",
    "RecompileError",
    "RecompileSentinel",
    "abstract_signature",
    "assert_compile_count",
    "check_serving_compile_counts",
    "THREAD_PATHS",
    "THREAD_RULES",
    "audit_parsed",
    "audit_paths",
    "audit_sources",
    "load_thread_specs",
    "thread_spawn_census",
]
