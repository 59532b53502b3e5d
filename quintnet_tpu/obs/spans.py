"""Phases of an engine step: one clock inside the program, two readers.

The engine opens ``with phases.phase("upload"): ...`` around each part
of ``ServeEngine.step``. That does two things at once:

- it opens a ``jax.profiler.TraceAnnotation("qn.serve.<phase>")`` — a
  no-op without a profiler session, a host span on the profiler's own
  clock (the clock of the device planes) with one, so an idle gap of
  the chip can be laid at the phase the host was in
  (``tools/trace_view.py --xplane``);
- it charges the elapsed time of the engine's injectable clock to the
  phase in the step's record (``StepRecord.phases``), always, so the
  flight-recorder ring says where a step's host time went with no
  profiler at all.

Phase times are EXCLUSIVE: while an inner phase is open the outer one
is not charged, so a step's phases sum to its ``t1 - t0``. Every clock
reading closes one stretch and opens the next; nothing between two
readings is lost.

This is the one module of ``obs/`` that imports jax (for the profiler's
annotations), so ``obs/__init__`` does not import it: what
``import quintnet_tpu.obs`` pulls in stays jax-free (tests/test_obs.py
pins that). Only modules that already import jax import this one.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

# The vocabulary: where the host's time in ServeEngine.step can go.
#   schedule  deadline sweep, promotion feed, admission decisions and
#             block allocation, grow/preempt (scheduler + kv_pool)
#   prefill   host side of an admission's prefill (ids, bucket, table
#             row, adapter binding), less its upload/dispatch/wait
#   upload    jnp.asarray of the host arrays a program takes
#   dispatch  the call of the jitted program until it returns
#   wait      every blocking device-to-host read
#   commit    the per-slot walk: append, callbacks, retire, metrics
PHASES = ("schedule", "prefill", "upload", "dispatch", "wait", "commit")

SERVE_PREFIX = "qn.serve."
SERVE_STEP = "qn.serve.step"


class StepPhases:
    """Exclusive phase times and counters of the engine step in
    progress (see module docstring). One per engine; ``begin`` resets
    it, ``end`` hands the totals to the step's record."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.seconds: Dict[str, float] = {}
        self.host_syncs = 0
        self.h2d_bytes = 0
        self._stack: List[str] = []
        self._mark = 0.0

    def begin(self) -> float:
        """Start a step; returns its ``t0``. The stretch until the
        first phase opens is charged to ``schedule``."""
        self.seconds = {}
        self.host_syncs = 0
        self.h2d_bytes = 0
        self._stack = ["schedule"]
        self._mark = self.clock()
        return self._mark

    def _charge(self) -> None:
        """Close the running stretch: its time goes to the innermost
        open phase. Outside a step (warmup, a direct call of an
        admission in a test) nothing is open and nothing is charged."""
        if self._stack:
            now = self.clock()
            name = self._stack[-1]
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + now - self._mark)
            self._mark = now

    def end(self) -> float:
        """Close the step; returns its ``t1``."""
        self._charge()
        self._stack = []
        return self._mark

    @contextmanager
    def phase(self, name: str):
        self._charge()
        in_step = bool(self._stack)
        if in_step:
            self._stack.append(name)
        try:
            with jax.profiler.TraceAnnotation(SERVE_PREFIX + name):
                yield
        finally:
            if in_step:
                self._charge()
                self._stack.pop()

    def upload(self, *arrays):
        """The host arrays as device arrays, counted in ``h2d_bytes``."""
        with self.phase("upload"):
            self.h2d_bytes += sum(a.nbytes for a in arrays)
            return tuple(jnp.asarray(a) for a in arrays)

    def wait(self, reads: int):
        """The phase around ``reads`` blocking device-to-host reads,
        each one a ``host_sync``."""
        self.host_syncs += reads
        return self.phase("wait")
