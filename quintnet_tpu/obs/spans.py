"""Phases: one clock inside the program, from import to every step.

One mechanism, two vocabularies. The mechanism (:class:`Phases`) is a
stack of open phases and one mark on one clock: every clock reading
closes the running stretch — its time goes to the innermost open
phase — and opens the next, so phase times are EXCLUSIVE and nothing
between two readings is lost. A phase also opens a
``jax.profiler.TraceAnnotation`` — a no-op without a profiler session,
a host span on the profiler's own clock (the clock of the device
planes) with one.

- **A step** (:class:`StepPhases`, ``qn.serve.<phase>``): the engine
  opens ``with phases.phase("upload"): ...`` around each part of
  ``ServeEngine.step``; the exclusive times land in the step's record
  (``StepRecord.phases``, summing to its ``t1 - t0``), always, so the
  flight-recorder ring says where a step's host time went with no
  profiler at all, and with one an idle gap of the chip can be laid at
  the phase the host was in (``tools/trace_view.py --xplane``).
- **Start-up** (:class:`SetupPhases`, ``qn.setup.<phase>``): ``import``
  (this package's, JAX's included), ``build`` (an engine's or a
  trainer's construction) and ``warmup``, under which each program is a
  child named by the program's own name
  (``qn.setup.warmup/jit_serve_decode``) from its first call until that
  returns. The spans land on the process's start-up record
  (``obs/recorder.startup()``) on ``time.perf_counter``.

**One compile listener** (``jax.monitoring``, registered when this
module is first imported) charges what JAX traced, lowered and compiled
or loaded WHERE THE WORK HAPPENED: to the innermost ``qn.setup.*`` span
open on the thread (``attrs``: ``trace_s``, ``lower_s``,
``compile_or_load_s``, ``programs``, ``cache_hits``, ``cache_misses``);
else to the engine step open on the thread (the program's name in
``StepRecord.attrs["compiled"]``: the step that recompiled, and what);
else to the record's ``unattributed``. It runs only when JAX compiles
or loads: a step that does neither pays two thread-local stores.

This is the one module of ``obs/`` that imports jax (for the profiler's
annotations and the listener), so ``obs/__init__`` does not import it:
what ``import quintnet_tpu.obs`` pulls in stays jax-free
(tests/test_obs.py pins that). Only modules that already import jax
import this one.
"""

from __future__ import annotations

import functools
import re
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from quintnet_tpu.obs import recorder

# The vocabulary: where the host's time in ServeEngine.step can go.
#   schedule  deadline sweep, promotion feed, admission decisions and
#             block allocation, grow/preempt (scheduler + kv_pool)
#   prefill   host side of an admission's prefill (ids, bucket, table
#             row, adapter binding), less its upload/dispatch/wait
#   upload    jnp.asarray of a copy of the host arrays a program takes
#   dispatch  the call of the jitted program until it returns
#   wait      every blocking device-to-host read
#   commit    the per-slot walk: append, callbacks, retire, metrics
PHASES = ("schedule", "prefill", "upload", "dispatch", "wait", "commit")

SERVE_PREFIX = "qn.serve."
SERVE_STEP = "qn.serve.step"

# The vocabulary before the first step.
#   import   ``import quintnet_tpu`` (JAX's import is most of it)
#   build    ServeEngine.__init__, get_strategy, Trainer.__init__,
#            Strategy.init_opt_state
#   warmup   ServeEngine.warmup(), the first call of a train or eval
#            step; its children ``warmup/<program>`` are one program's
#            first call each: trace + lower + compile or load, and the
#            dispatch of a first run that nobody waits for
SETUP_PREFIX = "qn.setup."

# this thread's open SetupPhases (``.setup``) and engine step (``.step``)
_THREAD = threading.local()


class Phases:
    """The mechanism (see module docstring): a stack of open phases,
    one mark, one clock. A subclass says what goes on the stack for a
    name (``_enter``; None: nothing is open to charge, the phase is an
    annotation alone), where a stretch's seconds go (``_add``) and what
    closing does (``_exit``)."""

    prefix = ""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self._stack: List = []
        self._mark = 0.0

    def _charge(self) -> None:
        """Close the running stretch: its time goes to the innermost
        open phase. With nothing open nothing is charged and the clock
        is not read."""
        if self._stack:
            now = self.clock()
            self._add(self._stack[-1], now - self._mark)
            self._mark = now

    def _enter(self, name: str, attrs: Dict):
        raise NotImplementedError

    def _add(self, top, seconds: float) -> None:
        raise NotImplementedError

    def _exit(self, top) -> None:
        pass

    @contextmanager
    def phase(self, name: str, **attrs):
        self._charge()
        top = self._enter(name, attrs)
        if top is not None:
            self._stack.append(top)
        try:
            with jax.profiler.TraceAnnotation(self.prefix + name):
                yield top
        finally:
            if top is not None:
                self._charge()
                self._stack.pop()
                self._exit(top)


class StepPhases(Phases):
    """Exclusive phase times and counters of the engine step in
    progress (see module docstring). One per engine; ``begin`` resets
    it, ``end`` hands the totals to the step's record."""

    prefix = SERVE_PREFIX

    def __init__(self, clock: Callable[[], float]):
        super().__init__(clock)
        self.seconds: Dict[str, float] = {}
        self.host_syncs = 0
        self.h2d_bytes = 0
        # the programs JAX compiled or loaded while the step was open
        # (the listener below appends): empty on every step but one
        # that recompiled
        self.compiled: List[str] = []

    def begin(self) -> float:
        """Start a step; returns its ``t0``. The stretch until the
        first phase opens is charged to ``schedule``."""
        self.seconds = {}
        self.host_syncs = 0
        self.h2d_bytes = 0
        if self.compiled:
            self.compiled = []
        self._stack = ["schedule"]
        _THREAD.step = self
        self._mark = self.clock()
        return self._mark

    def _enter(self, name: str, attrs: Dict) -> Optional[str]:
        # outside a step (warmup, a direct call of an admission in a
        # test) nothing is open and nothing is charged
        return name if self._stack else None

    def _add(self, name: str, seconds: float) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds

    def end(self) -> float:
        """Close the step; returns its ``t1``."""
        self._charge()
        self._stack = []
        _THREAD.step = None
        return self._mark

    def upload(self, *arrays):
        """The host arrays as device arrays, counted in ``h2d_bytes``.
        Copies: on the CPU a device array may share a host array's
        memory, and the engine writes its host arrays again before the
        step's one read, while the programs may still be reading."""
        with self.phase("upload"):
            self.h2d_bytes += sum(a.nbytes for a in arrays)
            return tuple(jnp.asarray(np.array(a)) for a in arrays)

    def wait(self, reads: int):
        """The phase around ``reads`` blocking device-to-host reads,
        each one a ``host_sync``."""
        self.host_syncs += reads
        return self.phase("wait")


class SetupPhases(Phases):
    """The ``qn.setup.*`` spans one thread has open. What is on the
    stack is the span itself, the live dict of the start-up record
    (``obs/recorder.StartupRecord``): opened there at ``_enter``,
    charged its exclusive seconds in place, closed at ``_exit``."""

    prefix = SETUP_PREFIX

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        super().__init__(clock)

    def _enter(self, name: str, attrs: Dict) -> Dict:
        if not self._stack:
            self._mark = self.clock()
        return recorder.startup().open(
            self.prefix + name, self._mark, attrs=attrs,
            parent=self._stack[-1]["id"] if self._stack else None)

    def _add(self, span: Dict, seconds: float) -> None:
        span["exclusive_s"] += seconds

    def _exit(self, span: Dict) -> None:
        span["t1"] = self._mark


def _setup() -> SetupPhases:
    phases = getattr(_THREAD, "setup", None)
    if phases is None:
        phases = _THREAD.setup = SetupPhases()
    return phases


def setup_phase(name: str, **attrs):
    """``with setup_phase("build"): ...``: a ``qn.setup.<name>`` span
    on this thread, a child of the one open around it."""
    return _setup().phase(name, **attrs)


def setup_span(name: str):
    """Decorator: every call is a ``qn.setup.<name>`` span."""
    def wrap(fn):
        @functools.wraps(fn)
        def in_span(*args, **kwargs):
            with setup_phase(name):
                return fn(*args, **kwargs)
        return in_span
    return wrap


def program_name(fun_name: str) -> str:
    """``serve_decode`` and ``jit(serve_decode)`` -> ``jit_serve_decode``:
    the name a program runs under on a device trace's ``XLA Modules``
    line."""
    name = re.sub(r"\W+", "_", fun_name).strip("_")
    return name if name.startswith("jit_") else "jit_" + name


def warmup_program(fn_name: str):
    """The child span of one program's first call, under the
    ``qn.setup.warmup`` that is open around it."""
    return setup_phase("warmup/" + program_name(fn_name))


@contextmanager
def first_call(fn_name: str):
    """A program warmed up by its first real call (the train step, the
    eval step): a ``warmup`` of its own with the program as its one
    child."""
    with setup_phase("warmup"), warmup_program(fn_name) as span:
        yield span


def stamp_import(t0: float) -> None:
    """``qn.setup.import`` from ``t0`` to now: the package's first line
    read the clock, its last line calls this."""
    recorder.startup().open(SETUP_PREFIX + "import", t0,
                            t1=time.perf_counter())


# ---------------------------------------------------------------------
# the compile listener
# ---------------------------------------------------------------------
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_DURATIONS = {
    _TRACE: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _COMPILE: "compile_or_load_s",
    # a part of compile_or_load_s: reading the executable back
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}


def _charge_event(key: str, amount: float, program: str = "") -> None:
    """To the innermost open ``qn.setup.*`` span of this thread, else
    to its open engine step, else to ``unattributed``."""
    record = recorder.startup()
    setup = getattr(_THREAD, "setup", None)
    step = getattr(_THREAD, "step", None)
    if setup is not None and setup._stack:
        sink = setup._stack[-1]["attrs"]
    elif step is not None and step._stack:
        sink = None
        if program:
            step.compiled.append(program)
    else:
        sink = record.unattributed
    record.charge(key, amount, sink)


def _on_trace_start(event: str, _value: float, **_kw) -> None:
    # a jitted function traced inside another's trace (jnp's own are
    # jitted) reports its own duration before the outer one does: only
    # the outermost is counted, it holds the others
    if event == _TRACE:
        _THREAD.tracing = getattr(_THREAD, "tracing", 0) + 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    key = _DURATIONS.get(event)
    if key is None:
        return
    if event == _TRACE:
        _THREAD.tracing = depth = max(getattr(_THREAD, "tracing", 1) - 1, 0)
        if depth:
            return
    _charge_event(key, seconds)
    if event == _COMPILE:
        _charge_event("programs", 1, program_name(kw.get("fun_name", "")))


def _on_event(event: str, **_kw) -> None:
    key = _COUNTS.get(event)
    if key is not None:
        _charge_event(key, 1)


jax.monitoring.register_scalar_listener(_on_trace_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
