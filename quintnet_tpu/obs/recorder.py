"""The engine-step flight recorder: a bounded ring of per-step records.

``ServeMetrics`` keeps monotone counters — totals that answer "how
much, overall". The :class:`StepRecorder` keeps the TIMELINE: one
:class:`StepRecord` per engine step with the phase mix (how many slots
decoded vs prefilled), batch occupancy, KV-pool pressure, the chunk
budget actually spent, speculation acceptance, and the step's wall
time via the engine's injectable clock. That is exactly the signal the
Sarathi/Orca literature argues scheduling decisions need: per-step
prefill/decode interference, not end-of-run aggregates.

The ring is bounded (``capacity`` steps; a long-running replica keeps
the freshest window and counts what scrolled off) and the records are
plain dict-able scalars, so:

- ``snapshot()`` feeds ``tools/trace_view.py``'s Chrome trace-event
  export (steps as thread slices in Perfetto);
- ``drain_new()`` ships increments over the process-fleet wire —
  replica children piggyback fresh records on their heartbeat frames,
  making the dispatcher's mirror the corpse's "last known" ring when a
  SIGKILL lands (fleet/proc.py; the crash-dump path);
- a crash dump embeds the ring as-is (obs/crashdump.py).

Inertness: ``record()`` is appended AFTER the step's device work was
dispatched, reads only host-side ints the engine already computed, and
never forces a sync — the step's ``t1 - t0`` therefore measures
dispatch + any blocking the step itself did, which is the honest
number for a recorder that must never add blocking of its own (the
bench's timed A/B keeps its own explicit drains).

The ring is ON BY DEFAULT: every ``ServeEngine`` owns one and registers
it in :func:`live`, the process-wide handle through which a reader that
holds no engine (a benchmark's per-layer metric, an operator attached
to a running replica) finds it, together with the engine's ``static``
facts (weight bytes, KV bytes a token, slots, program names).

The ring starts at the first ``engine.step()``. What the process did
BEFORE that — importing the package, building engines and trainers,
warming their programs up — is on the :class:`StartupRecord`, one a
process, found through :func:`startup`: the ``qn.setup.*`` spans
(``obs/spans.py`` opens them and charges JAX's compile events to them)
as plain dicts, oldest first, bounded.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional


@dataclass
class StepRecord:
    """One engine step, host-side facts only (all JSON-able)."""

    step: int                   # engine-lifetime step index (1-based)
    t0: float                   # clock() at step entry
    t1: float                   # clock() after host bookkeeping
    running: int = 0            # occupied slots after the step
    waiting: int = 0            # scheduler queue depth
    decoding: int = 0           # slots that rode the decode/verify step
    prefilling: int = 0         # slots mid-chunked-prefill
    admitted: int = 0           # admissions this step
    finished: int = 0           # retirements this step
    preempted: int = 0          # evictions this step
    kv_blocks_used: int = 0
    kv_blocks_total: int = 0
    prefill_tokens: int = 0     # prompt tokens pushed through prefill
    decode_tokens: int = 0      # tokens committed by decode/verify
    prefix_hit_tokens: int = 0
    prefill_chunks: int = 0     # chunk program invocations (budget use)
    spec_step: bool = False
    draft_tokens: int = 0
    accepted_draft_tokens: int = 0
    # exclusive seconds by phase of the step (obs/spans.py PHASES);
    # they sum to t1 - t0
    phases: Dict[str, float] = field(default_factory=dict)
    host_syncs: int = 0         # blocking device-to-host reads
    h2d_bytes: int = 0          # bytes of the host arrays uploaded
    context_tokens: int = 0     # sum of positions over the decoding slots
    # bytes of per-slot recurrent state the step's programs read and
    # wrote (2 x state_bytes_per_slot a decoding slot and a prefill
    # program); 0 for a KV-only family
    state_bytes: int = 0
    attrs: Dict = field(default_factory=dict)

    def to_dict(self) -> Dict:
        return asdict(self)

    @property
    def wall_s(self) -> float:
        return max(self.t1 - self.t0, 0.0)


class StepRecorder:
    """Bounded ring of :class:`StepRecord` (see module docstring).

    Thread-safe: the engine records from its worker thread while the
    heartbeat thread drains increments for the wire and stats RPCs
    snapshot the whole ring."""

    def __init__(self, *, capacity: int = 512, clock=time.monotonic,
                 lock=None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.clock = clock
        # ``lock=`` accepts an analysis.lockrt.InstrumentedLock so a
        # lock_audit=True fleet folds this mutex into its order graph
        self._lock = lock if lock is not None else threading.Lock()
        self._ring: "deque[StepRecord]" = deque(maxlen=self.capacity)
        self._total = 0          # records ever appended
        self._drained = 0        # records shipped via drain_new()
        # what does not change from step to step, filled once by the
        # engine that owns the ring (ServeEngine.recorder)
        self.static: Dict = {}

    def record(self, rec: StepRecord) -> None:
        with self._lock:
            self._ring.append(rec)
            self._total += 1

    # ---- reading ----------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    @property
    def total(self) -> int:
        """Steps ever recorded (>= len(); the excess scrolled off)."""
        with self._lock:
            return self._total

    def snapshot(self) -> List[Dict]:
        """The ring as JSON-able dicts, oldest first."""
        with self._lock:
            return [r.to_dict() for r in self._ring]

    def last(self) -> Optional[Dict]:
        """The freshest step record (or None before the first step) —
        what the signal plane samples occupancy/KV pressure from
        without copying the whole ring (obs/signals.py)."""
        with self._lock:
            return self._ring[-1].to_dict() if self._ring else None

    def drain_new(self, *, max_records: int = 64) -> List[Dict]:
        """Records appended since the last drain (at most
        ``max_records`` per call — heartbeat frames stay small; the
        rest comes on the next beat). Records that scrolled off the
        ring before being drained are simply gone — the mirror is
        "last known", not lossless, exactly like the black box it
        models."""
        with self._lock:
            undrained = self._total - self._drained
            # records that scrolled off the ring before being drained
            # are lost to the mirror; the cursor must skip them or a
            # later drain would re-ship records it already sent
            lost = max(undrained - len(self._ring), 0)
            self._drained += lost
            undrained -= lost
            take = min(undrained, max_records)
            if take <= 0:
                return []
            window = list(self._ring)[-undrained:]
            self._drained += take
            return [r.to_dict() for r in window[:take]]


# owner (an engine) -> its ring: an entry goes with its OWNER
_LIVE: "weakref.WeakKeyDictionary[object, StepRecorder]" = \
    weakref.WeakKeyDictionary()
_LIVE_LOCK = threading.Lock()
# the ring registered last, held strongly: what live() falls back to
# when no owner is alive
_NEWEST: Optional[StepRecorder] = None


def register(recorder: StepRecorder, owner: object) -> None:
    """Make ``owner``'s ring findable through :func:`live` for as long
    as the owner lives (an owner has one ring: a later one replaces
    it)."""
    global _NEWEST
    with _LIVE_LOCK:
        _LIVE[owner] = recorder
        _NEWEST = recorder


def live() -> List[StepRecorder]:
    """The rings of the engines alive in this process; when NONE is,
    the ring registered last. The reader this exists for holds no
    engine, so it may come when the last engine's last reference is
    gone (a benchmark reads its per-layer metrics after its driver
    returned): what it found then depended on whether the garbage
    collector had reached the engine's cycles yet — a cell that made
    more steps, hence more records a reading, lost its last metric to
    a collection in the middle of its readers (PR 34). While any engine
    lives a dropped one's ring is never listed beside it."""
    with _LIVE_LOCK:
        rings = list(_LIVE.values())
        return rings or ([_NEWEST] if _NEWEST is not None else [])


class StartupRecord:
    """What the process did before its first step: the ``qn.setup.*``
    spans (obs/spans.py), each a plain dict

    ``{"id", "name", "t0", "t1", "parent", "exclusive_s", "attrs"}``

    on ``time.perf_counter`` — ``parent`` the ``id`` of the span that
    was open when this one opened (None at the root), ``t1`` None
    while it is open, ``exclusive_s`` the time it was the innermost
    open span (a span's exclusive time plus its children's ``t1 - t0``
    is its own ``t1 - t0``), ``attrs`` what JAX traced, lowered and
    compiled or loaded inside it (``trace_s``, ``lower_s``,
    ``compile_or_load_s``, ``cache_retrieval_s``, ``programs``,
    ``cache_hits``, ``cache_misses``: present only where something
    was). Oldest first by ``t0``; bounded: past
    ``capacity`` the oldest falls off and ``dropped`` counts it.

    One a process (:func:`startup`), appended to by every engine and
    trainer the process builds — a second engine adds spans, nothing
    resets the record. ``unattributed`` sums the compile events that
    fell in no span and no engine step (eager operations, a caller's
    own programs); ``totals`` sums every event of the process,
    wherever it was charged.

    Thread-safe for its readers: a span is written by the thread that
    opened it alone, ``snapshot`` copies under the lock."""

    def __init__(self, *, capacity: int = 1024):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._spans: "deque[Dict]" = deque(maxlen=self.capacity)
        self._opened = 0            # spans ever opened: the next id
        self.unattributed: Dict[str, float] = {}
        self.totals: Dict[str, float] = {}

    def open(self, name: str, t0: float, *, t1: Optional[float] = None,
             parent: Optional[int] = None,
             attrs: Optional[Dict] = None) -> Dict:
        """Append a span and return it: the LIVE dict, which its owner
        closes (``t1``) and charges in place. With ``t1`` it is closed
        already (``qn.setup.import``: two clock readings, nothing
        inside it is another span's)."""
        with self._lock:
            span = {"id": self._opened, "name": name, "t0": t0, "t1": t1,
                    "parent": parent,
                    "exclusive_s": 0.0 if t1 is None else t1 - t0,
                    "attrs": dict(attrs or {})}
            self._opened += 1
            self._spans.append(span)
            return span

    def charge(self, key: str, amount: float,
               sink: Optional[Dict]) -> None:
        """Add ``amount`` of ``key`` to ``sink`` — a span's ``attrs``
        or ``unattributed``; None where the caller charged it elsewhere
        (to an engine step) — and to ``totals`` always."""
        with self._lock:
            for d in (self.totals, sink):
                if d is not None:
                    d[key] = d.get(key, 0) + amount

    @property
    def dropped(self) -> int:
        """Spans that fell off the front."""
        with self._lock:
            return self._opened - len(self._spans)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def snapshot(self) -> Dict:
        """``{"spans", "dropped", "unattributed", "totals"}`` as
        JSON-able copies, spans oldest first."""
        with self._lock:
            return {
                "spans": [{**s, "attrs": dict(s["attrs"])}
                          for s in self._spans],
                "dropped": self._opened - len(self._spans),
                "unattributed": dict(self.unattributed),
                "totals": dict(self.totals)}


_STARTUP = StartupRecord()


def startup() -> StartupRecord:
    """The process's start-up record: what a reader that holds no
    engine, and a training process that has no ring, both find."""
    return _STARTUP
