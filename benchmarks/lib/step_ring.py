"""What the per-layer readers of a serving cell share that is not in
the driver's ``context``: the engine's own flight-recorder ring, and
the device time of the engine's programs by name.

A reader is ``read(ctx)`` and holds no engine. The program keeps a ring
of per-step records (``quintnet_tpu/obs/recorder.py``: phases of the
step on the engine's clock, blocking reads, bytes uploaded, context
tokens) and registers it process-wide; :func:`find_ring` looks it up.
The engine and the driver both read ``CLOCK_MONOTONIC``
(``time.monotonic`` and ``time.perf_counter``), so a ring record lies
INSIDE the driver's span around the ``engine.step()`` that wrote it:
records are paired with the driver's steps by that containment, one to
one, or not at all.

A program without the ring (the parent of the PR that added it) gives
``None`` everywhere here, and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def find_ring():
    """The one live ring of this process, or None: where the program
    has no ring, no engine is alive, or more than one is (which ring a
    number came from must never be a guess)."""
    try:
        from quintnet_tpu.obs import recorder
    except ImportError:
        return None
    live = getattr(recorder, "live", None)
    if live is None:
        return None
    rings = live()
    return rings[0] if len(rings) == 1 else None


def _pair(steps, records) -> Optional[List[Dict]]:
    """One record inside each ``(start, end, ...)`` step, in order."""
    out, i = [], 0
    for step in steps:
        s, e = step[0], step[1]
        while i < len(records) and records[i]["t0"] < s:
            i += 1
        if (i >= len(records) or records[i]["t1"] > e
                or (i + 1 < len(records) and records[i + 1]["t1"] <= e)):
            return None
        out.append(records[i])
        i += 1
    return out


def window_records(ctx) -> Optional[List[Dict]]:
    """The ring's record of every step of the window
    (``ctx["engine_steps"]``: profiler off), or None."""
    steps, ring = ctx.get("engine_steps"), find_ring()
    if not steps or ring is None:
        return None
    return _pair(steps, ring.snapshot())


def traced_records(ctx) -> Optional[List[Dict]]:
    """The ring's records of the traced stretch: the
    ``ctx["traced_steps"]`` steps that follow the window's last step.
    None where the window does not pair or fewer follow."""
    n, ring = ctx.get("traced_steps"), find_ring()
    if not n or ring is None or window_records(ctx) is None:
        return None
    end = ctx["engine_steps"][-1][1]
    after = [r for r in ring.snapshot() if r["t0"] >= end]
    return after[:n] if len(after) >= n else None


def ring_static(key: str):
    """A fact the engine wrote on its ring once (``param_bytes``,
    ``kv_bytes_per_token``, ``max_slots``, ``programs``), or None."""
    ring = find_ring()
    return None if ring is None else ring.static.get(key)


def program_seconds(ctx, prefix: str) -> Tuple[int, float]:
    """(executions, device seconds) of the programs whose name on the
    trace's ``XLA Modules`` line starts with ``prefix``, chip 0, in the
    traced stretch: ``ctx["trace"]["modules"]``."""
    modules = (ctx.get("trace") or {}).get("modules") or {}
    hits = [v for k, v in modules.items() if k.startswith(prefix)]
    return sum(int(c) for c, _s in hits), sum(float(s) for _c, s in hits)
