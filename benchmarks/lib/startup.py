"""What the per-layer readers of ``setup_s`` share: the program's own
start-up record.

The program keeps, one a process, the spans of what it did before its
first step (``quintnet_tpu/obs/recorder.py`` ``startup()``:
``qn.setup.import``, ``.build``, ``.warmup`` and under it one child a
program, each a dict with ``t0``, ``t1``, ``exclusive_s`` on
``time.perf_counter`` and, in ``attrs``, what JAX traced, lowered and
compiled or loaded inside it), and beside them ``unattributed``: the
same sums for what compiled outside every span and every engine step (a
driver's own weights, its reference). A training process compiles
nothing in its window and a serving one must not
(``no_compile_in_window``), so at reading time the sums over the
process are the sums of start-up.

Nothing here is timed by the benchmark. A program without the record
(the parent of the PR that added it) gives ``None`` everywhere here, and
the metric is left out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional

PREFIX = "qn.setup."


def find_record() -> Optional[Dict]:
    """A snapshot of the process's start-up record (``spans`` oldest
    first, ``dropped``, ``unattributed``, ``totals``), or None where
    the program has none."""
    try:
        from quintnet_tpu.obs import recorder
    except ImportError:
        return None
    startup = getattr(recorder, "startup", None)
    return None if startup is None else startup().snapshot()


def closed_spans(record: Dict, phase: str,
                 children: bool = False) -> List[Dict]:
    """The closed spans named ``qn.setup.<phase>``; with ``children``
    also those under it by name (``qn.setup.<phase>/<program>``)."""
    name = PREFIX + phase
    return [s for s in record["spans"]
            if s["t1"] is not None
            and (s["name"] == name
                 or (children and s["name"].startswith(name + "/")))]


def exclusive_seconds(phase: str, children: bool = False
                      ) -> Optional[float]:
    """Seconds the process spent with a ``qn.setup.<phase>`` span
    innermost (with ``children``: or a span under it). Exclusive times
    do not overlap, so a span opened inside another of its own kind is
    not counted twice. None with no record or no such span."""
    record = find_record()
    if record is None:
        return None
    spans = closed_spans(record, phase, children)
    if not spans:
        return None
    return sum(s["exclusive_s"] for s in spans)


def charged(*keys: str) -> Optional[float]:
    """The sum of ``keys`` over every span's ``attrs`` and over
    ``unattributed``: what was charged anywhere but to an engine step.
    None with no record."""
    record = find_record()
    if record is None:
        return None
    sinks = [s["attrs"] for s in record["spans"]]
    sinks.append(record["unattributed"])
    return sum(sink.get(k, 0) for sink in sinks for k in keys)
