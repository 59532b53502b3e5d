"""From a profiler trace (``*.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU
trace has one plane per chip, ``/device:TPU:<n>``, whose lines matter
here:

- ``XLA Modules``: one event per execution of a jitted program;
- ``XLA Ops``: one event per HLO operation. Operations NEST: a ``while``
  (a layer scan) spans the operations of its body, so the plain sum of
  the durations counts the same nanosecond more than once. Busy time is
  the UNION of the intervals; an operation's own time is its duration
  less that of the operations nested inside it.

and one host plane, ``/host:CPU``, whose lines are threads; a
``jax.profiler.TraceAnnotation`` shows there under its own name, on the
same clock as the device planes.

Everything here works on plain ``(start_ns, end_ns)`` tuples once the
file is read, so the arithmetic is tested on hand-made intervals and on
the recorded v5e trace in ``artifacts/trace_r04`` (benchmarks/tests).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    """The one ``*.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def short_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the
    trace names an operation by its whole HLO line."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or event_name[:60]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_ns(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Own nanoseconds by name for nested ``(name, start, end)`` events:
    an event's duration less the events that lie inside it. The values
    sum to the union of the intervals (for properly nested events)."""
    own: Dict[str, float] = {}
    stack: List[List] = []          # [name, end, own_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, ns = stack.pop()
            own[name] = own.get(name, 0.0) + ns

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            # clip a child that overruns its parent by a rounding tick
            e = min(e, stack[-1][1])
            stack[-1][2] -= (e - s)
        stack.append([name, e, e - s])
    close(float("inf"))
    return own


def gaps(busy: Sequence[Interval]) -> List[Interval]:
    """The idle intervals between consecutive busy ones."""
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def attribute_gaps(idle: Sequence[Interval],
                   spans: Sequence[Tuple[str, float, float]],
                   other: str = "unannotated",
                   floor_ns: float = 1000.0) -> Dict[str, float]:
    """Idle nanoseconds by the host span that covered them. Where spans
    nest, the innermost (latest-starting) one that covers a stretch gets
    it; what no span covers goes to ``other``. Gaps under ``floor_ns``
    (the breath between two operations of one program: tens of
    thousands of them) are summed under ``between_ops`` unattributed."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda sp: sp[1])
    nxt = 0
    live: List[Tuple[str, float, float]] = []
    for gs, ge in sorted(idle):
        if ge - gs < floor_ns:
            out["between_ops"] = out.get("between_ops", 0.0) + (ge - gs)
            continue
        while nxt < len(spans) and spans[nxt][1] < ge:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[2] > gs]
        # cut the gap at every span boundary inside it
        cuts = {gs, ge}
        for _n, s, e in live:
            cuts.update(c for c in (s, e) if gs < c < ge)
        edges = sorted(cuts)
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            name = other
            for n, s, e in live:          # sorted by start: last wins
                if s <= mid < e:
                    name = n
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def read_trace(path: str, span_prefix: str = "bench.") -> Dict:
    """Read one xplane file into plain lists.

    Returns ``{"devices": {plane: {"ops": [(name, s, e)], "modules":
    [(name, s, e)]}}, "host_spans": [(name, s, e)]}`` with times in
    nanoseconds. Only host events whose name starts with
    ``span_prefix`` are kept (the benchmark's own annotations)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            rec = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    rec[key].append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
            devices[plane.name] = rec
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return {"devices": devices, "host_spans": host}


def reduce_trace(trace: Dict, top: int = 10) -> Optional[Dict]:
    """Busy time, window and breakdown of a trace read by
    :func:`read_trace`. ``None`` where no operation ran on a device.

    - ``window_s``: first operation's start to last operation's end,
      over all chips (profiler start-up and shutdown are not idle time
      of the program);
    - ``busy_s``: union of the operation intervals, averaged over the
      chips that ran any;
    - ``modules``: ``{program name: [count, total seconds]}``, chip 0;
    - ``device_ops``: the ``top`` operations by own time, summed over
      chips and divided by their number;
    - ``idle_gaps``: idle seconds of chip 0 by the benchmark's host
      span that covered them.
    """
    planes = {n: d for n, d in sorted(trace["devices"].items())
              if d["ops"]}
    if not planes:
        return None
    n = len(planes)
    starts = [min(s for _n, s, _e in d["ops"]) for d in planes.values()]
    ends = [max(e for _n, _s, e in d["ops"]) for d in planes.values()]
    window_ns = max(ends) - min(starts)
    busy_ns = 0.0
    own: Dict[str, float] = {}
    for d in planes.values():
        busy_ns += union_ns([(s, e) for _n, s, e in d["ops"]])
        for name, ns in self_times(
                [(short_name(nm), s, e) for nm, s, e in d["ops"]]).items():
            own[name] = own.get(name, 0.0) + ns
    first = next(iter(planes.values()))
    merged = merge([(s, e) for _n, s, e in first["ops"]])
    idle = attribute_gaps(gaps(merged), trace["host_spans"])
    modules: Dict[str, List[float]] = {}
    for name, s, e in first["modules"]:
        rec = modules.setdefault(name.split("(", 1)[0], [0, 0.0])
        rec[0] += 1
        rec[1] += (e - s) / 1e9

    def ranked(d: Dict[str, float], scale: float) -> List[List]:
        return [[k, v / scale] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": window_ns / 1e9, "busy_s": busy_ns / n / 1e9,
            "chips": n, "modules": modules,
            "device_ops": ranked(own, 1e9 * n),
            "idle_gaps": ranked(idle, 1e9)}
