"""Render flight-recorder output as Chrome trace-event JSON (loadable
in Perfetto / chrome://tracing).

Input is any JSON file carrying a step ring and/or request spans in
the obs formats (quintnet_tpu/obs/):

- a crash dump (``obs/crashdump.py``: ``{"kind": "crash_dump",
  "ring": [...], "traces": {...}}``) — the post-mortem, visualized;
- a raw obs dump (``{"ring": [...], "traces": {...}}``:
  ``eng.recorder.snapshot()`` beside ``eng.tracer.snapshot()``; with
  ``"static": eng.recorder.static`` beside them, or ``--paged-layers``,
  the ring's read amplification is printed and shown a step:
  :func:`read_amplification`; with ``"startup":
  obs.recorder.startup().snapshot()`` beside them, what the process
  did before its first step).

Mapping (the Chrome trace-event format, JSON Array/Object flavor):

- each engine STEP becomes a complete ("ph": "X") slice on the
  "engine steps" thread — duration = the step's clock window, args =
  the step's phase mix / occupancy / KV pressure / chunk + spec
  ledgers, so the Perfetto timeline shows exactly the prefill/decode
  interference Sarathi argues about; a step inside which JAX compiled
  or loaded a program says so in its name (``step 412 (compiled
  jit_serve_prefill_b512)``) and in ``args["compiled"]``;
- each closed START-UP span (``qn.setup.import`` / ``.build`` /
  ``.warmup`` / ``.warmup/<program>``) becomes a complete slice on the
  "start-up" thread, nested as the spans were, args = its exclusive
  seconds and the trace / lower / compile-or-load seconds and cache
  hits and misses charged to it;
- each request SPAN becomes an async begin/end pair ("ph": "b"/"e",
  id = trace id) on the "requests" track, instants (t1 == t0) become
  instant events ("ph": "i") — one row per request from queue to
  finish, migrations included (the id stitches cross-process spans);
- each fleet LIFECYCLE EVENT (obs/events.py — crash dumps embed the
  recent ring) becomes an instant marker ("ph": "i") on the "fleet
  events" track. SLO-judgment events (``slo_breach`` /
  ``slo_recovered`` / ``rebalance_recommended``, obs/slo.py +
  obs/signals.py) are scoped GLOBAL ("s": "g") so Perfetto draws a
  full-height line: "the fast+slow burn windows tripped HERE" and
  "the planner recommended decode→prefill HERE" line up visually
  against the step slices that caused them.

Timestamps are microseconds (the format's unit), re-based to the
earliest event so Perfetto opens at t=0 instead of hours into a
monotonic clock.

A second mode reads a PROFILER trace of a run on the chip
(``--xplane DIR``: the directory given to ``jax.profiler.start_trace``,
or the ``*.xplane.pb`` itself) and prints one JSON object of tables —
the operator's view of where a traced stretch went:

- ``programs``: per jitted program (``jit_serve_decode``,
  ``jit_local_step``, ...) executions and milliseconds, chip 0;
- ``ops_by_program``: per program its twelve largest operations by own
  time, chip 0 (``{"jit_serve_decode": {"ragged-dot-none.3": {"count":
  52, "ms": 76.9}}}``): ``ms / count`` is a call, ``count`` over the
  program's executions the calls a program (what split the grouped
  matmul by decode and prefill: PERF.md section 5, PR 38);
- ``device_ms_by_scope``: device own-time by the program's
  ``jax.named_scope`` path (``blocks/attn/sdpa``), mean over chips,
  with ``named_share_pct``: the share that lies in some scope. An
  operation's scope comes from ``DIR/qn_scopes.json``, which the
  traced process writes from its compiled programs
  (quintnet_tpu/obs/scopes.py); without the file everything reads
  ``(no scope map)``;
- ``idle_ms_by_span``: chip 0's idle time by the innermost ``qn.*``
  host span that covered it (``qn.serve.wait``, ``qn.train.dispatch``:
  the program's own spans, on the profiler's clock), with
  ``named_share_pct``;
- ``collective_ms_by_scope`` (a mesh): time of the collective
  operations by scope, and ``exposed_ms``: the part during which no
  other operation ran on that chip.

The xplane is read with the benchmark's reader
(benchmarks/lib/trace_reduce.read_trace), so both see one trace alike.

Usage:
  python tools/trace_view.py DUMP.json -o trace.json
  python tools/trace_view.py DUMP.json            # stdout
  python tools/trace_view.py --xplane DIR         # tables, stdout

Library surface: :func:`chrome_trace` (dict in, dict out — the bench
and tests call this), :func:`validate_chrome_trace` (structural check
used by CI so the export can never drift off-format).
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_US = 1e6

# pid/tid are display coordinates in the trace-event format; one
# process row with named threads reads best in Perfetto
PID = 1
TID_STEPS = 1
TID_REQUESTS = 2
TID_EVENTS = 3
TID_SETUP = 4

# fleet events drawn as FULL-HEIGHT markers ("s": "g"): the SLO
# judgment layer's output, which the reader wants to line up against
# every track at once. Everything else stays a thread-local tick.
_GLOBAL_EVENT_KINDS = frozenset({
    "slo_breach", "slo_recovered", "rebalance_recommended",
})


def _base_ts(ring: List[Dict], traces: Dict[str, List[Dict]],
             fleet_events: Optional[List[Dict]] = None,
             setup: Optional[List[Dict]] = None) -> float:
    ts = [r["t0"] for r in ring]
    ts += [s["t0"] for s in (setup or [])]
    ts += [s["t0"] for spans in traces.values() for s in spans]
    ts += [e["ts"] for e in (fleet_events or []) if "ts" in e]
    return min(ts) if ts else 0.0


def read_amplification(ring: List[Dict], paged_layers: int):
    """(ratio, steps): how many times what its rows HELD a ring's
    decode and verify steps READ of the paged pool — the sum of
    ``attrs["attended_rows"]`` (pool positions x layers the paged
    layers' attention read: a row rounded up to the walk's key block,
    or to the table's width where a view is still gathered) over the
    sum of ``context_tokens x paged_layers`` (the ring's static), over
    the steps that carry the counter and held a position. 1.0 reads
    what is live and nothing else. (None, 0) where no step counts."""
    steps = [r for r in ring
             if "attended_rows" in (r.get("attrs") or {})
             and r.get("context_tokens")]
    held = sum(r["context_tokens"] for r in steps) * paged_layers
    if not held:
        return None, 0
    return sum(r["attrs"]["attended_rows"] for r in steps) / held, len(steps)


def chrome_trace(ring: Optional[List[Dict]] = None,
                 traces: Optional[Dict[str, List[Dict]]] = None,
                 fleet_events: Optional[List[Dict]] = None,
                 *, label: str = "quintnet-serve",
                 paged_layers: Optional[int] = None,
                 startup: Optional[Dict] = None) -> Dict:
    """Build the Chrome trace-event JSON object (see module
    docstring). ``ring``: StepRecorder.snapshot(); ``traces``:
    Tracer.snapshot(); ``fleet_events``: EventLog.snapshot() (what a
    crash dump's ``events`` field carries); ``paged_layers``: the
    ring's static of that name — with it a step that counted
    ``attended_rows`` also shows its ``read_amplification``;
    ``startup``: obs.recorder.startup().snapshot() — its closed
    ``qn.setup.*`` spans on a thread of their own, each with what JAX
    traced, lowered and compiled or loaded inside it (the ring's
    ``time.monotonic`` and the spans' ``time.perf_counter`` are one
    clock). A step that compiled shows the programs in its name and
    in ``args["compiled"]``."""
    ring = ring or []
    traces = traces or {}
    fleet_events = fleet_events or []
    setup = [s for s in (startup or {}).get("spans", [])
             if s.get("t1") is not None]
    t_base = _base_ts(ring, traces, fleet_events, setup)
    events: List[Dict] = [
        {"ph": "M", "pid": PID, "name": "process_name",
         "args": {"name": label}},
        {"ph": "M", "pid": PID, "tid": TID_STEPS, "name": "thread_name",
         "args": {"name": "engine steps"}},
        {"ph": "M", "pid": PID, "tid": TID_REQUESTS,
         "name": "thread_name", "args": {"name": "requests"}},
        {"ph": "M", "pid": PID, "tid": TID_EVENTS,
         "name": "thread_name", "args": {"name": "fleet events"}},
    ]
    for rec in ring:
        args = {k: v for k, v in rec.items()
                if k not in ("t0", "t1", "attrs")}
        args.update(rec.get("attrs") or {})
        ratio, _ = read_amplification([rec], paged_layers or 0)
        if ratio is not None:
            args["read_amplification"] = ratio
        compiled = args.get("compiled")
        events.append({
            "name": f"step {rec.get('step', '?')}" + (
                f" (compiled {', '.join(compiled)})" if compiled else ""),
            "cat": "engine", "ph": "X",
            "ts": (rec["t0"] - t_base) * _US,
            "dur": max(rec["t1"] - rec["t0"], 0.0) * _US,
            "pid": PID, "tid": TID_STEPS, "args": args,
        })
    if setup:
        events.append({"ph": "M", "pid": PID, "tid": TID_SETUP,
                       "name": "thread_name", "args": {"name": "start-up"}})
    for s in setup:
        events.append({
            "name": s["name"], "cat": "setup", "ph": "X",
            "ts": (s["t0"] - t_base) * _US,
            "dur": max(s["t1"] - s["t0"], 0.0) * _US,
            "pid": PID, "tid": TID_SETUP,
            "args": {"exclusive_s": s.get("exclusive_s"),
                     **(s.get("attrs") or {})}})
    for trace_id, spans in sorted(traces.items()):
        for s in spans:
            common = {"cat": "request", "id": trace_id, "pid": PID,
                      "tid": TID_REQUESTS,
                      "args": dict(s.get("attrs") or {})}
            t0 = (s["t0"] - t_base) * _US
            if s["t1"] > s["t0"]:
                events.append({"name": s["name"], "ph": "b",
                               "ts": t0, **common})
                events.append({"name": s["name"], "ph": "e",
                               "ts": (s["t1"] - t_base) * _US,
                               **common})
            else:
                # instant: scope "t" (thread) keeps it a tick mark
                events.append({"name": s["name"], "ph": "i", "s": "t",
                               "ts": t0, **common})
    for e in fleet_events:
        if "ts" not in e or "kind" not in e:
            continue        # not an EventLog record; skip, don't guess
        kind = e["kind"]
        name = kind
        args = {k: v for k, v in e.items()
                if k not in ("ts", "seq", "kind")}
        if kind == "slo_breach":
            # the marker label carries the judgment: which objective,
            # which pool, how hard it is burning
            name = (f"slo_breach {args.get('objective', '?')} "
                    f"[{args.get('pool', '?')}] "
                    f"{args.get('burn_fast', 0):.1f}x")
        elif kind == "rebalance_recommended":
            name = (f"rebalance {args.get('direction', '?')}"
                    + (" (revert)" if args.get("revert") else ""))
        events.append({
            "name": name, "cat": "fleet", "ph": "i",
            "s": "g" if kind in _GLOBAL_EVENT_KINDS else "t",
            "ts": (e["ts"] - t_base) * _US,
            "pid": PID, "tid": TID_EVENTS, "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"source": label}}


def validate_chrome_trace(obj: Dict) -> int:
    """Structural validation of a trace-event JSON object; returns the
    event count. Raises ValueError on anything Perfetto would choke
    on — the CI gate behind 'the export validates as Chrome
    trace-event JSON'."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("not a trace-event object: no 'traceEvents'")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    open_async: Dict = {}
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            raise ValueError(f"event {i} is not an object")
        ph = e.get("ph")
        if ph is None or "pid" not in e or "name" not in e:
            raise ValueError(
                f"event {i} is missing ph/pid/name: {e}")
        if ph == "M":
            continue
        if "ts" not in e or not isinstance(e["ts"], (int, float)):
            raise ValueError(f"event {i} has no numeric ts: {e}")
        if ph == "X":
            if "dur" not in e or e["dur"] < 0:
                raise ValueError(
                    f"complete event {i} needs a dur >= 0: {e}")
        elif ph in ("b", "e"):
            if "id" not in e or "cat" not in e:
                raise ValueError(
                    f"async event {i} needs id + cat: {e}")
            key = (e["cat"], e["id"], e["name"])
            if ph == "b":
                open_async[key] = open_async.get(key, 0) + 1
            else:
                if open_async.get(key, 0) < 1:
                    raise ValueError(
                        f"async end without begin at event {i}: {e}")
                open_async[key] -= 1
        elif ph == "i":
            if e.get("s") not in (None, "t", "p", "g"):
                raise ValueError(
                    f"instant event {i} has invalid scope: {e}")
        else:
            raise ValueError(f"event {i} has unknown ph {ph!r}")
    dangling = {k: v for k, v in open_async.items() if v}
    if dangling:
        raise ValueError(f"unbalanced async begin/end: {dangling}")
    return len(events)


# ---------------------------------------------------------------------
# --xplane: a profiler trace of a chip run, as tables
# ---------------------------------------------------------------------
NO_SCOPE = "(no scope)"
NO_MAP = "(no scope map)"
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")


def opcode(event_name: str) -> str:
    """``%psum.3 = bf16[8,128]{1,0} all-reduce(%x), ...`` ->
    ``all-reduce``: a trace names an operation by its whole HLO line,
    and the instruction's own name need not say what it is."""
    rest = event_name.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rest)
    return m.group(1) if m else ""


def _leaves(events: List[Tuple]) -> List[Tuple]:
    """The events that enclose no other event (a ``while`` encloses the
    operations of its body): the ones that occupy the device."""
    out = []
    ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    for ev, nxt in zip(ordered, ordered[1:] + [None]):
        if nxt is None or nxt[1] >= ev[2] or nxt[2] > ev[2]:
            out.append(ev)
    return out


def _overlap(merged: List[Tuple[float, float]], starts: List[float],
             s: float, e: float) -> float:
    """Length of [s, e) covered by the disjoint sorted ``merged``."""
    total = 0.0
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(merged) and merged[i][0] < e:
        total += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


def xplane_tables(path: str) -> Dict:
    """The tables of ``--xplane`` (module docstring) as one dict."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib import trace_reduce as tr
    from quintnet_tpu.obs.scopes import SCOPES_FILE

    trace_dir = path if os.path.isdir(path) else os.path.dirname(path)
    xplane = tr.find_xplane(path) if os.path.isdir(path) else path
    trace = tr.read_trace(xplane, span_prefix="qn.")
    maps = None
    scopes_path = os.path.join(trace_dir, SCOPES_FILE)
    if os.path.isfile(scopes_path):
        with open(scopes_path) as f:
            maps = json.load(f)

    # programs, window and idle-by-span are the benchmark's own
    # reduction, here over the program's qn.* spans
    reduced = tr.reduce_trace(trace, top=1000)
    if reduced is None:
        raise SystemExit(f"{xplane}: no operation ran on a device")
    planes = [d for _n, d in sorted(trace["devices"].items()) if d["ops"]]
    n = reduced["chips"]
    by_scope: Dict[str, float] = {}
    coll: Dict[str, List[float]] = {}      # scope -> [ns, exposed ns]
    by_program: Dict[str, Dict[str, List[float]]] = {}
    for d in planes:
        mods = sorted((s, e, name.split("(", 1)[0])
                      for name, s, e in d["modules"])
        mod_starts = [m[0] for m in mods]

        def program_of(start: float) -> Optional[str]:
            i = bisect.bisect_right(mod_starts, start) - 1
            return mods[i][2] if i >= 0 and start < mods[i][1] else None

        if d is planes[0]:
            keyed = [((program_of(s), tr.short_name(nm)), s, e)
                     for nm, s, e in d["ops"]]
            for (prog, op), ns in tr.self_times(keyed).items():
                by_program.setdefault(prog or NO_SCOPE, {})[op] = [0, ns]
            for (prog, op), _s, _e in keyed:
                by_program[prog or NO_SCOPE][op][0] += 1

        def scope_of(name: str, start: float) -> str:
            if maps is None:
                return NO_MAP
            return maps.get(program_of(start), {}).get(
                tr.short_name(name), NO_SCOPE)

        # own time by scope: the scope rides in the name self_times
        # keys on, so equal instruction names of two programs stay apart
        keyed = [(scope_of(nm, s), s, e) for nm, s, e in d["ops"]]
        for scope, ns in tr.self_times(keyed).items():
            by_scope[scope] = by_scope.get(scope, 0.0) + ns
        leaves = _leaves(d["ops"])
        is_coll = [opcode(nm).startswith(_COLLECTIVES)
                   for nm, _s, _e in leaves]
        others = tr.merge([(s, e) for (nm, s, e), c
                           in zip(leaves, is_coll) if not c])
        starts = [iv[0] for iv in others]
        for (nm, s, e), c in zip(leaves, is_coll):
            if c:
                rec = coll.setdefault(scope_of(nm, s), [0.0, 0.0])
                rec[0] += e - s
                rec[1] += (e - s) - _overlap(others, starts, s, e)

    idle = {name: 1e9 * sec for name, sec in reduced["idle_gaps"]}

    def table(d: Dict[str, float], scale: float) -> Dict[str, float]:
        return {k: v / scale for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    def named_share(d: Dict[str, float], unnamed) -> float:
        total = sum(d.values())
        named = sum(v for k, v in d.items() if k not in unnamed)
        return 100.0 * named / total if total else 0.0

    host_names = sorted({nm for nm, _s, _e in trace["host_spans"]})
    out = {
        "xplane": xplane, "chips": n,
        "window_ms": 1e3 * reduced["window_s"],
        "scope_map": scopes_path if maps is not None else None,
        "programs": {k: {"count": c, "ms": 1e3 * sec} for k, (c, sec)
                     in sorted(reduced["modules"].items())},
        "ops_by_program": {
            prog: {op: {"count": c, "ms": ns / 1e6} for op, (c, ns) in
                   sorted(ops.items(), key=lambda kv: -kv[1][1])[:12]}
            for prog, ops in sorted(by_program.items())},
        "device_ms_by_scope": table(by_scope, 1e6 * n),
        "device_named_share_pct": named_share(by_scope,
                                              (NO_SCOPE, NO_MAP)),
        "host_spans": host_names,
        "idle_ms_by_span": table(idle, 1e6),
        "idle_named_share_pct": named_share(
            idle, ("unannotated", "between_ops")),
    }
    if n > 1:
        out["collective_ms_by_scope"] = {
            k: {"ms": v[0] / 1e6 / n, "exposed_ms": v[1] / 1e6 / n}
            for k, v in sorted(coll.items(), key=lambda kv: -kv[1][0])}
    return out


def _load_dump(path: str) -> Dict:
    with open(path) as f:
        payload = json.load(f)
    if not isinstance(payload, dict):
        raise SystemExit(f"{path}: expected a JSON object")
    if ("ring" not in payload and "traces" not in payload
            and "events" not in payload):
        raise SystemExit(
            f"{path}: no 'ring', 'traces' or 'events' — not a crash "
            f"dump or obs dump")
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="trace_view",
        description="crash dump / obs dump -> Chrome trace-event JSON "
                    "(Perfetto)")
    ap.add_argument("dump", nargs="?",
                    help="crash-dump or obs-dump JSON file")
    ap.add_argument("--xplane", default=None, metavar="DIR",
                    help="a profiler trace directory (or *.xplane.pb): "
                         "print per-program, per-scope, idle-by-span "
                         "and collective tables as JSON")
    ap.add_argument("-o", "--out", default=None,
                    help="output file (default: stdout)")
    ap.add_argument("--paged-layers", type=int, default=None,
                    help="the ring's static of that name (default: the "
                         "dump's static.paged_layers): print the ring's "
                         "read amplification, attended_rows over "
                         "context_tokens x layers, and show it a step")
    args = ap.parse_args(argv)
    if (args.dump is None) == (args.xplane is None):
        ap.error("give a DUMP.json or --xplane DIR (one of them)")
    if args.xplane is not None:
        text = json.dumps(xplane_tables(args.xplane), indent=1)
        if args.out:
            with open(args.out, "w") as f:
                f.write(text)
        else:
            print(text)
        return 0

    payload = _load_dump(args.dump)
    label = payload.get("replica") or "quintnet-serve"
    layers = args.paged_layers or (payload.get("static") or {}).get(
        "paged_layers")
    trace = chrome_trace(payload.get("ring"), payload.get("traces"),
                         payload.get("events"), label=label,
                         paged_layers=layers,
                         startup=payload.get("startup"))
    if layers:
        ratio, steps = read_amplification(payload.get("ring") or [], layers)
        if ratio is not None:
            print(f"read amplification {ratio:.3f} over {steps} steps "
                  f"(attended_rows / (context_tokens x {layers} paged "
                  f"layers))", file=sys.stderr)
    validate_chrome_trace(trace)
    text = json.dumps(trace, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {len(trace['traceEvents'])} events to "
              f"{args.out}", file=sys.stderr)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
