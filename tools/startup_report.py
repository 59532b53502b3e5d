"""Where a process's start-up went, from the program's own record:

    python tools/startup_report.py [--json OUT] SCRIPT [ARGS ...]
    chiprun -- python tools/startup_report.py --json chiprun_out/xl.json \\
        benchmarks/run.py --workload gpt2-xl.serve-chat-sat --seed 7 \\
        --seconds 10 --trace 0

Runs ``SCRIPT`` in this process as ``__main__`` (``benchmarks/run.py``,
``chip_smoke.py``, an example) and, when it ends, prints the start-up
record (``quintnet_tpu/obs/recorder.startup()``) as a table: each
``qn.setup.*`` span nested under the span that caused it, with its
seconds, its exclusive seconds and what JAX traced, lowered and compiled
or loaded inside it — under ``qn.setup.warmup`` one row a program — then
``unattributed`` (what compiled outside every span and engine step) and
the process's totals. ``before_import_s`` is the time from this tool's
first line to the start of ``qn.setup.import``: the script's own imports
and argument handling (the interpreter's start lies before any clock of
the process). For a COLD start point ``JAX_COMPILATION_CACHE_DIR`` at an
empty directory: ``compile_or_load_s`` is then compile time, by program.
``--json`` also writes the record's snapshot, with ``t_tool_start``.
The script's exit code is kept.
"""

from __future__ import annotations

import time

T_TOOL_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import runpy  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COLUMNS = ("trace_s", "lower_s", "compile_or_load_s", "programs",
           "cache_hits", "cache_misses")
HEADS = ("trace_s", "lower_s", "c_or_ld_s", "progs", "hits", "misses")


def _cells(attrs) -> str:
    return " ".join(
        f"{attrs.get(k, 0):9.3f}" if k.endswith("_s")
        else f"{int(attrs.get(k, 0)):6d}" for k in COLUMNS)


def render(snapshot, t_start: float) -> str:
    """The table (see module docstring) of a record's snapshot."""
    spans = snapshot["spans"]
    kids = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    known = {s["id"] for s in spans}
    head = (f"{'span':58s} {'seconds':>9s} {'exclusive':>9s} "
            + " ".join(f"{h:>9s}" if h.endswith("_s") else f"{h:>6s}"
                       for h in HEADS))
    lines = [head]

    def walk(span, depth):
        wall = (span["t1"] - span["t0"]) if span["t1"] is not None else None
        lines.append(
            f"{'  ' * depth + span['name']:58s} "
            + (f"{wall:9.3f}" if wall is not None else f"{'open':>9s}")
            + f" {span['exclusive_s']:9.3f} " + _cells(span["attrs"]))
        for kid in kids.get(span["id"], []):
            walk(kid, depth + 1)

    for s in spans:                # roots, and orphans of the cap
        if s["parent"] is None or s["parent"] not in known:
            walk(s, 0)
    lines.append(f"{'unattributed':58s} {'':9s} {'':9s} "
                 + _cells(snapshot["unattributed"]))
    lines.append(f"{'totals (steps included)':58s} {'':9s} {'':9s} "
                 + _cells(snapshot["totals"]))
    first = next((s for s in spans if s["name"] == "qn.setup.import"), None)
    if first is not None:
        lines.append(f"before_import_s {first['t0'] - t_start:.3f}")
    if snapshot["dropped"]:
        lines.append(f"dropped {snapshot['dropped']} span(s) off the front")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--json", default=None,
                    help="also write the record's snapshot here")
    ap.add_argument("script")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.argv = [args.script, *args.args]
    code = 0
    try:
        runpy.run_path(args.script, run_name="__main__")
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else int(e.code is not None)
    from quintnet_tpu.obs.recorder import startup

    snapshot = startup().snapshot()
    print(render(snapshot, T_TOOL_START), file=sys.stderr, flush=True)
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({**snapshot, "t_tool_start": T_TOOL_START,
                       "t_tool_end": time.perf_counter(),
                       "argv": sys.argv, "exit_code": code}, f)
    return code


if __name__ == "__main__":
    sys.exit(main())
