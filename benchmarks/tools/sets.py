#!/usr/bin/env python3
"""Run a cell several times, as the driver's check does, and print the
spread: how the bounds in BENCHMARK.json were set.

    chiprun --chips 1 --timeout 1800 -- python benchmarks/tools/sets.py \
        --workload <cell> --sets 2 --seeds 2147483659 2147483693 ...

Each run is a new process of the manifest's ``command`` (this parent
never touches JAX, so the child gets the chip). Per set and metric it
prints the median and the spread the contract defines — the distance
between the first and third quartile (``statistics.quantiles(values,
n=4)``) as a share of the median — and keeps every run's full output
in ``chiprun_out/bench/<tag>.log`` and result line in ``<tag>.jsonl``.
``--seconds`` defaults to the manifest's ``run_seconds``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", default=None)
    args = ap.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    tag = args.tag or f"{args.workload}.t{args.trace}"
    out = os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out, exist_ok=True)
    log = open(os.path.join(out, tag + ".log"), "a")
    lines = open(os.path.join(out, tag + ".jsonl"), "a")
    rc = 0
    for s in range(args.sets):
        values = {}
        for seed in args.seeds:
            cmd = manifest["command"] + [
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(args.trace)]
            t0 = time.time()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                               text=True)
            wall = time.time() - t0
            log.write(f"### set {s} seed {seed} rc {p.returncode} "
                      f"wall {wall:.1f}s\n{p.stdout}\n--- stderr (tail)\n"
                      f"{p.stderr[-3000:]}\n")
            log.flush()
            last = p.stdout.strip().splitlines()[-1:] or [""]
            if p.returncode != 0 or '"metrics"' not in last[0]:
                print(f"set {s} seed {seed}: rc {p.returncode}\n"
                      f"{p.stderr[-1500:]}", flush=True)
                rc = 1
                continue
            rec = json.loads(last[0])
            lines.write(json.dumps({"set": s, "seed": seed, "wall_s": wall,
                                    **rec}) + "\n")
            lines.flush()
            print(f"set {s} seed {seed} wall {wall:.0f}s correct "
                  f"{rec['correct']} attempted {rec['attempted']} failed "
                  f"{rec['failed']} " + " ".join(
                      f"{k}={v['value']:.5g}"
                      for k, v in rec["metrics"].items())
                  + f" mem={rec['device']['memory_peak_bytes'] / 1e9:.2f}GB",
                  flush=True)
            for k, v in rec["metrics"].items():
                values.setdefault(k, []).append(v["value"])
        for k, xs in values.items():
            if len(xs) >= 2:
                q1, _q2, q3 = statistics.quantiles(xs, n=4)
                med = statistics.median(xs)
                print(f"  set {s} {k}: median {med:.6g} spread "
                      f"{(q3 - q1) / med:.4%} min {min(xs):.6g} max "
                      f"{max(xs):.6g} n {len(xs)}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
