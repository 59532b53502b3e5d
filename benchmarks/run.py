#!/usr/bin/env python3
"""One process, one cell, once.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, prints earlier lines of
detail and, LAST, one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (``--trace 0``: the cell's
end-to-end metrics; ``--trace 1``: its per-layer metrics, with
``busy_s`` and ``window_s`` in ``device`` and a ``breakdown``).

Where JAX finds no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result line. See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _info(obj) -> None:
    print(json.dumps(obj), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmarks.lib import harness
    from benchmarks.lib.device import (CompileMeter, NoAccelerator,
                                       device_record, require_tpu)

    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    driver = bench.driver(cell.spec["driver"])

    # the compile cache before the first use of the backend: where
    # JAX_COMPILATION_CACHE_DIR is set JAX follows it, else it is the
    # fixed <checkout>/.jax_cache (core/runtime.py)
    from quintnet_tpu.core.runtime import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    try:
        devices = require_tpu(cell.chips)
    except NoAccelerator as e:
        print(e.msg, file=sys.stderr)
        return 3
    meter = CompileMeter()
    scratch = os.path.join(ROOT, ".bench_out", cell.name)
    os.makedirs(scratch, exist_ok=True)
    ctx = harness.RunContext(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, meter=meter,
        t_process_start=T_PROCESS_START, scratch=scratch, info=_info)
    rec = driver.run(ctx)

    compile_s, compiles, hits, misses = meter.read()
    _info({"run": {"workload": cell.name, "seed": args.seed,
                   "seconds": args.seconds, "trace": args.trace,
                   "setup_s": rec["setup_s"], "compile_cache": cache_dir,
                   "compile_or_load_s": compile_s, "programs": compiles,
                   "cache_hits": hits, "cache_misses": misses,
                   "wall_s": time.perf_counter() - T_PROCESS_START}})
    device = device_record(devices)
    rec["context"]["memory_peak_bytes"] = device["memory_peak_bytes"]
    _info({"memory_stats": devices[0].memory_stats()})
    line = {"correct": all(c["ok"] for c in rec["checks"].values()),
            "attempted": int(rec["attempted"]),
            "failed": int(rec["failed"])}
    if args.trace:
        reduced = rec["context"].get("trace")
        if reduced is None:
            print("the traced stretch shows no operation on the device",
                  file=sys.stderr)
            return 4
        line["metrics"] = harness.per_layer_values(bench, cell,
                                                   rec["context"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        _info({"trace": {"modules": reduced["modules"],
                         "chips": reduced["chips"]}})
    else:
        values = {**rec["end_to_end"], "setup_s": rec["setup_s"]}
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]),
                        "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
