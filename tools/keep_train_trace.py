#!/usr/bin/env python3
"""One traced run of a TRAINING cell's driver with the xplane kept long
enough to reduce it by scope — ``benchmarks/tools/hybrid_probe.py
--keep-trace`` for the serving cells, this for the training ones (chip
only: the cell's driver refuses any other platform).

    chiprun [--chips 4] -- python tools/keep_train_trace.py \
        --workload gpt2-124m.train-packed-s1024 --seed 2147484123 \
        [--root _parent] [--tag parent] [--seconds 30]

``--root``: the checkout whose program and benchmark run (default: this
one; a parent unpacked by ``git archive`` for the "before" column).
Writes ``chiprun_out/bench/<cell>.<tag>.scopes.json``: the tables of
``tools/trace_view.py --xplane`` (device own-time by scope, programs,
idle by span, collectives on a mesh), the run's end-to-end and per-layer
numbers and its check, and the compiled step's instructions under
``attn/sdpa`` — what runs in that scope, by name. The xplane is deleted.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--tag", default="change")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    out = os.path.join(HERE, "chiprun_out", "bench")
    os.makedirs(out, exist_ok=True)
    sys.path.insert(0, root)
    os.chdir(root)

    import jax
    import jax.numpy as jnp

    from benchmarks.lib import harness, trace_reduce
    from benchmarks.lib.device import CompileMeter, require_tpu
    from quintnet_tpu.core.runtime import enable_compilation_cache
    from quintnet_tpu.obs.scopes import write_scope_maps

    enable_compilation_cache()
    bench = harness.Bench(root)
    cell = bench.cell(args.workload)
    driver = bench.driver("train")
    devices = require_tpu(int(cell.chips))
    scratch = os.path.join(root, ".bench_out", cell.name)
    kept = os.path.join(scratch, "kept_trace")
    shutil.rmtree(kept, ignore_errors=True)

    loops, in_scope = [], []
    plain_step = driver._Loop.step

    def step(self, batch=None):      # remember the loop: stop() needs
        loops[:] = [self]            # its trainer, state and batches
        return plain_step(self, batch)

    driver._Loop.step = step

    def stop(self):
        jax.profiler.stop_trace()
        shutil.copytree(self.dir, kept)
        lp = loops[0]
        x, y = next(lp.batches)
        b = lp.strategy.shard_batch((jnp.asarray(x), jnp.asarray(y)),
                                    lp.model)
        text = lp.trainer.step_fn.fn.lower(
            lp.params, lp.opt_state, b, 0).compile().as_text()
        write_scope_maps(kept, [text])
        in_scope.extend(line.strip()[:240] for line in text.splitlines()
                        if "attn/sdpa" in line)
        return trace_reduce.reduce_trace(trace_reduce.read_trace(
            trace_reduce.find_xplane(self.dir)))

    harness.DeviceTrace.stop = stop
    lines = []
    ctx = harness.RunContext(
        cell=cell, seed=args.seed, seconds=args.seconds, trace=True,
        devices=devices, meter=CompileMeter(), t_process_start=T0,
        scratch=scratch, info=lines.append)
    os.makedirs(scratch, exist_ok=True)
    rec = driver.run(ctx)

    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(root, "tools", "trace_view.py"))
    view = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(view)
    tables = view.xplane_tables(kept)
    shutil.rmtree(kept, ignore_errors=True)
    tables.update(
        checks=rec["checks"], end_to_end=rec["end_to_end"],
        train=lines[0].get("train") if lines else None,
        per_layer=harness.per_layer_values(bench, cell, rec["context"]),
        attn_sdpa_instructions=in_scope)
    path = os.path.join(out, f"{args.workload}.{args.tag}.scopes.json")
    with open(path, "w") as f:
        json.dump(tables, f, indent=1, default=str)
    print(json.dumps({"cell": args.workload, "tag": args.tag,
                      "end_to_end": rec["end_to_end"],
                      "per_layer": tables["per_layer"],
                      "checks": rec["checks"], "tables": path},
                     default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
