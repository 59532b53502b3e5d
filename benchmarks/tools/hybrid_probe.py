#!/usr/bin/env python3
"""Two looks at a recurrent family's serving cell, on the chip:

    python benchmarks/tools/hybrid_probe.py --workload <cell> --probe <seed> [<seed> ...]
    python benchmarks/tools/hybrid_probe.py --workload <cell> --keep-trace <seed> [--seconds 20]

``--probe``: the readings the cell's two limits (``logits_tolerance``,
``state_tolerance``) are set between, per seed, with the cell's own
check AND ITS OWN LIMITS (drivers/serve_hybrid.check_logits: chunk
program, then decode program, logits and the state left in the pool
against the f32 reference; ``ok`` is the driver's own verdict) on
engines of ``--max-slots`` slots (16: a row's logits do not depend on
the number of rows, and an int8 engine beside the bf16 weights its
reference needs does not fit beside 64 slots of state):

- ``stated``: the engine as the cell states it;
- ``bf16_state``: the same engine with the SSM state rounded to bf16
  every time a program hands it back (what a bf16 state pool would
  hold between steps), passed off as the stated f32;
- ``int8_weights``: an engine serving the same weights in int8,
  against the reference on the stated bf16 ones;
- ``highest`` (with ``--highest``): the stated engine's two check
  programs traced under ``jax.default_matmul_precision("highest")`` —
  how much of ``stated`` is the matrix unit's rounding of f32
  operands, and how much is left for everything else.

The limits have to pass every ``stated`` reading and refuse the other
two (``bf16_state`` by the state's limit, ``int8_weights`` by the
logits'). One JSON line per seed, and ``chiprun_out/bench/<cell>.probe.jsonl``.

``--keep-trace``: one traced run of the cell's driver with the xplane
KEPT long enough to reduce it by scope (obs/scopes.write_scope_maps
beside it, then tools/trace_view.xplane_tables): the tables of PERF.md
section 5, written to ``chiprun_out/bench/<cell>.scopes.json``.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out", "bench")


def _setup(workload):
    from benchmarks.lib import harness
    from benchmarks.lib.device import require_tpu
    from quintnet_tpu.core.runtime import enable_compilation_cache

    enable_compilation_cache()
    bench = harness.Bench(ROOT)
    cell = bench.cell(workload)
    os.makedirs(OUT, exist_ok=True)
    return bench, cell, bench.driver(cell.spec["driver"]), require_tpu(1)


def probe(workload, seeds, slots, highest, controls, lens) -> int:
    import jax
    import jax.numpy as jnp

    bench, cell, driver, _devices = _setup(workload)
    cfg = driver.GraniteHybridConfig.from_dict(cell.config)
    spec = json.loads(json.dumps(cell.spec))
    # three engines' worth of weights do not fit beside the cell's own
    # state; a row's logits do not depend on how many rows there are
    spec["engine"].update(max_slots=slots, num_blocks=32 * slots)
    if lens:
        spec["correctness"]["prompt_lens"] = lens

    def reading(engine, seed, reference_params=None, precision=None):
        with jax.default_matmul_precision(precision):
            rec = driver.check_logits(engine, cell.config, spec, seed,
                                      reference_params=reference_params)
        return {k: rec[k] for k in (
            "ok", "max_abs_diff", "at_chunk_end", "at_last_step", "ref_std",
            "state_rel_err", "state_rel_err_max_head",
            "state_rel_err_by_layer")}

    with open(os.path.join(OUT, workload + ".probe.jsonl"), "a") as out:
        for seed in seeds:
            params = driver.make_params(
                cfg, spec["engine"]["weights_dtype"], seed)
            engine = driver.build_engine(spec, cfg, params)
            line = {"seed": seed, "prompt_lens":
                    spec["correctness"]["prompt_lens"],
                    "stated": reading(engine, seed)}
            if highest:
                line["highest"] = reading(engine, seed,
                                          precision="highest")
            if "bf16_state" in controls:
                update = engine.pool.update
                engine.pool.update = lambda k, v, ssm, conv: update(
                    k, v, ssm.astype(jnp.bfloat16).astype(jnp.float32),
                    conv)
                line["bf16_state"] = reading(engine, seed)
                del update
            del engine
            gc.collect()
            if "int8_weights" in controls:
                as_int8 = json.loads(json.dumps(spec))
                as_int8["engine"]["weights_dtype"] = "int8"
                engine = driver.build_engine(as_int8, cfg, params)
                line["int8_weights"] = reading(engine, seed,
                                               reference_params=params)
                del engine
            del params
            gc.collect()
            jax.clear_caches()
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
    return 0


def keep_trace(workload, seed, seconds) -> int:
    import jax

    from benchmarks.lib import harness, trace_reduce
    from benchmarks.lib.device import CompileMeter
    from quintnet_tpu.obs.scopes import write_scope_maps

    bench, cell, driver, devices = _setup(workload)
    kept = os.path.join(ROOT, ".bench_out", cell.name, "kept_trace")
    shutil.rmtree(kept, ignore_errors=True)
    engines = []
    build = driver.build_engine
    driver.build_engine = lambda *a: engines.append(build(*a)) or engines[-1]

    def stop(self):
        jax.profiler.stop_trace()
        shutil.copytree(self.dir, kept)
        write_scope_maps(kept, engines[-1].program_texts())
        return trace_reduce.reduce_trace(trace_reduce.read_trace(
            trace_reduce.find_xplane(self.dir)))

    harness.DeviceTrace.stop = stop
    lines = []
    ctx = harness.RunContext(
        cell=cell, seed=seed, seconds=seconds, trace=True, devices=devices,
        meter=CompileMeter(), t_process_start=T0,
        scratch=os.path.join(ROOT, ".bench_out", cell.name),
        info=lines.append)
    os.makedirs(ctx.scratch, exist_ok=True)
    rec = driver.run(ctx)
    spec_ = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(ROOT, "tools", "trace_view.py"))
    view = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(view)
    tables = view.xplane_tables(kept)
    tables["checks"] = rec["checks"]
    tables["serve"] = lines[0]["serve"] if lines else None
    tables["per_layer"] = harness.per_layer_values(bench, cell,
                                                   rec["context"])
    with open(os.path.join(OUT, workload + ".scopes.json"), "w") as f:
        json.dump(tables, f, indent=1, default=str)
    shutil.rmtree(kept, ignore_errors=True)
    print(json.dumps({k: tables[k] for k in (
        "window_ms", "programs", "device_ms_by_scope") if k in tables},
        default=str)[:6000], flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--probe", type=int, nargs="+", default=None)
    ap.add_argument("--keep-trace", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--max-slots", type=int, default=16,
                    help="slots of the probe's engines (--probe)")
    ap.add_argument("--controls", nargs="*",
                    default=["bf16_state", "int8_weights"],
                    choices=["bf16_state", "int8_weights"],
                    help="--probe: which of the controls to read")
    ap.add_argument("--prompt-lens", type=int, nargs="+", default=None,
                    help="--probe: other row lengths than the cell's "
                         "(more decode steps)")
    ap.add_argument("--highest", action="store_true",
                    help="--probe: add the 'highest' reading")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    if (args.probe is None) == (args.keep_trace is None):
        ap.error("give --probe SEEDS or --keep-trace SEED (one of them)")
    if args.probe is not None:
        return probe(args.workload, args.probe, args.max_slots,
                     args.highest, args.controls, args.prompt_lens)
    return keep_trace(args.workload, args.keep_trace, args.seconds)


if __name__ == "__main__":
    sys.exit(main())
