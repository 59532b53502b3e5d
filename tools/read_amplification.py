"""What a serving cell's decode and verify programs READ of the paged
pool against what their rows hold, on the chip (chip only):

    chiprun -- python tools/read_amplification.py --workload <cell> \\
        --seed <n> [--seconds 20]

One traced run of the cell's own driver through
``benchmarks/tools/hybrid_probe.py --keep-trace`` (the by-scope tables
of PERF.md section 5 land in ``chiprun_out/bench/<cell>.scopes.json`` as
they always did), then the engine's step ring: over the steps that
decoded, ``attended_rows`` (pool positions x layers the paged layers'
attention read: a row rounded up to the walk's key block, or to the
table's width where a view is still gathered) over ``context_tokens x
paged_layers`` (what the rows held). 1.0 is a program that reads what
is live and nothing else; the gathered form read 2.5 (GPT-2 XL) and 3.4
(the window cell) times that (PERF.md section 6, PR 34). For a family
with the dropless mixture also ``tile_visits_over_touched``: the
grouped-matmul kernel's (row tile, expert) visits over the (layer,
expert) pairs that received a row (nn/moe._expert_rows, PR 38), of the
decode program's steps (``decode``) and of the steps that prefilled
(``with_prefill``: every program of such a step) — 1.0 where each
touched expert's weights meet the matrix unit once. One JSON line,
also written to ``chiprun_out/bench/<cell>.reads.json``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()

    probe = _load(os.path.join(ROOT, "benchmarks", "tools",
                               "hybrid_probe.py"), "hybrid_probe")
    view = _load(os.path.join(ROOT, "tools", "trace_view.py"), "trace_view")
    rc = probe.keep_trace(args.workload, args.seed, args.seconds)
    # the probe dropped its engine when it returned: the registry still
    # gives the ring registered last (obs/recorder.live)
    from quintnet_tpu.obs import recorder

    rings = recorder.live()
    if rc or len(rings) != 1:
        return rc or 1
    (ring,) = rings
    layers = ring.static["paged_layers"]
    counted = [r for r in ring.snapshot()
               if "attended_rows" in r["attrs"] and r["context_tokens"]]
    ratio, steps = view.read_amplification(counted, layers)

    def a_step(value):
        return sum(value(r) for r in counted) / max(steps, 1)

    line = {"workload": args.workload, "seed": args.seed,
            "read_amplification": ratio, "steps": steps,
            "paged_layers": layers,
            "attended_rows_a_step": a_step(
                lambda r: r["attrs"]["attended_rows"]),
            "context_tokens_a_step": a_step(lambda r: r["context_tokens"]),
            "decoding_a_step": a_step(lambda r: r["decoding"]),
            "max_slots": ring.static["max_slots"]}
    mixed = [r for r in ring.snapshot()
             if r["attrs"].get("experts_touched")]
    if mixed:
        def visits_over_touched(records, prefix=""):
            touched = sum(r["attrs"][prefix + "experts_touched"]
                          for r in records)
            return sum(r["attrs"][prefix + "expert_tile_visits"]
                       for r in records) / touched if touched else None

        line["tile_visits_over_touched"] = {
            "decode": visits_over_touched(mixed, "decode_"),
            "with_prefill": visits_over_touched(
                [r for r in mixed if r["attrs"]["experts_touched"]
                 > r["attrs"]["decode_experts_touched"]]),
            "steps": len(mixed)}
    with open(os.path.join(probe.OUT, args.workload + ".reads.json"),
              "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
