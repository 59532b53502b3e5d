#!/usr/bin/env python3
"""Size a linear-attention + latent-attention mixture-of-experts
serving cell without the chip: compile the ENGINE'S OWN jitted programs
(the decode step, prefill buckets) and the logits check's two programs
for a DESCRIBED ``v5e:2x2`` topology, per rung of ``max_slots``
(``num_blocks`` = ``--blocks-per-slot`` x ``max_slots``: the pool at its
worst case, every slot at ``max_seq_len``), and read the bytes the
compiler plans — as ``tools/aot_sizes_moe_mla.py`` does, the parameters
SHAPES (``jax.eval_shape`` of the driver's own initialiser: eight
gigabytes of weights are never built on this CPU), and here the latent
pool AND both per-slot state buffers.

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_sizes_kda_moe.py \
        --workload ling-3.0-flash.serve-reason-sat \
        --max-slots 192 128 [--blocks-per-slot 768] [--buckets 1024 16] \
        [--hlo-dir DIR]

Per program: argument, output, temporary and aliased bytes and their
live total per device, beside what is resident (parameters, pool).
Nothing runs: it says nothing about time. ``--hlo-dir`` keeps each
program's compiled text.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GIB = 2.0 ** 30


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--max-slots", type=int, nargs="+", required=True)
    ap.add_argument("--blocks-per-slot", type=int, default=None)
    ap.add_argument("--buckets", type=int, nargs="*", default=None)
    ap.add_argument("--hlo-dir", default=None)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import harness
    from benchmarks.tools.aot_sizes import _bytes

    class Shape(jax.ShapeDtypeStruct):
        """A leaf that is a shape, with the two things the engine asks
        of a weight while it is built."""

        def astype(self, dtype):
            return Shape(self.shape, dtype)

        @property
        def nbytes(self):
            return int(np.prod(self.shape)) * jnp.dtype(self.dtype).itemsize

    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it off
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    driver = bench.driver(cell.spec["driver"])
    cfg = driver.LingHybridConfig.from_dict(cell.config)
    params = jax.tree.map(
        lambda x: Shape(x.shape, x.dtype),
        jax.eval_shape(lambda: driver.make_params(
            cfg, cell.spec["engine"]["weights_dtype"], 0)))

    def sds(x):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=chip)

    def report(head, name, fn, call_args):
        compiled = fn.lower(*jax.tree.map(sds, call_args)).compile()
        print(json.dumps({**head, "program": name,
                          "GiB_per_device": _bytes(compiled)}), flush=True)
        if args.hlo_dir:
            os.makedirs(args.hlo_dir, exist_ok=True)
            with open(os.path.join(
                    args.hlo_dir,
                    f"{head['max_slots']}.{name}.hlo.txt"), "w") as f:
                f.write(compiled.as_text())

    per_slot = args.blocks_per_slot or (
        cell.spec["engine"]["num_blocks"] // cell.spec["engine"]["max_slots"])
    for slots in args.max_slots:
        spec = json.loads(json.dumps(cell.spec))
        spec["engine"]["max_slots"] = slots
        spec["engine"]["num_blocks"] = per_slot * slots
        eng = driver.build_engine(spec, cfg, params)
        pool = eng.pool
        # the pool and the state as shapes too, from here on
        for name in ("k", "ssm", "conv"):
            buf = getattr(pool, name)
            setattr(pool, name, Shape(buf.shape, buf.dtype))
        head = {"cell": cell.name, "max_slots": slots,
                "num_blocks": pool.num_blocks,
                "params_GiB": round(sum(
                    x.nbytes for x in jax.tree.leaves(eng.params)) / GIB, 3),
                "pool_GiB": round(pool.k.nbytes / GIB, 3),
                "state_GiB": round((pool.ssm.nbytes + pool.conv.nbytes)
                                   / GIB, 3)}
        for sentinel, call_args in eng._warmup_calls():
            name = sentinel.fn.__name__
            if (args.buckets is not None and "prefill" in name
                    and int(name.rsplit("b", 1)[1]) not in args.buckets):
                continue
            report(head, name, sentinel.fn, call_args)
        prefill, decode = driver.check_programs(eng)
        bucket = max(spec["correctness"]["chunk_calls"])
        n = len(spec["correctness"]["prompt_lens"])
        row = np.zeros((eng.table_width,), np.int32)
        report(head, f"check_prefill_b{bucket}", prefill,
               (eng.params, *pool.caches(), np.zeros((1, bucket), np.int32),
                np.int32(0), np.int32(1), row, np.int32(0)))
        report(head, "check_decode", decode,
               (eng.params, *pool.caches(), eng._tok, eng._pos, eng._tables,
                np.arange(n)))
        del eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
