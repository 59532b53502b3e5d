#!/usr/bin/env python3
"""Size a cell without the chip: compile its programs for a DESCRIBED
``v5e:2x2`` topology and read the bytes the compiler plans.

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_sizes.py --workload <cell> \
        [--batch 8 16 32] [--num-blocks 256 384 512]

A training cell: the trainer's own jitted step (``Trainer.step_fn``) on
a mesh of described devices, per ``--batch``. A serving cell: the
ENGINE'S OWN jitted programs (every prefill bucket and the decode step,
``engine._prefills[b].fn`` / ``engine._decode.fn``) per ``--num-blocks``
(``max_slots = num_blocks / 32``), plus the logits check's verify
program. Per program it prints argument, output, temporary and aliased
bytes, and their live total ``arguments + outputs + temporaries -
aliased``, per device. It counts ONE program at a time, not what else
the process keeps on the device; nothing runs, so it says nothing
about time. A compile the chip's compiler refuses (out of memory, a
kernel it cannot tile) raises here as it would there.

Every later cell is sized the same way; the numbers go into the cell's
file under ``sizing`` and into PERF.md, tagged as a compile and never
as a chip run.
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


def _bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    rec = {"arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes,
           "temporaries": m.temp_size_in_bytes,
           "aliased": m.alias_size_in_bytes}
    rec["live"] = (rec["arguments"] + rec["outputs"] + rec["temporaries"]
                   - rec["aliased"])
    return {k: round(v / GIB, 3) for k, v in rec.items()}


def _describe(tree, sharding_of):
    import jax

    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding_of)


def size_train(bench, cell, topo, batches) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from quintnet_tpu.parallel.train_step import opt_state_specs

    driver = bench.driver("train")
    for batch in batches:
        spec = json.loads(json.dumps(cell.spec))
        spec["trainer"]["batch"] = batch
        n_dev = 1
        for d in spec["trainer"]["mesh_dim"]:
            n_dev *= d
        gcfg, model, strategy, trainer = driver.build(
            spec, cell.config, 0, devices=topo.devices[:n_dev])
        mesh = strategy.mesh
        tp = mesh.shape.get("tp", 1)
        p_specs = strategy.param_specs(model)
        params = jax.eval_shape(
            lambda k: model.to_tp_layout(model.init(k), tp),
            jax.random.key(0))
        o_specs = opt_state_specs(trainer.optimizer, params, p_specs)
        opt = jax.eval_shape(trainer.optimizer.init, params)

        def named(specs):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

        seq = int(cell.traffic["seq_len"])
        ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        b_specs = strategy.batch_partition_specs(model)
        args = (_describe(params, named(p_specs)),
                _describe(opt, named(o_specs)),
                _describe((ids, ids), named(b_specs)))
        step = trainer.step_fn.fn
        compiled = jax.jit(lambda p, o, b: step(p, o, b, 0),
                           donate_argnums=(0, 1)).lower(*args).compile()
        print(json.dumps({"cell": cell.name, "program": "train_step",
                          "batch": batch, "seq": seq,
                          "mesh": dict(mesh.shape),
                          "GiB_per_device": _bytes(compiled)}), flush=True)


def size_serve(bench, cell, topo, rungs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from quintnet_tpu.models.gpt2 import GPT2Config

    driver = bench.driver("serve")
    chip = SingleDeviceSharding(topo.devices[0])
    gcfg = GPT2Config.from_dict(cell.config)
    params = driver.make_params(gcfg, cell.spec["engine"]["weights_dtype"],
                                0)

    def sds(x):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=chip)

    for num_blocks in rungs:
        spec = json.loads(json.dumps(cell.spec))
        spec["engine"]["num_blocks"] = num_blocks
        spec["engine"]["max_slots"] = max(1, num_blocks // 32)
        eng = driver.build_engine(spec, gcfg, params)
        head = {"cell": cell.name, "num_blocks": num_blocks,
                "max_slots": eng.max_slots,
                "pool_GiB": round(eng.pool.pool_bytes / GIB, 3)}
        for name, fn, args in driver.engine_programs(eng):
            compiled = fn.lower(*jax.tree.map(sds, args)).compile()
            print(json.dumps({**head, "program": name,
                              "GiB_per_device": _bytes(compiled)}),
                  flush=True)
        p = jax.tree.map(sds, eng.params)
        pools = tuple(sds(c) for c in eng.pool.caches())
        c = spec["correctness"]
        S, half = len(c["prompt_lens"]), int(c["half_width"])
        vec = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=chip)
        compiled = driver.verify_program(eng).lower(
            p, *pools,
            jax.ShapeDtypeStruct((S, half), jnp.int32, sharding=chip),
            vec, vec,
            jax.ShapeDtypeStruct((S, eng.table_width), jnp.int32,
                                 sharding=chip)).compile()
        print(json.dumps({**head, "program": "logits_check_verify",
                          "GiB_per_device": _bytes(compiled)}), flush=True)
        del eng


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, nargs="*", default=None)
    ap.add_argument("--num-blocks", type=int, nargs="*", default=None)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies

    from benchmarks.lib import harness

    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it off
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    if cell.spec["driver"] == "train":
        size_train(bench, cell, topo,
                   args.batch or [cell.spec["trainer"]["batch"]])
    else:
        size_serve(bench, cell, topo,
                   args.num_blocks or [cell.spec["engine"]["num_blocks"]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
