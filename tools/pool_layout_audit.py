#!/usr/bin/env python3
"""What the chip's compiler does with the KV pool: compile a serving
cell's OWN jitted programs for a DESCRIBED ``v5e:2x2`` topology and
read, per program,

- the ENTRY layout the compiler gives every per-sequence buffer (the
  pool's k and v, and a recurrent family's state buffers): row-major
  ``{2,1,0}`` means a token's features are contiguous; anything else
  that the slot dim is minor, and every read re-lays it;
- every ``copy`` / ``transpose`` at least as large as ONE LAYER's slice
  of the pool — a gathered view is larger — with the computation it
  sits in (a loop body's run once a layer); a ``transpose`` that
  permutes nothing (``dimensions={0,1,..}``, as the gather fusions
  carry) moves no byte and is not listed;
- every ``fusion`` or ``dynamic-slice`` of the programs themselves
  (not of a fusion's body) whose output is at least ONE LAYER's slice
  and less than a whole buffer — a slice the compiler MATERIALISES
  before the op that reads it, which a ``copy`` count never sees
  (``big_slices``; the window families' ``window_gather`` of every
  slot's ring is one a layer and buffer, PR 39);
- every call of the per-row walk (``paged_walk_attention``: decode
  and verify on a bf16/f16 pool read each row's live blocks of the
  carried pool, or its ring of a window store, in place) with what
  FEEDS its pool-sized operands (and their dims): the
  loop's carry or the in-place ``kv_write`` scatter is the pool itself;
  anything else — a ``copy``, a slice of a layer, a fusion outside
  ``kv_write`` — is a layer- or view-sized copy next to the call, and
  is named under ``copies_beside``;
- the sum of the compiler's own ``estimated_cycles`` over the layer
  loop's body (a guide, not a time; gathers carry no estimate);
- the bytes the compiler plans (arguments, temporaries, aliased, live).

    JAX_PLATFORMS=cpu python tools/pool_layout_audit.py \
        --workload gpt2-xl.serve-chat-sat [--programs serve_decode ...] \
        [--hlo-dir /root/scratch/hlo]

``--read-hlo FILE --pool-shape L,SLOTS,F`` reads a compiled
program's text that another tool wrote (benchmarks/tools/
aot_sizes_window_moe.py ``--hlo-dir``: the window cell compiled from
shapes alone, 8.6 GB of weights never built) the same way.

Nothing runs and no chip is needed (benchmarks/tools/aot_sizes.py is
the same kind of compile, for sizing); the weights are made at the
cell's real size on the CPU, so a run takes minutes. One JSON line a
program. The bar every paged program is held to (PERF.md, PR 28 and
30): the pools' layout row-major, and ``big_copies`` holding nothing
pool- or view-shaped. A decode or verify program of a bf16 pool
gathers no view at all (PR 34: ``row_walks`` one a layer loop, its
``copies_beside`` empty); a prefill bucket splits the view it gathers
inside its fusions, and the compiler has written no copy for it. What
every program still lists is the f32 token table re-laid for the
logits (``f32[vocab, width]``, outside the layer loop: PERF.md
section 7).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2.0 ** 30

_ITEM = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
         "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "pred": 1, "s64": 8,
         "u64": 8, "f64": 8}
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+"
                    r"([\w\-]+)\(")
_CYCLES = re.compile(r'"estimated_cycles":"?(\d+)')


def _permutes_nothing(line: str) -> bool:
    m = re.search(r"\bdimensions=\{([\d,]*)\}", line)
    return bool(m) and m.group(1) == ",".join(
        str(i) for i in range(m.group(1).count(",") + 1))


def _first_shape(text):
    m = _SHAPE.search(text)
    if not m or m.group(1) not in _ITEM:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    n = 1
    for d in dims:
        n *= d
    return m.group(1), dims, (m.group(3) or ""), n * _ITEM[m.group(1)]


def read_hlo(text: str, buffers: dict, layer_bytes: int) -> dict:
    """``buffers``: name -> shape of every per-sequence buffer."""
    want = {}
    for n, s in buffers.items():        # k and v share a shape
        want[tuple(s)] = "/".join(filter(None, (want.get(tuple(s)), n)))
    layouts, copies, cycles, bodies = {}, [], {}, set()
    made, walks = {}, []      # instruction -> (opcode, scope, bytes)
    slices, fused, whole = [], set(), []
    comp, entry = None, False
    for line in text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$",
                        line)
        if head:
            comp, entry = head.group(2), bool(head.group(1))
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, result, op = m.groups()
        if op == "while":
            bodies.update(re.findall(r"body=%?([\w.\-]+)", line))
        if op == "fusion":
            fused.update(re.findall(r"calls=%?([\w.\-]+)", line))
        shape = _first_shape(result)
        scope = re.search(r'op_name="([^"]*)"', line)
        made[name] = (op, "/".join(scope.group(1).split("/")[-2:])
                      if scope else "", shape[3] if shape else 0,
                      list(shape[1]) if shape else [])
        if op == "custom-call" and "paged_walk_attention" in line:
            walks.append((name, comp, re.findall(
                r"%([\w.\-]+)", line.split("custom-call(", 1)[1]
                .split(")", 1)[0])))
        cyc = _CYCLES.search(line)
        if cyc:
            cycles[comp] = cycles.get(comp, 0) + int(cyc.group(1))
        if shape is None:
            continue
        dtype, dims, layout, nbytes = shape
        if entry and op == "parameter" and dims in want:
            whole.append(nbytes)
            layouts.setdefault(want[dims], []).append(
                layout.split(":")[0] + "}" if ":" in layout else layout)
        if op in ("fusion", "dynamic-slice") and nbytes >= layer_bytes:
            slices.append({"op": name, "in": comp, "dims": list(dims),
                           "scope": made[name][1], "bytes": nbytes})
        if (op in ("copy", "copy-start", "transpose")
                and nbytes >= layer_bytes and not _permutes_nothing(line)):
            copies.append({"op": name, "in": comp, "dims": list(dims),
                           "shape": f"{dtype}{list(dims)}{layout}",
                           "MB": round(nbytes / 1e6, 1)})
    row_walks = []
    for name, where, operands in walks:
        fed = [{"operand": o, "op": made[o][0], "scope": made[o][1],
                "dims": made[o][3]}
               for o in operands if made.get(o, ("", "", 0))[2] >= layer_bytes]
        row_walks.append({
            "call": name, "in": where, "pool_operands": fed,
            # the carry itself, or the scatter that wrote it in place
            "copies_beside": [f for f in fed if not (
                f["op"] in ("get-tuple-element", "parameter")
                or (f["op"] == "fusion" and "kv_write" in f["scope"]))]})
    # the layer loop is the while whose body the compiler prices highest
    return {"entry_layouts": layouts, "big_copies": copies,
            "big_slices": [
                {k: v for k, v in c.items() if k != "bytes"}
                | {"MB": round(c["bytes"] / 1e6, 1)} for c in slices
                if c["in"] not in fused and c["bytes"] < min(
                    whole, default=0)],
            "row_walks": row_walks,
            "loop_body_Mcyc": max(
                (round(cycles.get(b, 0) / 1e6, 3) for b in bodies),
                default=None)}


def _bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    rec = {"arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes,
           "temporaries": m.temp_size_in_bytes,
           "aliased": m.alias_size_in_bytes}
    rec["live"] = (rec["arguments"] + rec["outputs"] + rec["temporaries"]
                   - rec["aliased"])
    return {k: round(v / GIB, 3) for k, v in rec.items()}


def programs(driver, eng, spec):
    """(name, jitted fn, args) of the engine's warmup programs and the
    cell's check programs."""
    import numpy as np

    for sentinel, call_args in eng._warmup_calls():
        yield sentinel.fn.__name__, sentinel.fn, call_args
    c = spec["correctness"]
    pools = eng.pool.caches()
    if hasattr(driver, "check_programs"):          # serve_hybrid
        prefill, decode = driver.check_programs(eng)
        bucket = max(c["chunk_calls"])
        row = np.zeros((eng.table_width,), np.int32)
        yield (f"check_prefill_b{bucket}", prefill,
               (eng.params, *pools, np.zeros((1, bucket), np.int32),
                np.int32(0), np.int32(1), row, np.int32(0)))
        yield ("check_decode", decode,
               (eng.params, *pools, eng._tok, eng._pos, eng._tables,
                np.arange(len(c["prompt_lens"]))))
    else:
        S, half = len(c["prompt_lens"]), int(c["half_width"])
        vec = np.zeros((S,), np.int32)
        yield ("check_verify", driver.verify_program(eng),
               (eng.params, *pools, np.zeros((S, half), np.int32), vec,
                vec, np.zeros((S, eng.table_width), np.int32)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--programs", nargs="*", default=None,
                    help="program names (serve_decode, serve_prefill_b16, "
                         "check_verify, ...); default: decode, the "
                         "smallest and the largest prefill bucket and "
                         "the check's programs")
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--max-slots", type=int, default=None)
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--read-hlo", default=None, metavar="FILE",
                    help="read this compiled program's text instead of "
                         "compiling (with --pool-shape)")
    ap.add_argument("--pool-shape", default=None, metavar="L,SLOTS,F")
    args = ap.parse_args()
    if args.read_hlo:
        shape = tuple(int(d) for d in args.pool_shape.split(","))
        with open(args.read_hlo) as f:
            print(json.dumps({
                "cell": args.workload, "hlo": args.read_hlo,
                "buffers": {"k": list(shape), "v": list(shape)},
                **read_hlo(f.read(), {"k": shape, "v": shape},
                           2 * shape[1] * shape[2])}), flush=True)
        return 0

    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import harness

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    driver = bench.driver(cell.spec["driver"])
    spec = json.loads(json.dumps(cell.spec))
    if args.num_blocks:
        spec["engine"]["num_blocks"] = args.num_blocks
    if args.max_slots:
        spec["engine"]["max_slots"] = args.max_slots
    if hasattr(driver, "GraniteHybridConfig"):
        cfg = driver.GraniteHybridConfig.from_dict(cell.config)
    else:
        from quintnet_tpu.models.gpt2 import GPT2Config

        cfg = GPT2Config.from_dict(cell.config)
    params = driver.make_params(cfg, spec["engine"]["weights_dtype"], 0)
    eng = driver.build_engine(spec, cfg, params)
    pool = eng.pool
    names = ["k", "v", "k_scale", "v_scale"] if pool.policy.scaled else (
        ["k", "v", "ssm", "conv"] if pool.state is not None else ["k", "v"])
    buffers = {n: tuple(c.shape) for n, c in zip(names, pool.caches())}
    layer_bytes = int(pool.k.nbytes // pool.k.shape[0])

    def sds(x):
        x = jnp.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=chip)

    todo = list(programs(driver, eng, spec))
    if args.programs is None:
        pre = sorted((n for n, _f, _a in todo if "serve_prefill_b" in n),
                     key=lambda n: int(n.rsplit("b", 1)[1]))
        keep = {"serve_decode", pre[0], pre[-1]} | {
            n for n, _f, _a in todo if n.startswith("check_")}
    else:
        keep = set(args.programs)
    for name, fn, call_args in todo:
        if name not in keep:
            continue
        compiled = fn.lower(*jax.tree.map(sds, call_args)).compile()
        text = compiled.as_text()
        if args.hlo_dir:
            os.makedirs(args.hlo_dir, exist_ok=True)
            with open(os.path.join(args.hlo_dir,
                                   f"{cell.name}.{name}.hlo.txt"), "w") as f:
                f.write(text)
        print(json.dumps({
            "cell": cell.name, "program": name,
            "num_blocks": pool.num_blocks, "max_slots": eng.max_slots,
            "buffers": {n: list(s) for n, s in buffers.items()},
            "layer_slice_MB": round(layer_bytes / 1e6, 1),
            **read_hlo(text, buffers, layer_bytes),
            "GiB": _bytes(compiled)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
