"""What a serving step's device-to-host reads cost after its program,
one array at a time against one ``jax.device_get`` (chip only):

    python tools/d2h_microbench.py [--program-ms 2,10,30] [--leaves 8]
        [--extra 2] [--slots 128] [--reps 40]

The program is a chain of bf16 matmuls run for about ``--program-ms``
(calibrated on the chip first), each call fed the last one's result so
that calls queue as a serving loop's do. It also returns what a MoE
step returns beside its pools: ``--leaves`` small arrays shaped like
the routing-stats dict (a per-expert count vector and scalars), and
``--extra`` arrays shaped like the decode's sampled tokens ``[slots]``
and keys ``[slots, 2]``. Every output depends on the chain's last
result, so none is ready before the program ends. A mode, a rep:
dispatch, then

- ``block``: ``block_until_ready`` on the outputs (the program alone);
- ``serial``: ``np.asarray`` of each output in turn (the tree of
  stats by ``jax.tree.map``), what the engine did before its reads
  were gathered;
- ``fetch``: one ``jax.device_get`` of them all;
- ``async_fetch``: ``copy_to_host_async()`` on each output right after
  the dispatch, then one ``jax.device_get``.

One JSON line a (program length, mode): the median and quartiles of
ms a rep from the dispatch to the last byte on the host, and beside
``serial`` its cost over ``block`` a read.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

WIDTH = 2048
EXPERTS = 64


def build(n_iters: int, leaves: int, extra: int, slots: int):
    import jax
    import jax.numpy as jnp

    def d2h_probe(x, w):
        x = jax.lax.fori_loop(
            0, n_iters, lambda _, y: (y @ w) * jnp.bfloat16(0.5), x)
        s = jnp.sum(x[:8].astype(jnp.float32))
        stats = {"expert_tokens": jnp.full((EXPERTS,), s)}
        for i in range(leaves - 1):
            stats[f"s{i}"] = s + i
        outs = [jnp.full((slots,), s).astype(jnp.int32),
                jnp.full((slots, 2), s).astype(jnp.uint32)][:extra]
        return x, stats, outs

    return jax.jit(d2h_probe)


def timed(fn, x, w, mode: str, reps: int):
    import jax
    import numpy as np

    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        x, stats, outs = fn(x, w)
        if mode == "block":
            jax.block_until_ready((stats, outs))
        elif mode == "serial":
            jax.tree.map(np.asarray, stats)
            for o in outs:
                np.asarray(o)
        else:
            if mode == "async_fetch":
                for leaf in jax.tree.leaves((stats, outs)):
                    leaf.copy_to_host_async()
            jax.device_get((stats, outs))
        ms.append((time.perf_counter() - t0) * 1e3)
    return x, ms


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--program-ms", default="2,10,30")
    ap.add_argument("--leaves", type=int, default=8)
    ap.add_argument("--extra", type=int, default=2)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--reps", type=int, default=40)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"no TPU: {dev.platform}"}))
        return 3
    x = jnp.ones((WIDTH, WIDTH), jnp.bfloat16)
    w = jnp.eye(WIDTH, dtype=jnp.bfloat16)
    # calibrate: ms an iteration of the chain, from a 64-iteration call
    probe = build(64, a.leaves, a.extra, a.slots)
    x, ms = timed(probe, x, w, "block", 10)
    per_iter = statistics.median(ms[2:]) / 64
    reads = a.leaves + a.extra
    for target in (float(t) for t in a.program_ms.split(",")):
        fn = build(max(1, round(target / per_iter)), a.leaves, a.extra,
                   a.slots)
        x, _ = timed(fn, x, w, "block", 3)              # compile + warm
        seen = {}
        # interleave the modes so that drift lands on all of them
        for _ in range(2):
            for mode in ("block", "serial", "fetch", "async_fetch"):
                x, ms = timed(fn, x, w, mode, a.reps // 2)
                seen.setdefault(mode, []).extend(ms)
        base = statistics.median(seen["block"])
        for mode, ms in seen.items():
            q1, med, q3 = statistics.quantiles(ms, n=4)
            row = {"program_ms": target, "mode": mode, "reads": reads,
                   "median_ms": med, "q1_ms": q1, "q3_ms": q3,
                   "over_block_ms": med - base,
                   "device": dev.device_kind}
            if mode == "serial":
                row["ms_a_read"] = (med - base) / reads
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
