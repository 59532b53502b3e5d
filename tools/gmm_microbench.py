"""Time the dropless mixture's grouped matmul at the three MoE cells'
shapes, every candidate on the same operands (chip only):

    chiprun -- python tools/gmm_microbench.py [--cells kda,latent,window]
        [--variants ragged,library:128:0:0,own:128:0] [--reps 40]

A shape is one of a sparse layer's three calls as a serving program
makes it: a STACK of ``layers x held`` experts ``[K, N]`` bf16 of which
one layer's have rows, ``rows = S x top_k`` routings sorted by expert
of which only those that landed on held experts are in a group (uniform
routing over the router's whole range: 1,536 routings of a KDA decode
step leave 390 rows on 106 of a layer's 128 held experts). ``decode``
is the cell's slots x 8, ``b1024`` a 1,024-token chunk's 8,192; ``up``
is ``[d, f]`` (gate and up), ``down`` ``[f, d]``.

Variants: ``ragged`` — ``lax.ragged_dot`` over ``layers x held`` groups
padded with zeros, what nn/moe ran until PR 38; ``library:tm:tk:tn`` —
``jax.experimental.pallas.ops.tpu.megablox.gmm`` over the same flat
stack and padded sizes (0 = the whole dimension); ``own:tm:tn`` —
ops/grouped_matmul (0 = the module's own choice). One JSON line a
(shape, variant): ms a call (the group metadata computed inside the
timed program, as a serving program does), the touched experts' bytes,
the share of 819 GB/s they crossed at, and the largest difference from
``ragged`` over the groups' rows. Also ``chiprun_out/gmm_microbench.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# cell -> (sparse layers, experts held, router's experts, d, f, slots)
CELLS = {"kda": (4, 128, 512, 2560, 768, 192),
         "latent": (4, 16, 256, 7680, 2048, 64),
         "window": (4, 256, 256, 2048, 512, 48)}
TOP_K = 8
HBM_GBPS = 819.0


def group_sizes(rng, tokens: int, held: int, experts: int):
    """Rows a held expert gets when ``tokens`` tokens each choose
    ``TOP_K`` distinct experts of ``experts`` uniformly."""
    import numpy as np

    picks = np.argsort(rng.random((tokens, experts)), axis=1)[:, :TOP_K]
    return np.bincount(picks[picks < held], minlength=held).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="kda,latent,window")
    ap.add_argument("--programs", default="decode,b1024")
    ap.add_argument("--sides", default="up,down")
    ap.add_argument("--variants",
                    default="ragged,library:128:0:0,own:0:0")
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from quintnet_tpu.core.runtime import enable_compilation_cache
    from quintnet_tpu.ops import grouped_matmul as gm

    if jax.default_backend() != "tpu":
        print("gmm_microbench: no TPU", file=sys.stderr)
        return 3
    enable_compilation_cache()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "gmm_microbench.jsonl"), "a")
    rng = np.random.default_rng(args.seed)
    dt = jnp.bfloat16

    def build(variant, rows, L, G, K, N):
        kind, *nums = variant.split(":")
        nums = [int(n) for n in nums]

        def padded(sizes, layer):
            # the stack as ``layers x held`` groups, one layer's live
            return lax.dynamic_update_slice(
                jnp.zeros((L * G,), sizes.dtype), sizes, (layer * G,))

        if kind == "ragged":
            def fn(x, w, sizes, layer):
                return lax.ragged_dot(x, w.reshape(-1, K, N),
                                      padded(sizes, layer),
                                      preferred_element_type=jnp.float32)
        elif kind == "library":
            import importlib

            # (the package re-exports the function under the module's
            # name)
            lib = importlib.import_module(
                "jax.experimental.pallas.ops.tpu.megablox.gmm")
            tm, tk, tn = nums
            tiling = (tm, tk or K, tn or N)

            def fn(x, w, sizes, layer):
                return lib.gmm(x, w.reshape(-1, K, N), padded(sizes, layer),
                               preferred_element_type=jnp.float32,
                               tiling=tiling)
        elif kind == "own":
            tm = nums[0] or gm.row_tile_for(rows, dt)
            tn = nums[1] or None

            def fn(x, w, sizes, layer):
                visits = gm.group_visits(sizes, rows=rows, row_tile=tm)
                return gm.grouped_matmul(x, w, visits, layer=layer,
                                         row_tile=tm, column_tile=tn)
        else:
            raise SystemExit(f"unknown variant {variant!r}")
        return jax.jit(fn)

    for cell in args.cells.split(","):
        L, G, E, d, f, slots = CELLS[cell]
        for program in args.programs.split(","):
            tokens = slots if program == "decode" else 1024
            rows = tokens * TOP_K
            sizes_np = group_sizes(rng, tokens, G, E)
            sizes = jnp.asarray(sizes_np)
            live = int(sizes_np.sum())
            touched = int((sizes_np > 0).sum())
            for side in args.sides.split(","):
                K, N = (d, f) if side == "up" else (f, d)
                kx, kw = jax.random.split(jax.random.PRNGKey(args.seed))
                x = jax.random.normal(kx, (rows, K), jnp.float32).astype(dt)
                w = (jax.random.normal(kw, (L, G, K, N), jnp.float32)
                     / np.sqrt(K)).astype(dt)
                layer = jnp.asarray(L - 2, jnp.int32)
                gbytes = touched * K * N * 2 / 1e9
                want = None
                for variant in args.variants.split(","):
                    line = {"cell": cell, "program": program, "side": side,
                            "rows": rows, "live_rows": live, "held": G,
                            "touched": touched, "K": K, "N": N,
                            "variant": variant,
                            "touched_GB": gbytes}
                    try:
                        fn = build(variant, rows, L, G, K, N)
                        got = jax.block_until_ready(fn(x, w, sizes, layer))
                        if want is None:
                            want = got
                        line["max_abs_diff"] = float(jnp.max(jnp.abs(
                            got[:live] - want[:live]))) if live else 0.0
                        for _ in range(3):
                            jax.block_until_ready(fn(x, w, sizes, layer))
                        t0 = time.perf_counter()
                        for _ in range(args.reps):
                            got = fn(x, w, sizes, layer)
                        jax.block_until_ready(got)
                        ms = 1e3 * (time.perf_counter() - t0) / args.reps
                        line.update(ms=ms, roofline_pct=100.0 * gbytes
                                    / HBM_GBPS / (ms / 1e3))
                    except Exception as e:      # a variant the compiler
                        line["error"] = repr(e)[:300]   # refuses: say so
                    print(json.dumps(line), flush=True)
                    out.write(json.dumps(line) + "\n")
                del x, w
    out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
