"""Causal attention forward + backward at one [B, H, S, Dh], every way
this repo can run it, timed on the chip (chip only: exits 3 elsewhere).

The numbers behind ``nn/attention.local_attention_path``'s constants
(PERF.md section 6, PR 32). A micro-benchmark finds the geometry; the
training cells decide (PERF.md, PR 26's lesson). One JSON line a
variant: ``fwd_ms``, ``fwd_bwd_ms`` (a ``jax.grad`` of a weighted sum),
``layer_ms`` = fwd + fwd_bwd — what a layer under ``remat`` costs —
and the largest deviation of the output and of dq from XLA's ``sdpa``
on the same bf16 inputs, as a share of the largest magnitude.

    chiprun -- python tools/attn_microbench.py --shape 32,12,1024,64 \
        --variants sdpa,resident:256:256,streamed:512,library:512
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _variant(spec: str, causal: bool):
    """'name:a:b:c' -> attention(q, k, v) for [B, H, S, Dh]."""
    name, *nums = spec.split(":")
    nums = [int(n) for n in nums]
    if name == "sdpa":
        from quintnet_tpu.nn.attention import sdpa

        return lambda q, k, v: sdpa(q, k, v, causal=causal)
    if name == "resident":      # block_q : block_k [: transposed forward]
        from quintnet_tpu.ops.pallas_attention import \
            resident_flash_attention

        return lambda q, k, v: resident_flash_attention(q, k, v, causal,
                                                        *nums)
    if name == "streamed":      # one square tile size
        from quintnet_tpu.ops.pallas_attention import pallas_flash_attention

        return lambda q, k, v: pallas_flash_attention(q, k, v, causal,
                                                      nums[0], nums[0])
    if name == "library":       # the kernel JAX ships, one tile size
        from jax.experimental.pallas.ops.tpu import flash_attention as lib

        def fn(q, k, v):
            t = min(nums[0], q.shape[2])
            sizes = lib.BlockSizes(
                block_q=t, block_k_major=t, block_k=t, block_b=1,
                block_q_major_dkv=t, block_k_major_dkv=t, block_k_dkv=t,
                block_q_dkv=t, block_k_major_dq=t, block_k_dq=t,
                block_q_dq=t)
            return lib.flash_attention(q, k, v, causal=causal,
                                       sm_scale=q.shape[-1] ** -0.5,
                                       block_sizes=sizes)
        return fn
    raise SystemExit(f"unknown variant {spec!r}")


def _time(fn, args, iters: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="32,12,1024,64")
    ap.add_argument("--variants", default="sdpa,resident:256:256")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("attn_microbench: no TPU; a time from any other platform "
              "is not a device number", file=sys.stderr)
        return 3
    b, h, s, d = (int(x) for x in args.shape.split(","))
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, w = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                  for kk in keys)
    # a matmul pass over the (causal half) square, 2 flops a
    # multiply-add: forward 2 passes, backward 5
    pass_flops = 2 * b * h * s * s * d / (2 if args.causal else 1)
    ref = None
    for spec in args.variants.split(","):
        line = {"variant": spec, "shape": [b, h, s, d],
                "causal": bool(args.causal),
                "device_kind": jax.devices()[0].device_kind}
        try:
            attn = _variant(spec, bool(args.causal))
            fwd = jax.jit(attn)
            grad = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(
                    (attn(q, k, v) * w).astype(jnp.float32)),
                argnums=(0, 1, 2)))
            line["fwd_ms"] = _time(fwd, (q, k, v), args.iters)
            line["fwd_bwd_ms"] = _time(grad, (q, k, v), args.iters)
            line["layer_ms"] = line["fwd_ms"] + line["fwd_bwd_ms"]
            line["layer_tflops"] = 9 * pass_flops / line["layer_ms"] / 1e9
            got = (fwd(q, k, v).astype(jnp.float32),
                   grad(q, k, v)[0].astype(jnp.float32))
            if ref is None:
                ref = got
            line["o_err"], line["dq_err"] = (
                float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
                for g, r in zip(got, ref))
        except Exception as e:  # noqa: BLE001 — a refused variant is a row
            line["error"] = f"{type(e).__name__}: {e}"[:400]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
