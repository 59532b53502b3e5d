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

``--decode CELL[,CELL]`` times the DECODE attention of a serving cell
instead (PR 34; the numbers behind ``nn/attention.WALK_KEY_BLOCK``): one
token a row written into a bf16 pool of the cell's own shape and
scored against the row's history, every layer of the pool in one
program — the gathered view at the table's width, heads on the lane
diagonal (``gathered``), against the per-row walk of live key blocks
at each ``--key-blocks`` size (``walk:<positions>``); for ``latent``
(PR 36) the ONE pool of latent rows, 128 absorbed heads a row: the
view gathered at the table's width against the same walk with no v
pool. Row lengths are
drawn as the cell's traffic draws them; ``live_share`` is what the rows
hold of the table, ``ms_a_layer`` the program's time over its layers,
``o_err`` the largest deviation from ``gathered`` over the largest
magnitude.

    chiprun -- python tools/attn_microbench.py \
        --decode xl,hybrid,window,latent --key-blocks 128,256,512

``--rings CELL[,CELL]`` times a window cell's SLIDING layers' decode
attention: one token a row written into the cell's bf16 window
store, every ring of a layer sliced out whole and contracted on the
lane diagonal (``gathered``: the form the walk replaced) against each row's
walk of its own ring in place, at the key block ``--key-blocks`` gives
the ring (``walk:<RING_KEY_BLOCK>``; ``key_block`` is the ring's, from
``nn/attention._ring_key_block``). Rows that sit out (``lens`` 0) in
the share the cell's traffic leaves them; ``read_rows`` is what the
walk reads over what the decoding rows hold live.

    python tools/attn_microbench.py --rings wide,window \
        --key-blocks 128,256,512,1024      # on a TPU
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


# a serving cell's decode attention: rows, query heads, kv heads, head
# width, pool layers, pool blocks, table width in blocks; row lengths
# (median, sigma of a log-normal, low, high) as its traffic file draws
# prompts, with half a median output on top
DECODE_CELLS = {
    "xl": dict(rows=12, hq=25, hkv=25, dh=64, layers=48, blocks=384,
               table=64, lengths=(256, 0.6, 16, 768, 64), scale=None),
    "hybrid": dict(rows=64, hq=32, hkv=8, dh=64, layers=4, blocks=2048,
                   table=64, lengths=(256, 0.6, 16, 768, 64),
                   scale=0.0078125),
    "window": dict(rows=48, hq=48, hkv=8, dh=128, layers=2, blocks=22528,
                   table=1088, lengths=(4096, 0.7, 1024, 16384, 192),
                   scale=None),
    # ONE pool of latent rows (PR 36): hq heads of queries carried into
    # the latent space, all on the one row a position has — rank 512 +
    # rope 64 features in 640 lanes — as key and as value
    "latent": dict(rows=64, hq=128, hkv=1, dh=576, rank=512, layers=5,
                   blocks=7680, table=320,
                   lengths=(1024, 0.8, 128, 4096, 192), scale=576 ** -0.5),
}
BLOCK = 16

# a window cell's sliding layers: slots, query heads a kv head, kv heads,
# window, sliding layers, rows that decode in a step (the rest sit out),
# positions drawn as the cell's traffic draws prompts
RING_CELLS = {
    "wide": dict(rows=128, g=7, hkv=4, window=4096, layers=3, decoding=72,
                 lengths=(3072, 1.0, 128, 15360, 192)),
    "window": dict(rows=48, g=8, hkv=8, window=512, layers=3, decoding=47,
                   lengths=(4096, 0.7, 1024, 16384, 192)),
}


def _ring_lines(cell: str, key_blocks, iters: int):
    """One line a form of ``cell``'s sliding-layer decode attention
    (module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import quintnet_tpu.nn.attention as attention

    c = RING_CELLS[cell]
    rows, g, hkv, window, layers = (c[n] for n in ("rows", "g", "hkv",
                                                   "window", "layers"))
    ring, dh = window + BLOCK, 128
    rng = np.random.default_rng(40)
    med, sigma, lo, hi, out = c["lengths"]
    pos = (np.clip(rng.lognormal(np.log(med), sigma, rows), lo, hi)
           + rng.integers(0, 2 * out, rows)).astype(np.int32)
    live = np.zeros(rows, np.int32)
    live[rng.permutation(rows)[:c["decoding"]]] = 1
    pos = np.where(live > 0, pos, 0)
    keys = jax.random.split(jax.random.key(0), 5)
    shape = (layers, (rows + 1) * ring, hkv * dh)
    wk, wv = (jax.random.normal(k, shape, jnp.bfloat16) for k in keys[:2])
    q = jax.random.normal(keys[2], (rows, hkv, g, dh), jnp.float32)
    k, v = (jax.random.normal(kk, (rows, hkv, 1, dh), jnp.float32)
            for kk in keys[3:])
    positions, lens = jnp.asarray(pos)[:, None], jnp.asarray(live)

    def program(form):
        def layer(l, carry):
            wk, wv, acc = carry
            if form == "gathered":
                wk, wv = attention.window_write(
                    wk, wv, l, k, v, positions, lens, 0, ring=ring)
                held = attention._ring_positions(
                    positions[:, 0] + lens - 1, ring)[:, None, None, :]
                qp = positions[:, None, :, None]
                valid = (held >= 0) & (held <= qp) & (held > qp - window)
                o = attention._lane_diag_sdpa(
                    q, attention.window_gather(wk, l, 0, rows, ring=ring)[
                        :, None],
                    attention.window_gather(wv, l, 0, rows, ring=ring)[
                        :, None], valid, kv_heads=hkv)
            else:
                o, (wk, wv) = attention.window_attend(
                    q, k, v, (wk, wv), l, positions, lens, 0, window=window,
                    ring=ring, max_diag_rows=hkv * g)
            return wk, wv, acc + o
        return jax.jit(lambda wk, wv: jax.lax.fori_loop(
            0, layers, layer, (wk, wv, jnp.zeros(q.shape, jnp.float32))),
            donate_argnums=(0, 1))

    held = np.minimum(pos + 1, ring)[live > 0]
    ref = None
    for form in ["gathered"] + [f"walk:{kb}" for kb in key_blocks]:
        line = {"cell": cell, "form": form, "rows": rows,
                "decoding": int(live.sum()), "ring": ring,
                "device_kind": jax.devices()[0].device_kind}
        try:
            if form == "gathered":
                line["read_rows"] = rows * ring / float(held.sum())
            else:
                attention.RING_KEY_BLOCK = int(form.split(":")[1])
                kb = attention._ring_key_block(ring // BLOCK, BLOCK)
                trips = np.maximum(-(-np.minimum(pos + 1, ring) // kb), 1)
                trips = np.where(live > 0, trips, 1)
                line["key_block"] = kb
                line["read_rows"] = float((trips * kb).sum() / held.sum())
            fn = program(form)
            wk, wv, got = jax.block_until_ready(fn(wk, wv))
            t0 = time.perf_counter()
            for _ in range(iters):
                wk, wv, _o = fn(wk, wv)
            jax.block_until_ready(_o)
            line["ms_a_layer"] = ((time.perf_counter() - t0) / iters * 1e3
                                  / layers)
            got = jnp.where(lens[:, None, None, None] > 0, got, 0)
            ref = got if ref is None else ref
            line["o_err"] = float(jnp.max(jnp.abs(got - ref))
                                  / jnp.max(jnp.abs(ref)))
        except Exception as e:  # noqa: BLE001 — a refused form is a row
            line["error"] = f"{type(e).__name__}: {e}"[:400]
        yield line


def _decode_lines(cell: str, key_blocks, iters: int):
    """One line a form of ``cell``'s decode attention (module
    docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import quintnet_tpu.nn.attention as attention
    from quintnet_tpu.serve.kv_pool import feature_width

    c = DECODE_CELLS[cell]
    rows, hq, hkv, dh, layers = (c[n] for n in ("rows", "hq", "hkv", "dh",
                                                 "layers"))
    rng = np.random.default_rng(34)
    med, sigma, lo, hi, out = c["lengths"]
    pos = (np.clip(rng.lognormal(np.log(med), sigma, rows), lo, hi)
           + rng.integers(0, 2 * out, rows)).astype(np.int32)
    pos = np.minimum(pos, c["table"] * BLOCK - 1)
    tables = np.zeros((rows, c["table"]), np.int32)
    free = rng.permutation(np.arange(1, c["blocks"]))
    for r in range(rows):
        n = pos[r] // BLOCK + 1
        tables[r, :n], free = free[:n], free[n:]
    f = feature_width(hkv, dh)
    keys = jax.random.split(jax.random.key(0), 5)
    shape = (layers, c["blocks"] * BLOCK, f)
    latent = "rank" in c
    kp, vp = (jax.random.normal(k, shape, jnp.bfloat16) for k in keys[:2])
    if latent:
        # ONE pool, its pad lanes zero as every writer leaves them; no v
        # buffer: ``vp`` is a scalar that rides the donated carry
        kp = kp.at[..., dh:].set(0)
        vp = jnp.zeros((), jnp.bfloat16)
    q = jax.random.normal(keys[2], (rows, hkv, hq // hkv, dh), jnp.float32)
    k, v = (jax.random.normal(kk, (rows, hkv, 1, dh), jnp.float32)
            for kk in keys[3:])
    positions, lens = jnp.asarray(pos)[:, None], jnp.ones(rows, jnp.int32)
    tables = jnp.asarray(tables)

    def latent_layer(form, l, kp):
        """One layer's decode attention over the latent pool: the row
        written, then the absorbed form — the view gathered at the
        table's width, or each row's live blocks walked in place."""
        rank = c["rank"]
        kp = attention.latent_write(kp, l, k[:, 0], positions, lens,
                                    block_tables=tables, block_size=BLOCK)
        if form == "gathered":
            o = attention._latent_absorbed_gathered(
                q.reshape(rows, hq, dh), kp, l, positions, tables,
                block_size=BLOCK, scale=c["scale"], heads=hq, rank=rank)
        else:
            ql = q.reshape(rows, 1, hq, dh)
            o = attention.latent_attend_absorbed(
                ql[..., :rank], ql[..., rank:], kp, l, positions, tables,
                block_size=BLOCK, scale=c["scale"])
        return kp, o

    def program(form):
        def layer(l, carry):
            kp, vp, acc = carry
            if latent:
                kp, o = latent_layer(form, l, kp)
            elif form == "gathered":
                kp, vp = attention.paged_write(
                    kp, vp, l, k, v, positions, lens, block_tables=tables,
                    block_size=BLOCK)
                kr, vr = attention._gather_kv(
                    (kp, vp), l, None, tables, block_size=BLOCK,
                    head_shape=None)
                o = attention._lane_diag_sdpa(
                    q, kr, vr, attention._seen(positions, q, tables, BLOCK),
                    kv_heads=hkv, scale=c["scale"])
            else:
                o, (kp, vp) = attention.paged_attend(
                    q, k, v, (kp, vp), l, positions, lens, tables,
                    block_size=BLOCK, scale=c["scale"],
                    max_diag_rows=hq)
            return kp, vp, acc + o
        # the pools are donated and handed back, as the engine's are: a
        # program that may not write them in place copies both first
        out = (rows, 1, hq, c["rank"]) if latent else q.shape
        return jax.jit(lambda kp, vp: jax.lax.fori_loop(
            0, layers, layer, (kp, vp, jnp.zeros(out, jnp.float32))),
            donate_argnums=(0, 1))

    ref = None
    for form in ["gathered"] + [f"walk:{kb}" for kb in key_blocks]:
        line = {"cell": cell, "form": form, "rows": rows,
                "table_positions": c["table"] * BLOCK,
                "live_share": float(pos.mean() + 1) / (c["table"] * BLOCK),
                "device_kind": jax.devices()[0].device_kind}
        try:
            if form != "gathered":
                attention.WALK_KEY_BLOCK = int(form.split(":")[1])
                kb = attention.WALK_KEY_BLOCK
                line["read_amplification"] = float(
                    ((pos // kb + 1) * kb).sum() / (pos + 1).sum())
            fn = program(form)
            kp, vp, got = jax.block_until_ready(fn(kp, vp))
            t0 = time.perf_counter()
            for _ in range(iters):
                kp, vp, _o = fn(kp, vp)
            jax.block_until_ready(_o)
            line["ms_a_layer"] = ((time.perf_counter() - t0) / iters * 1e3
                                  / layers)
            ref = got if ref is None else ref
            line["o_err"] = float(jnp.max(jnp.abs(got - ref))
                                  / jnp.max(jnp.abs(ref)))
        except Exception as e:  # noqa: BLE001 — a refused form is a row
            line["error"] = f"{type(e).__name__}: {e}"[:400]
        yield line


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
    ap.add_argument("--decode", default="",
                    help="serving cells whose decode attention to time "
                         f"instead: {','.join(DECODE_CELLS)}")
    ap.add_argument("--rings", default="",
                    help="window cells whose sliding layers' decode "
                         f"attention to time instead: {','.join(RING_CELLS)}")
    ap.add_argument("--key-blocks", default="128,256,512")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("attn_microbench: no TPU; a time from any other platform "
              "is not a device number", file=sys.stderr)
        return 3
    if args.decode or args.rings:
        kbs = [int(n) for n in args.key_blocks.split(",")]
        for cell in filter(None, args.decode.split(",")):
            for line in _decode_lines(cell, kbs, args.iters):
                print(json.dumps(line), flush=True)
        for cell in filter(None, args.rings.split(",")):
            for line in _ring_lines(cell, kbs, args.iters):
                print(json.dumps(line), flush=True)
        return 0
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
