#!/usr/bin/env python3
"""The readings the limits of a window-and-global mixture-of-experts
cell are set between, on the chip:

    python benchmarks/tools/window_moe_probe.py --workload <cell> --probe <seed> [<seed> ...] [--controls ...]

Per seed, with the cell's own check AND ITS OWN LIMITS
(drivers/serve_window_moe.check_logits: the widest prefill bucket, a
second CHUNK call of another bucket, then the decode program
teacher-forced past two windows, against the f32 reference; ``ok`` is
the driver's own verdict) at the cell's own slot count:

- ``stated``: the engine as the cell states it;
- ``window_off`` / ``rope_swapped`` / ``no_gate`` /
  ``no_attention_factor``: the stated engine against a reference that
  lets the sliding layers see every earlier key, rotates each layer
  kind with the other's setting, leaves the per-head gate out, or
  leaves YaRN's attention factor off the tables
  (lib/reference_laguna.py ``controls``) — each the smallest piece of
  its mechanism a check could fail to see;
- ``fp8_pool``: the same engine with every k and v row rounded to
  float8_e4m3 on its way into the bf16 block pool AND the bf16 window
  store, passed off as bf16;
- ``int8_experts`` / ``int8_attn``: an engine whose routed-expert (or
  attention: q, k, v, o, gate) weights went through int8 (per output
  channel, absmax) before they were packed to bf16, against the
  reference on the stated ones.

The limits have to pass every ``stated`` reading and refuse every
control, each by at least one of them. The reference is computed FIRST,
on the stated weights alone: two engines' worth of weights do not fit
the chip. One JSON line per seed, and
``chiprun_out/bench/<cell>.probe.jsonl``. (The by-scope tables of a
kept trace come from ``tools/hybrid_probe.py --keep-trace``, which
takes any serving cell.)
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out", "bench")
MATH = ("window_off", "rope_swapped", "no_gate", "no_attention_factor")
CONTROLS = (*MATH, "fp8_pool", "int8_experts", "int8_attn")
KEPT = ("ok", "why", "token_rms_median", "expert_rel_err_median",
        "routings_agreeing_share", "token_rms_p90", "token_rms_max",
        "at_chunk_end", "at_last_step", "ref_std", "expert_rel_err_p90",
        "expert_tokens", "routings_compared", "dropped", "token_rms")


def _through_int8(w):
    """``w`` [..., in, out] rounded through int8 with one absmax scale
    an output channel, as serve/weight_quant.py's int8 policy packs it,
    and back: what an int8 weight would compute with."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def _rounded(which):
    """A ``finish`` hook for ``make_params``: the named weights through
    int8 before the packing."""
    def finish(p):
        blocks = dict(p["blocks"])
        if which == "int8_experts":
            blocks["experts"] = {
                n: {"w": _through_int8(blocks["experts"][n]["w"])}
                for n in ("gate", "up", "down")}
        else:
            for kind, stack in blocks.items():
                if "attn" in stack:
                    blocks[kind] = {**stack, "attn": {
                        n: {"w": _through_int8(node["w"])}
                        for n, node in stack["attn"].items()}}
        return {**p, "blocks": blocks}
    return finish


def probe(workload, seeds, controls) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import harness
    from benchmarks.lib.device import require_tpu
    from quintnet_tpu.core.runtime import enable_compilation_cache
    from quintnet_tpu.nn import attention

    enable_compilation_cache()
    bench = harness.Bench(ROOT)
    cell = bench.cell(workload)
    driver = bench.driver(cell.spec["driver"])
    require_tpu(1)
    os.makedirs(OUT, exist_ok=True)
    cfg = driver.LagunaConfig.from_dict(cell.config)
    spec, config = cell.spec, cell.config
    dtype = spec["engine"]["weights_dtype"]

    def reading(engine, seed, want):
        rec = driver.check_logits(engine, config, spec, seed,
                                  reference_out=want, detail=True)
        return {k: rec[k] for k in KEPT if k in rec}

    def fp8_rows(write):
        def rounded(a, b, layer, k, v, *rest, **kw):
            k, v = (x.astype(jnp.float8_e4m3fn).astype(x.dtype)
                    for x in (k, v))
            return write(a, b, layer, k, v, *rest, **kw)
        return rounded

    def through_fp8(engine, seed, want):
        """One reading with both writers of the cache (the block pool's
        and the window store's) rounding their rows through fp8 while
        the check's programs are traced."""
        names = ("paged_write", "window_write")
        originals = {n: getattr(attention, n) for n in names}
        for n in names:
            setattr(attention, n, fp8_rows(originals[n]))
        try:
            return reading(engine, seed, want)
        finally:
            for n in names:
                setattr(attention, n, originals[n])

    with open(os.path.join(OUT, workload + ".probe.jsonl"), "a") as out:
        for seed in seeds:
            params = driver.make_params(cfg, dtype, seed)
            want = jax.block_until_ready(
                driver.reference_side(params, config, spec, seed))
            wrong = {c: jax.block_until_ready(driver.reference_side(
                params, config, spec, seed, controls=(c,)))
                for c in MATH if c in controls}
            engine = driver.build_engine(spec, cfg, params)
            del params
            line = {"seed": seed, "max_slots": engine.max_slots,
                    "prompt_lens": spec["correctness"]["prompt_lens"],
                    "stated": reading(engine, seed, want)}
            for c, ref in wrong.items():
                line[c] = reading(engine, seed, ref)
            wrong.clear()
            if "fp8_pool" in controls:
                line["fp8_pool"] = through_fp8(engine, seed, want)
            del engine
            for which in ("int8_experts", "int8_attn"):
                if which not in controls:
                    continue
                gc.collect()
                engine = driver.build_engine(spec, cfg, driver.make_params(
                    cfg, dtype, seed, finish=_rounded(which)))
                line[which] = reading(engine, seed, want)
                del engine
            gc.collect()
            jax.clear_caches()
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--probe", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=list(CONTROLS),
                    choices=list(CONTROLS))
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    return probe(args.workload, args.probe, args.controls)


if __name__ == "__main__":
    sys.exit(main())
