#!/usr/bin/env python3
"""The readings the limits of a latent-attention mixture-of-experts cell
are set between, on the chip:

    python benchmarks/tools/moe_mla_probe.py --workload <cell> --probe <seed> [<seed> ...] [--controls ...]

Per seed, with the cell's own check AND ITS OWN LIMITS
(drivers/serve_moe_mla.check_logits: a prefill bucket, a second CHUNK
call, then the decode program teacher-forced, against the f32
reference; ``ok`` is the driver's own verdict) at the cell's own slot
count:

- ``stated``: the engine as the cell states it;
- ``no_routed``: the stated engine against a reference that leaves the
  routed experts' part out (the shared expert alone) — a token meets on
  average half a held expert, so this is the smallest piece of the
  mathematics a check could fail to see;
- ``fp8_latent``: the same engine with every latent row rounded to
  float8_e4m3 on its way into the bf16 pool, passed off as bf16;
- ``unrotated_key``: the same engine with the rotary key cached as it
  comes off ``W_dkv``, unrotated (the queries still rotate);
- ``int8_experts`` / ``int8_mla``: an engine whose routed-expert (or
  latent-attention) weights went through int8 (per output channel,
  absmax) before they were packed to bf16, against the reference on
  the stated ones.

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
CONTROLS = ("no_routed", "fp8_latent", "unrotated_key", "int8_experts",
            "int8_mla")
KEPT = ("ok", "why", "token_rms_median", "expert_rel_err_median",
        "routings_agreeing_share", "token_rms_p90", "token_rms_max",
        "max_abs_diff", "at_chunk_end", "at_last_step", "ref_std",
        "expert_rel_err_p90", "expert_tokens", "routings_compared",
        "dropped", "token_rms")


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
        blocks = p["blocks"]
        if which == "int8_experts":
            moe = blocks["moe"]["moe"]
            experts = {n: {"w": _through_int8(moe["experts"][n]["w"])}
                       for n in ("gate", "up", "down")}
            blocks = {**blocks, "moe": {**blocks["moe"], "moe": {
                **moe, "experts": experts}}}
        else:
            def mla(attn):
                return {**attn, **{
                    n: {"w": _through_int8(attn[n]["w"])}
                    for n in ("q_down", "q_up", "kv_down", "kv_up", "o")}}
            blocks = {k: {**v, "attn": mla(v["attn"])}
                      for k, v in blocks.items()}
        return {**p, "blocks": blocks}
    return finish


def probe(workload, seeds, controls) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import harness
    from benchmarks.lib.device import require_tpu
    from quintnet_tpu.core.runtime import enable_compilation_cache
    from quintnet_tpu.models import pangu_moe

    enable_compilation_cache()
    bench = harness.Bench(ROOT)
    cell = bench.cell(workload)
    driver = bench.driver(cell.spec["driver"])
    require_tpu(1)
    os.makedirs(OUT, exist_ok=True)
    cfg = driver.PanguMoEConfig.from_dict(cell.config)
    spec, config = cell.spec, cell.config
    dtype = spec["engine"]["weights_dtype"]

    def reading(engine, seed, want):
        rec = driver.check_logits(engine, config, spec, seed,
                                  reference_out=want, detail=True)
        return {k: rec[k] for k in KEPT if k in rec}

    def patched(name, replacement, engine, seed, want):
        """One reading with ``pangu_moe.<name>`` replaced while the
        check's programs are traced."""
        original = getattr(pangu_moe, name)
        setattr(pangu_moe, name, replacement(original))
        try:
            return reading(engine, seed, want)
        finally:
            setattr(pangu_moe, name, original)

    def fp8_rows(write):
        return lambda pool, layer, rows, *a, **kw: write(
            pool, layer,
            rows.astype(jnp.float8_e4m3fn).astype(rows.dtype), *a, **kw)

    def key_unrotated(rope):
        # the shared rotary key is the one 3-d operand ([S, P, rope]);
        # the queries' rotary parts are [S, P, H, rope]
        return lambda x, cos, sin: x if x.ndim == 3 else rope(x, cos, sin)

    with open(os.path.join(OUT, workload + ".probe.jsonl"), "a") as out:
        for seed in seeds:
            params = driver.make_params(cfg, dtype, seed)
            want = jax.block_until_ready(
                driver.reference_side(params, config, spec, seed))
            cut = (jax.block_until_ready(driver.reference_side(
                params, config, spec, seed, routed=False))
                if "no_routed" in controls else None)
            engine = driver.build_engine(spec, cfg, params)
            del params
            line = {"seed": seed, "max_slots": engine.max_slots,
                    "prompt_lens": spec["correctness"]["prompt_lens"],
                    "stated": reading(engine, seed, want)}
            if cut is not None:
                line["no_routed"] = reading(engine, seed, cut)
            if "fp8_latent" in controls:
                line["fp8_latent"] = patched("latent_write", fp8_rows,
                                             engine, seed, want)
            if "unrotated_key" in controls:
                line["unrotated_key"] = patched("apply_rope", key_unrotated,
                                                engine, seed, want)
            del engine
            for which in ("int8_experts", "int8_mla"):
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
