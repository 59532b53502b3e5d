#!/usr/bin/env python3
"""The readings the limits of a linear-attention + latent-attention
mixture-of-experts cell are set between, on the chip:

    python benchmarks/tools/kda_moe_probe.py --workload <cell> --probe <seed> [<seed> ...] [--controls ...]

Per seed, with the cell's own check AND ITS OWN LIMITS
(drivers/serve_kda_moe.check_logits: a prefill bucket, a second CHUNK
call that starts past 0, then the decode program teacher-forced, on
rows spread over the slots, once through the engine's OWN compiled
programs and once through the same bodies for the logits, against the
f32 reference; ``ok`` is the driver's own verdict) at the cell's own
slot count. A control that alters what the programs compute is read on
a FRESH engine built while the alteration holds (on the same weights,
after the stated engine is gone: the engine's own programs are traced
once), so both passes run it:

- ``stated``: the engine as the cell states it;
- ``bf16_state``: the same engine with the KDA state rounded to bf16
  every time the delta rule hands it back (both forms;
  ``lax.reduce_precision``, which the compiler may not fold away as it
  may a pair of casts), passed off as f32 — the logits cannot see it,
  the state limit must;
- ``alpha_1`` / ``beta_1``: the per-channel decay forced to 1 (``g`` =
  0: nothing is ever forgotten) / the write strength forced to 1;
- ``tail_zeroed``: the slot's conv tail zeroed before the second chunk
  call, as a chunk feeder that lost it would leave it;
- ``last_slot_state_lost``: the LAST slot's state zeroed after its
  first chunk call — a fault of one high slot, which rows on the first
  slots alone would not show;
- ``programs_differ``: the STATED engine, its own programs as they
  are, with the decay forced to 1 in the logits' programs alone — the
  engine's tokens must then part from the logits' argmax
  (``engine_tokens_floor``);
- ``fp8_latent``: every latent row rounded to float8_e4m3's four
  exponent and three mantissa bits on its way into the bf16 pool,
  passed off as bf16 — one latent layer of six hardly moves the
  logits, the latent leg (the layer's own rows for unit-normal
  tokens) must see it;
- ``int8_kda``: an engine whose KDA projections went through int8 (per
  output channel, absmax) before they were packed to bf16, against the
  reference on the stated ones;
- ``no_group_limit`` / ``bias_in_weights``: the stated engine against a
  reference that routes by plain top-8 of 512 (one group, kept) / takes
  the routing weights from ``s + b`` (logits, routings AND the expert
  leg against that reference) — the two halves of the published router
  a transcription most easily gets wrong.

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
CONTROLS = ("bf16_state", "alpha_1", "beta_1", "tail_zeroed",
            "last_slot_state_lost", "programs_differ", "fp8_latent", "int8_kda", "no_group_limit",
            "bias_in_weights")
KEPT = ("ok", "why", "token_rms_median", "engine_tokens_agreeing_share",
        "state_rel_err", "latent_rows_in_run_rel_err",
        "latent_rel_err_median", "latent_rel_err_p90",
        "expert_rel_err_median", "routings_agreeing_share", "token_rms_p90",
        "token_rms_max", "max_abs_diff", "at_chunk_end", "at_last_step",
        "ref_std", "state_norm", "expert_rel_err_p90", "expert_tokens",
        "routings_compared", "dropped")


def _through_int8(w):
    """``w`` [..., in, out] rounded through int8 with one absmax scale
    an output channel, as serve/weight_quant.py's int8 policy packs it,
    and back: what an int8 weight would compute with."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / scale).clip(-127, 127) * scale


def _int8_kda(p):
    """A ``finish`` hook for ``make_params``: every KDA projection
    through int8 before the packing."""
    kda = p["blocks"]["kda"]
    mixer = {**kda["mixer"], **{
        n: {"w": _through_int8(kda["mixer"][n]["w"])}
        for n in ("q", "k", "v", "decay", "beta", "gate", "o")}}
    return {**p, "blocks": {**p["blocks"], "kda": {**kda, "mixer": mixer}}}


def probe(workload, seeds, controls) -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmarks.lib import harness
    from benchmarks.lib.device import require_tpu
    from quintnet_tpu.core.runtime import enable_compilation_cache
    from quintnet_tpu.models import pangu_moe
    from quintnet_tpu.nn import kda

    enable_compilation_cache()
    bench = harness.Bench(ROOT)
    cell = bench.cell(workload)
    driver = bench.driver(cell.spec["driver"])
    require_tpu(1)
    os.makedirs(OUT, exist_ok=True)
    cfg = driver.LingHybridConfig.from_dict(cell.config)
    spec, config = cell.spec, cell.config
    dtype = spec["engine"]["weights_dtype"]

    def reading(engine, seed, want, **kw):
        rec = driver.check_logits(engine, config, spec, seed,
                                  reference_out=want, **kw)
        return {k: rec[k] for k in KEPT if k in rec}

    def patched(module, names, replacement, seed, want, *, engine=None,
                params=None):
        """One reading with every ``module.<name>`` replaced while the
        programs are traced: the check's alone on a standing ``engine``
        (whose own are traced already), or all of them on an engine
        built here from ``params``."""
        originals = {name: getattr(module, name) for name in names}
        for name, original in originals.items():
            setattr(module, name, replacement(original))
        try:
            if engine is None:
                engine = driver.build_engine(spec, cfg, params)
            return reading(engine, seed, want)
        finally:
            del engine
            gc.collect()
            for name, original in originals.items():
                setattr(module, name, original)

    def state_through_bf16(rule):
        def rounded(*a, **kw):
            o, state = rule(*a, **kw)
            return o, lax.reduce_precision(state, exponent_bits=8,
                                           mantissa_bits=7)
        return rounded

    def gates(**forced):
        def replace(original):
            def fn(p, x, dims):
                g, beta = original(p, x, dims)
                return (jnp.zeros_like(g) if "alpha" in forced else g,
                        jnp.ones_like(beta) if "beta" in forced else beta)
            return fn
        return replace

    def fp8_rows(write):
        return lambda pool, layer, rows, *a, **kw: write(
            pool, layer, lax.reduce_precision(rows, exponent_bits=4,
                                              mantissa_bits=3), *a, **kw)

    def zero_tail(pool, row, start):
        if start == 0:                  # after a row's first chunk call
            pool.conv = pool.conv.at[:, row].set(0)

    with open(os.path.join(OUT, workload + ".probe.jsonl"), "a") as out:
        for seed in seeds:
            params = driver.make_params(cfg, dtype, seed)
            want = jax.block_until_ready(
                driver.reference_side(params, config, spec, seed))
            others = {}
            if "no_group_limit" in controls:
                others["no_group_limit"] = jax.block_until_ready(
                    driver.reference_side(
                        params, {**config, "n_group": 1, "topk_group": 1},
                        spec, seed))
            if "bias_in_weights" in controls:
                others["bias_in_weights"] = jax.block_until_ready(
                    driver.reference_side(params, config, spec, seed,
                                          bias_in_weights=True))
            engine = driver.build_engine(spec, cfg, params)
            del params
            line = {"seed": seed, "max_slots": engine.max_slots,
                    "prompt_lens": spec["correctness"]["prompt_lens"],
                    "stated": reading(engine, seed, want)}
            for name, ref in others.items():
                line[name] = reading(engine, seed, ref)
            if "tail_zeroed" in controls:
                line["tail_zeroed"] = reading(engine, seed, want,
                                              after_call=zero_tail)
            if "last_slot_state_lost" in controls:
                last = engine.max_slots - 1

                def lose_state(pool, row, start):
                    if row == last and start == 0:
                        pool.ssm = pool.ssm.at[:, row].set(0)
                line["last_slot_state_lost"] = reading(
                    engine, seed, want, after_call=lose_state)
            if "programs_differ" in controls:
                line["programs_differ"] = patched(
                    kda, ("_gates",), gates(alpha=1), seed, want,
                    engine=engine)
            params = engine.params
            del engine
            gc.collect()
            if "bf16_state" in controls:
                line["bf16_state"] = patched(
                    kda, ("delta_step", "delta_chunked"),
                    state_through_bf16, seed, want, params=params)
            if "alpha_1" in controls:
                line["alpha_1"] = patched(kda, ("_gates",), gates(alpha=1),
                                          seed, want, params=params)
            if "beta_1" in controls:
                line["beta_1"] = patched(kda, ("_gates",), gates(beta=1),
                                         seed, want, params=params)
            if "fp8_latent" in controls:
                # the latent layer writes its rows through the latent
                # family's shared body (models/pangu_moe.py)
                line["fp8_latent"] = patched(
                    pangu_moe, ("latent_write",), fp8_rows, seed, want,
                    params=params)
            del params
            if "int8_kda" in controls:
                gc.collect()
                engine = driver.build_engine(spec, cfg, driver.make_params(
                    cfg, dtype, seed, finish=_int8_kda))
                line["int8_kda"] = reading(engine, seed, want)
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
