#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path ONCE on one TPU chip, through the entry points a
user calls, at GPT-2 124M's published widths (12 layers, 768 wide, 12
heads, vocab 50257, 1024 positions). Weights and data come from
``--seed``; nothing is read that git would not commit, nothing is
downloaded, no child process is started.

    python chip_smoke.py               # one chip: train, serve, kernels
    python chip_smoke.py --multichip   # four chips: the mesh, and only it

Default phases (one JSON line each, with the phase's wall time and the
seconds it spent compiling or loading compiled programs):

- **train** — ``get_strategy`` + ``Trainer.fit`` on a one-device mesh,
  bf16 compute, batch 8 x seq 512 of packed synthetic documents, remat
  on, 6 optimizer steps on a repeated batch: every step's loss finite,
  the last lower than the first; one checkpoint written by ``fit`` and
  restored by ``Trainer.resume_state`` (train/checkpoint.py), restored
  step equal to saved and the arrays bit-equal.
- **serve** — one ``ServeEngine`` (gathered-view attention, bf16 KV,
  ``max_seq_len`` 1024, 8 slots) as the single replica of a
  ``ServeFleet`` behind ``FrontDoor``: 8 concurrent ``POST
  /v1/generate`` over real HTTP, prompts of 16-700 tokens, 32 new
  tokens each, one streamed; ``GET /healthz``; ``GET /metrics`` parsed
  by ``obs.parse_exposition``. Correctness is on LOGITS, not tokens
  (random weights give near-flat logits, so greedy tokens are no
  contract between two programs): the paged programs' logits for two
  prompts — ``Family.verify`` against the engine's own pool, the seam
  ``serve/kv_quant.paged_eval_nll`` shows — against a plain dense
  ``gpt2_apply`` of the same params on the chip.
- **kernels** — ``nn/attention.local_attention`` at seq 1024 and 8192
  (12 heads, Dh 64, causal: the fused kernel's resident and streamed
  geometries, as the chooser picks them) forward and backward against
  ``blockwise_attention``; and the serve phase's engine's decode
  program, which must walk each row's live blocks of the pool
  (ops/paged_attention.py), with the paged logits over the first
  tokens of two serve prompts — few enough query rows that
  ``Family.verify`` walks too — against the dense forward. Both must
  show a ``tpu_custom_call`` in the lowered program: a kernel phase
  that ran the reference is a failure.

- **kda_moe** — the linear-attention + latent-attention family
  (Ling 3.0's: KDA layers with a per-slot f32 state beside one MLA
  layer's paged latent rows, a group-limited router) at a TINY size
  through ``ServeEngine`` with chunked prefill: five requests, more
  than slots, one preempted-free run; every generated token is, by
  the plain f32 reference's own logits for the same prefix
  (benchmarks/lib/reference_ling_hybrid.py), within ``TOL`` of that
  reference's largest logit. The published widths are the
  benchmark's (``ling-3.0-flash.serve-reason-sat``); this phase proves
  the family's programs start on the chip.

``--multichip`` runs none of those. It takes the same train step on a
``dp=2 x tp=2`` mesh over four chips against a one-device mesh on the
first of them (same seed, same global batch, three steps), a tp=2
``ServeEngine(mesh=...)`` against the mesh-less engine on logits, and
prints where params, optimizer state and the KV pool actually live.

Tolerances (measured on a v5e in PR 21, CHANGES.md has the readings):
see ``TOL`` below. Any failure anywhere is an exception and a non-zero
exit; nothing is caught and carried past.

The LAST line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only when JAX found a TPU and every phase passed.

The phase functions take their sizes from a :class:`Size`, so the CPU
rehearsal in tests/test_chip_bringup.py can call them at a tiny size;
``main`` only ever runs :func:`full_size` and only on a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import math
import os
import re
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))

# What "agree" means, per comparison. Logit tolerances are absolute, on
# logits whose own spread the phase line reports beside them.
TOL = {
    # paged (bf16 KV pool) vs dense f32 forward, same params
    "paged_vs_dense_logits": 0.05,
    # tp=2 engine vs mesh-less engine (the psum reassociates the sums)
    "tp_vs_single_logits": 0.05,
    # bf16 flash kernel vs f32 blockwise reference, as a share of the
    # reference's largest magnitude (output, dq, dk, dv alike)
    "flash_vs_blockwise_rel": 0.02,
    # dp2 x tp2 vs one-device loss, per step (bf16 compute)
    "mesh_vs_single_loss": 0.02,
    # a greedy token of the tiny KDA + MLA engine (f32 pool and weights,
    # the chip's default matmul precision) vs the f32 reference's best:
    # a third of the logits' spread (0.59); 0.077 read on a v5e at seed
    # 0 (PR 37), 0 on the CPU's exact f32
    "kda_moe_greedy_gap": 0.2,
}


@dataclasses.dataclass(frozen=True)
class Size:
    """Every size a phase uses. ``full_size()`` is what ``main`` runs;
    the CPU rehearsal builds a tiny one."""

    cfg: Any                            # GPT2Config
    batch: int = 8
    seq: int = 512
    train_steps: int = 6
    learning_rate: float = 3e-4
    slots: int = 8
    block_size: int = 16
    num_blocks: int = 640
    max_seq_len: int = 1024
    prompt_lens: Tuple[int, ...] = (16, 48, 100, 170, 260, 390, 540, 700)
    max_new: int = 32
    dense_check: Tuple[int, int] = (1, 6)    # prompts compared with dense
    kernel_check: Tuple[int, int] = (0, 2)   # prompts the walk scores
    flash_seqs: Tuple[int, ...] = (1024, 8192)  # resident, streamed


def full_size() -> Size:
    from quintnet_tpu.models.gpt2 import GPT2Config

    return Size(cfg=GPT2Config.base())


# ---------------------------------------------------------------------
# phase bookkeeping
# ---------------------------------------------------------------------
def compile_totals() -> Dict:
    """What JAX compiled or loaded in this process so far, from the
    program's own start-up record (quintnet_tpu/obs/recorder.py: its
    one compile listener charges every event, wherever it fell, to
    ``totals``): seconds of backend compile-or-load, programs, and the
    persistent cache's hits and misses."""
    from quintnet_tpu.obs.recorder import startup

    totals = startup().totals
    return {key: totals.get(key, 0) for key in (
        "compile_or_load_s", "programs", "cache_hits", "cache_misses")}


def run_phase(name: str, fn, sink) -> Dict:
    """Run one phase; print (and append to ``sink``) its JSON line. An
    exception is not caught: it ends the script."""
    before = compile_totals()
    t0 = time.perf_counter()
    rec = fn()
    spent = {k: v - before[k] for k, v in compile_totals().items()}
    line = {"phase": name, "ok": True, **rec,
            "wall_s": round(time.perf_counter() - t0, 3),
            "compile_s": round(spent["compile_or_load_s"], 3),
            "programs": spent["programs"],
            "cache_hits": spent["cache_hits"],
            "cache_misses": spent["cache_misses"]}
    text = json.dumps(line)
    print(text, flush=True)
    sink.write(text + "\n")
    sink.flush()
    return line


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------
# train
# ---------------------------------------------------------------------
def packed_batch(size: Size, seed: int):
    """One [batch, seq] batch of packed synthetic documents
    (data/datasets.pack_documents): token documents of 32-400 tokens
    joined by EOS, no padding."""
    import numpy as np

    from quintnet_tpu.data import PackedLMDataset, pack_documents

    rng = np.random.default_rng(seed)
    eos = size.cfg.vocab_size - 1
    need = size.batch * size.seq
    docs, have = [], 0
    while have < need + size.seq:
        n = int(rng.integers(min(32, size.seq // 2), min(400, size.seq) + 1))
        docs.append(rng.integers(0, eos, (n,)))
        have += n + 1
    ds = PackedLMDataset(pack_documents(docs, size.seq, eos_id=eos))
    check(len(ds) >= size.batch, f"packed only {len(ds)} rows")
    return next(iter(ds.batches(size.batch, seed=seed)))


def train_run(size: Size, seed: int, *, mesh_dim: Sequence[int],
              mesh_name: Sequence[str], devices=None, steps: int,
              checkpoint_dir=None):
    """``steps`` optimizer steps on one repeated batch through
    ``Trainer.fit``. Returns (per-step losses, trainer, strategy)."""
    import jax.numpy as jnp

    from quintnet_tpu.core.config import Config
    from quintnet_tpu.models.gpt2 import gpt2_model_spec
    from quintnet_tpu.parallel.strategy import get_strategy
    from quintnet_tpu.train.trainer import Trainer

    cfg = Config.from_dict({
        "mesh_dim": list(mesh_dim), "mesh_name": list(mesh_name),
        "training": {"batch_size": size.batch, "epochs": 1,
                     "optimizer": "adamw",
                     "learning_rate": size.learning_rate,
                     "grad_clip_norm": 1.0, "dtype": "bfloat16",
                     "remat": True, "log_every": 1, "seed": seed},
    })
    model = gpt2_model_spec(size.cfg, remat=cfg.training.remat_mode,
                            compute_dtype=jnp.bfloat16)
    strategy = get_strategy("auto", cfg, devices=devices)
    log: List[str] = []
    trainer = Trainer(cfg, model, strategy=strategy, task_type="clm",
                      checkpoint_dir=checkpoint_dir, log_fn=log.append)
    batch = packed_batch(size, seed)
    hist = trainer.fit(lambda epoch: [batch] * steps)
    # log_every=1: the trainer logs every step's own loss
    losses = [float(m.group(1)) for line in log
              for m in [re.match(r"epoch 0 step \d+: loss (\S+)", line)]
              if m]
    check(len(losses) == steps,
          f"expected {steps} logged step losses, got {log}")
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss: {losses}")
    check(abs(hist.train_loss[0] - sum(losses) / steps) < 1e-3,
          f"epoch mean {hist.train_loss[0]} vs steps {losses}")
    return losses, trainer, strategy


def phase_train(size: Size, out_dir: str, seed: int) -> Dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    ckpt = os.path.join(out_dir, "ckpt")
    # fit() resumes from whatever the directory holds; this run is fresh
    shutil.rmtree(ckpt, ignore_errors=True)
    losses, trainer, strategy = train_run(
        size, seed, mesh_dim=[1], mesh_name=["dp"],
        steps=size.train_steps, checkpoint_dir=ckpt)
    check(losses[-1] < losses[0],
          f"loss did not fall over {size.train_steps} steps: {losses}")
    trainer.assert_compile_count(steps=1)

    # the checkpoint fit() wrote at the epoch boundary, read back
    params, opt_state = trainer.final_state
    t0 = time.perf_counter()
    r_params, r_opt, cursor = trainer.resume_state()
    restore_s = time.perf_counter() - t0
    check(cursor is not None and cursor.global_step == size.train_steps,
          f"restored cursor {cursor} != saved step {size.train_steps}")
    same = jax.tree.map(jnp.array_equal, (params, opt_state),
                        (r_params, r_opt))        # compared on device
    check(all(bool(x) for x in jax.tree.leaves(same)),
          "restored arrays differ from saved")
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    shutil.rmtree(ckpt, ignore_errors=True)
    return {"mesh": dict(strategy.mesh.shape), "n_params": n_params,
            "batch": size.batch, "seq": size.seq, "losses": losses,
            "checkpoint": {"saved_step": size.train_steps,
                           "restored_step": cursor.global_step,
                           "arrays_equal": True,
                           "restore_s": round(restore_s, 3)}}


# ---------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------
def make_prompts(size: Size, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    return [rng.integers(0, size.cfg.vocab_size, (n,)).astype(np.int32)
            for n in size.prompt_lens]


def pad_rows(prompts, width: int):
    """Prompts as one right-padded [S, width] int32 array."""
    import numpy as np

    rows = np.zeros((len(prompts), width), np.int32)
    for s, p in enumerate(prompts):
        rows[s, :len(p)] = p
    return rows


def build_engine(size: Size, params, **kw):
    from quintnet_tpu.serve import ServeEngine, gpt2_family

    return ServeEngine(gpt2_family(size.cfg), params, max_slots=size.slots,
                       block_size=size.block_size,
                       num_blocks=size.num_blocks,
                       max_seq_len=size.max_seq_len, kv_dtype="bf16", **kw)


def paged_logits(engine, prompts, width: int):
    """Logits at every position of ``prompts`` (right-padded to
    ``width``) from the PAGED programs: ``Family.verify`` against the
    engine's own pool — the seam serve/kv_quant.paged_eval_nll shows.
    Under a tp mesh the call runs in the same shard_map arrangement
    the engine's own programs use. Returns [S, width, V] float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pool, fam = engine.pool, engine.family
    S = len(prompts)
    rows = pad_rows(prompts, width)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    need = pool.blocks_for(width)
    tables = np.zeros((S, engine.table_width), np.int32)
    held = []
    for s in range(S):
        got = pool.acquire(need)
        check(got is not None, f"pool cannot hold {S} rows of {width}")
        tables[s, :need] = got
        held.append(got)
    n_pool = len(pool.caches())
    scaled = pool.policy.scaled

    def body(params, *rest):
        caches, (ids, starts, tail, tbl) = rest[:n_pool], rest[n_pool:]
        return fam.verify(
            params, caches[0], caches[1], ids, starts, tail, tbl,
            pool.block_size, tp_axis=engine.tp_axis,
            kv_scales=caches[2:] if scaled else None, policy=pool.policy)

    if engine.mesh is None:
        fn = jax.jit(body)
    else:
        from jax.sharding import PartitionSpec as P

        from quintnet_tpu.core import collectives as cc

        pool_specs = (P(None, None, engine.tp_axis, None),) * 2
        fn = jax.jit(cc.shard_map_fn(
            body, engine.mesh,
            in_specs=((fam.partition_specs(engine.tp_axis),)
                      + pool_specs + (P(),) * 4),
            out_specs=(P(),) + pool_specs))
    out = fn(engine.params, *pool.caches(), jnp.asarray(rows),
             jnp.zeros((S,), jnp.int32), jnp.asarray(lens),
             jnp.asarray(tables))
    pool.update(*out[1:])
    for blocks in held:
        pool.release(blocks)
    return out[0].astype(jnp.float32)


def dense_logits(size: Size, params, prompts, width: int):
    """A plain dense ``gpt2_apply`` of ``prompts`` right-padded to
    ``width``. Returns [S, width, V] float32."""
    import jax
    import jax.numpy as jnp

    from quintnet_tpu.models.gpt2 import gpt2_apply

    return jax.jit(lambda pr, ids: gpt2_apply(pr, ids, size.cfg))(
        params, jnp.asarray(pad_rows(prompts, width))).astype(jnp.float32)


def logits_gap(a, b, prompts) -> Dict:
    """Largest |a - b| over the REAL positions of each row, with the
    reference's own spread for scale."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    check(a.shape == b.shape, f"logit shapes {a.shape} vs {b.shape}")
    gap, spread = 0.0, 0.0
    for s, p in enumerate(prompts):
        x, y = a[s, :len(p)], b[s, :len(p)]
        check(bool(np.isfinite(x).all() and np.isfinite(y).all()),
              "non-finite logits")
        gap = max(gap, float(np.abs(x - y).max()))
        spread = max(spread, float(y.std()))
    return {"max_abs_diff": round(gap, 6), "ref_std": round(spread, 4),
            "positions": [len(p) for p in prompts]}


def _width(prompts, block_size: int) -> int:
    n = max(len(p) for p in prompts)
    return -(-n // block_size) * block_size


def _post_generate(host: str, port: int, prompt, max_new: int, seed: int,
                   stream: bool) -> Dict:
    conn = http.client.HTTPConnection(host, port, timeout=900)
    conn.request("POST", "/v1/generate", json.dumps(
        {"prompt": [int(t) for t in prompt], "max_new_tokens": max_new,
         "seed": seed, "stream": stream}), {})
    r = conn.getresponse()
    raw = r.read().decode()
    check(r.status == 200, f"POST /v1/generate -> {r.status}: {raw[:300]}")
    if not stream:
        return {"output": json.loads(raw)["output"], "streamed": None}
    events = [e for e in raw.split("\n\n") if e.strip()]
    toks = [json.loads(e.split("data: ", 1)[1])
            for e in events if e.startswith("data: ")]
    done = [e for e in events if e.startswith("event: done")]
    check(len(done) == 1, f"stream ended with {len(done)} done events")
    check([t["last"] for t in toks].count(True) == 1 and toks[-1]["last"],
          "stream did not mark exactly its final token last")
    return {"output": json.loads(done[0].split("data: ", 1)[1])["output"],
            "streamed": [t["token"] for t in toks]}


def _get(host: str, port: int, path: str) -> Tuple[int, str]:
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", path)
    r = conn.getresponse()
    return r.status, r.read().decode()


def phase_serve(size: Size, seed: int) -> Tuple[Dict, Any, list]:
    """Returns (record, the engine, the prompts) — the kernels phase
    checks this engine's walk."""
    import jax

    from quintnet_tpu.fleet import FrontDoor, ServeFleet
    from quintnet_tpu.models.gpt2 import gpt2_init
    from quintnet_tpu.obs import parse_exposition

    params = gpt2_init(jax.random.key(seed), size.cfg)
    prompts = make_prompts(size, seed)
    engine = build_engine(size, params)
    t0 = time.perf_counter()
    engine.warmup()
    jax.block_until_ready(engine.pool.caches())
    warmup_s = time.perf_counter() - t0

    fleet = ServeFleet(lambda: engine, n_replicas=1)
    try:
        with FrontDoor(fleet, request_timeout_s=900.0) as fd:
            t0 = time.perf_counter()
            with ThreadPoolExecutor(len(prompts)) as ex:
                futs = [ex.submit(_post_generate, fd.host, fd.port, p,
                                  size.max_new, seed + i, i == 0)
                        for i, p in enumerate(prompts)]
                replies = [f.result() for f in futs]
            requests_s = time.perf_counter() - t0
            st_h, health = _get(fd.host, fd.port, "/healthz")
            st_m, exposition = _get(fd.host, fd.port, "/metrics")
        fleet.drain(timeout=60.0)
    finally:
        fleet.close()

    n_new = 0
    for p, rep in zip(prompts, replies):
        out = rep["output"]
        check(len(out) == len(p) + size.max_new,
              f"{len(p)}-token prompt + {size.max_new} new came back "
              f"as {len(out)} tokens")
        check(out[:len(p)] == [int(t) for t in p], "prompt not echoed")
        check(all(0 <= t < size.cfg.vocab_size for t in out),
              "token outside the vocabulary")
        if rep["streamed"] is not None:
            check(rep["streamed"] == out[len(p):],
                  "streamed tokens differ from the final output")
        n_new += len(out) - len(p)
    check(n_new == len(prompts) * size.max_new, "token counts do not add up")
    check(st_h == 200 and json.loads(health)["status"] == "ok",
          f"/healthz -> {st_h} {health[:200]}")
    check(st_m == 200, f"/metrics -> {st_m}")
    parsed = parse_exposition(exposition)
    finished = [v for (name, _l), v in parsed.items()
                if name == "quintnet_fleet_finished"]
    check(finished == [float(len(prompts))],
          f"quintnet_fleet_finished = {finished}")
    summary = engine.metrics.summary()
    check(summary["gen_tokens"] == n_new,
          f"engine counted {summary['gen_tokens']} new tokens, "
          f"clients received {n_new}")

    # logits: the paged programs against a plain dense forward
    picked = [prompts[i] for i in size.dense_check]
    width = _width(picked, size.block_size)
    gap = logits_gap(paged_logits(engine, picked, width),
                     dense_logits(size, params, picked, width), picked)
    check(gap["max_abs_diff"] <= TOL["paged_vs_dense_logits"],
          f"paged vs dense logits: {gap} > {TOL['paged_vs_dense_logits']}")

    rec = {"requests": len(prompts), "streamed_requests": 1,
           "prompt_tokens": [len(p) for p in prompts],
           "new_tokens": n_new, "healthz": "ok",
           "metrics_series": len(parsed),
           "warmup_s": round(warmup_s, 3),
           "requests_s": round(requests_s, 3),
           "compiled_programs": engine.compile_stats(),
           "paged_vs_dense_logits": {
               **gap, "tol": TOL["paged_vs_dense_logits"]}}
    return rec, engine, prompts


# ---------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------
def check_flash(size: Size, seed: int, S: int) -> Dict:
    """``local_attention`` fwd+bwd at ``S`` positions, on the Pallas
    branch its chooser takes there, against ``blockwise_attention``
    (f32, highest matmul precision)."""
    import jax
    import jax.numpy as jnp

    from quintnet_tpu.nn.attention import (local_attention,
                                           local_attention_path)
    from quintnet_tpu.ops import blockwise_attention

    H = size.cfg.n_head
    D = size.cfg.n_embd // H
    ks = jax.random.split(jax.random.key(seed + 2), 4)
    q, k, v, w = (jax.random.normal(kk, (1, H, S, D), jnp.bfloat16)
                  for kk in ks)

    def fwd_bwd(attn):
        def loss(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o.astype(jnp.float32)
                           * w.astype(jnp.float32)), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    kernel = jax.jit(fwd_bwd(
        lambda q, k, v: local_attention(q, k, v, causal=True))).lower(
            q, k, v)
    has_call = "tpu_custom_call" in kernel.as_text()
    (_, o_k), g_k = kernel.compile()(q, k, v)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        (_, o_r), g_r = jax.jit(fwd_bwd(
            lambda q, k, v: blockwise_attention(q, k, v, causal=True)))(
                f32(q), f32(k), f32(v))
    rel = {}
    for name, a, b in zip(("o", "dq", "dk", "dv"), (o_k, *g_k),
                          (o_r, *g_r)):
        a, b = f32(a), f32(b)
        check(bool(jnp.isfinite(a).all()), f"flash {name} not finite")
        rel[name] = round(float(jnp.max(jnp.abs(a - b))
                                / jnp.max(jnp.abs(b))), 5)
    return {"seq": S, "heads": H, "head_dim": D, "dtype": "bfloat16",
            "path": local_attention_path(
                backend=jax.default_backend(), seq=S, head_dim=D,
                dropout=False),
            "tpu_custom_call": has_call, "rel_err": rel,
            "tol": TOL["flash_vs_blockwise_rel"]}


def check_paged_walk(size: Size, engine, prompts) -> Dict:
    """The engine's decode program as it will run must lower to a
    ``tpu_custom_call``: each row walks its live blocks of the pool
    (ops/paged_attention.py). Its numbers: the paged logits over the
    first tokens of two serve prompts, few enough query rows a row
    (nn/attention._MAX_DIAG_ROWS) that ``Family.verify`` walks too,
    against the dense forward."""
    import jax.numpy as jnp

    from quintnet_tpu.nn.attention import _MAX_DIAG_ROWS

    lowered = engine._decode.fn.lower(
        engine.params, *engine.pool.caches(), jnp.asarray(engine._tok),
        jnp.asarray(engine._pos), jnp.asarray(engine._tables),
        jnp.asarray(engine._key_data))
    bs = size.block_size
    most = _MAX_DIAG_ROWS // size.cfg.n_head // bs * bs
    picked = [prompts[i][:most] for i in size.kernel_check]
    width = _width(picked, bs)
    gap = logits_gap(paged_logits(engine, picked, width),
                     dense_logits(size, engine.params, picked, width),
                     picked)
    return {"tpu_custom_call": "tpu_custom_call" in lowered.as_text(),
            "paged_vs_dense_logits": {
                **gap, "tol": TOL["paged_vs_dense_logits"]}}


def phase_kernels(size: Size, engine, prompts, seed: int) -> Dict:
    flash = [check_flash(size, seed, S) for S in size.flash_seqs]
    for f in flash:
        check(f["tpu_custom_call"],
              f"local_attention at seq {f['seq']} lowered without a "
              f"tpu_custom_call: it ran {f['path']}, not the kernel")
        check(max(f["rel_err"].values()) <= TOL["flash_vs_blockwise_rel"],
              f"flash vs blockwise at seq {f['seq']}: {f['rel_err']}")
    paged = check_paged_walk(size, engine, prompts)
    check(paged["tpu_custom_call"],
          "the engine's decode program has no tpu_custom_call")
    check(paged["paged_vs_dense_logits"]["max_abs_diff"]
          <= TOL["paged_vs_dense_logits"],
          f"walked vs dense logits: {paged['paged_vs_dense_logits']}")
    return {"flash_attention": flash, "paged_attention": paged}


# ---------------------------------------------------------------------
# the linear-attention + latent family, tiny
# ---------------------------------------------------------------------
def phase_kda_moe(seed: int, *, slots: int = 3, max_new: int = 8,
                  prompt_lens: Sequence[int] = (5, 20, 40, 60, 33)) -> Dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import reference_ling_hybrid as reference
    from quintnet_tpu.models.ling_hybrid import (LingHybridConfig,
                                                 ling_hybrid_init)
    from quintnet_tpu.serve import ServeEngine, ling_hybrid_family

    cfg = LingHybridConfig.tiny()
    params = ling_hybrid_init(jax.random.key(seed), cfg)
    engine = ServeEngine(
        ling_hybrid_family(cfg), params, max_slots=slots, block_size=8,
        num_blocks=64, max_seq_len=96, prefill_len=32,
        chunked_prefill=True, prefix_cache=False)
    engine.warmup()
    rng = np.random.default_rng([seed, 9])
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in prompt_lens]
    rids = [engine.submit(p, max_new) for p in prompts]
    engine.run()
    config = dataclasses.asdict(cfg)
    gap, spread = 0.0, 0.0
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(engine.result(rid))
        check(len(out) == len(prompt) + max_new,
              f"request {rid}: {len(out) - len(prompt)} of {max_new} tokens")
        logits, _, _ = reference.forward(
            params, jnp.asarray(out[None, :-1]), config,
            positions=list(range(len(prompt) - 1, len(out) - 1)))
        logits = np.asarray(logits[0])
        chosen = np.take_along_axis(
            logits, out[len(prompt):, None], axis=-1)[:, 0]
        gap = max(gap, float((logits.max(axis=-1) - chosen).max()))
        spread = max(spread, float(logits.std()))
    check(gap <= TOL["kda_moe_greedy_gap"],
          f"a greedy token lies {gap} under the reference's best logit")
    m = engine.metrics
    check(m.moe_dropped_tokens == 0, "the dropless router dropped")
    return {"requests": len(rids), "new_tokens": len(rids) * max_new,
            "greedy_gap": gap, "ref_std": spread,
            "prefill_chunks": m.prefill_chunks, "preempted": m.preempted,
            "state_bytes_per_slot": engine.pool.state_bytes_per_slot,
            "programs_of_the_engine": sorted(
                engine.recorder.static["programs"])}


# ---------------------------------------------------------------------
# --multichip
# ---------------------------------------------------------------------
def placement(tree) -> Dict:
    """Where a pytree's arrays actually live: how many arrays sit on
    each distinct set of device ids (read from every array's own
    shards), sharded arrays — those whose sharding is not fully
    replicated — apart from replicated ones."""
    import jax

    out: Dict[str, Dict[str, int]] = {"sharded": {}, "replicated": {}}
    for x in jax.tree.leaves(tree):
        ids = sorted({s.device.id for s in x.addressable_shards})
        kind = ("replicated" if x.sharding.is_fully_replicated
                else "sharded")
        key = ",".join(map(str, ids))
        out[kind][key] = out[kind].get(key, 0) + 1
    return out


def check_spread(name: str, place: Dict) -> None:
    check(place["sharded"], f"{name}: no array is sharded: {place}")
    check(all("," in ids for ids in place["sharded"]),
          f"{name}: a sharded array sits on one device: {place}")


def _devices_of(place: Dict) -> List[str]:
    return sorted({*place["sharded"], *place["replicated"]})


def mesh_builder(mesh) -> str:
    """Which branch of core/mesh.build_mesh built this mesh: the
    topology solver, or the plain reshape it falls back to when the
    solver raises (the fallback is silent there, so ask again here)."""
    import jax
    from jax.experimental import mesh_utils

    try:
        solved = mesh_utils.create_device_mesh(
            tuple(mesh.shape.values()), devices=jax.devices())
    except (ValueError, NotImplementedError, AssertionError) as e:
        return f"reshape (create_device_mesh raised {type(e).__name__}: {e})"
    same = ([d.id for d in mesh.devices.ravel()]
            == [d.id for d in solved.ravel()])
    return "create_device_mesh" if same else "reshape"


def phase_mesh_train(size: Size, seed: int, steps: int = 3) -> Dict:
    import jax

    one, _, _ = train_run(size, seed, mesh_dim=[1], mesh_name=["dp"],
                          devices=jax.devices()[:1], steps=steps)
    four, trainer, strategy = train_run(
        size, seed, mesh_dim=[2, 2], mesh_name=["dp", "tp"], steps=steps)
    diffs = [round(abs(a - b), 5) for a, b in zip(one, four)]
    check(max(diffs) <= TOL["mesh_vs_single_loss"],
          f"dp2 x tp2 vs one device: {four} vs {one}")
    params, opt_state = trainer.final_state
    place = {"params": placement(params), "opt_state": placement(opt_state)}
    for name, p in place.items():
        check_spread(name, p)
    return {"mesh": dict(strategy.mesh.shape),
            "mesh_devices": [d.id for d in strategy.mesh.devices.ravel()],
            "mesh_built_by": mesh_builder(strategy.mesh),
            "losses_one_device": one, "losses_dp2_tp2": four,
            "abs_diff": diffs, "tol": TOL["mesh_vs_single_loss"],
            "placement": place}


def phase_mesh_serve(size: Size, seed: int) -> Dict:
    import jax

    from quintnet_tpu.core.mesh import mesh_from_sizes
    from quintnet_tpu.fleet import ServeFleet
    from quintnet_tpu.models.gpt2 import gpt2_init, gpt2_to_tp_layout
    from quintnet_tpu.parallel.train_step import shard_pytree
    from quintnet_tpu.serve import gpt2_family

    params = gpt2_init(jax.random.key(seed), size.cfg)
    prompts = make_prompts(size, seed)
    picked = [prompts[i] for i in size.dense_check]
    width = _width(picked, size.block_size)

    single = build_engine(size, params)
    mesh = mesh_from_sizes(tp=2)
    tp_params = shard_pytree(
        mesh, gpt2_to_tp_layout(params, size.cfg, 2),
        gpt2_family(size.cfg).partition_specs("tp"))
    sharded = build_engine(size, tp_params, mesh=mesh, tp_axis="tp")
    gap = logits_gap(paged_logits(sharded, picked, width),
                     paged_logits(single, picked, width), picked)
    check(gap["max_abs_diff"] <= TOL["tp_vs_single_logits"],
          f"tp=2 vs mesh-less logits: {gap}")
    place = {"params": placement(sharded.params),
             "kv_pool": placement(sharded.pool.caches())}
    for name, p in place.items():
        check_spread(name, p)

    # where do two thread replicas of one fleet land? (reported, not
    # judged: placing replicas is ROADMAP D6's change)
    small = dataclasses.replace(size, num_blocks=2 * size.slots)
    fleet = ServeFleet(lambda: build_engine(small, params), n_replicas=2)
    try:
        replicas = {
            r.name: {"params": _devices_of(placement(r.engine.params)),
                     "kv_pool": _devices_of(placement(
                         r.engine.pool.caches()))}
            for r in fleet.replicas}
    finally:
        fleet.close()
    return {"tp_mesh": dict(mesh.shape),
            "tp_mesh_devices": [d.id for d in mesh.devices.ravel()],
            "tp_vs_single_logits": {**gap,
                                    "tol": TOL["tp_vs_single_logits"]},
            "placement": place,
            "mesh_less_engine_devices": _devices_of(placement(
                single.pool.caches())),
            "thread_fleet_replicas": replicas}


# ---------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the four-chip mesh phases (needs 4 "
                         "chips; the last line then has count 4)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every weight, document and prompt")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "chip_smoke"),
        help="output directory (phase lines, the scratch checkpoint)")
    args = ap.parse_args()

    sys.path.insert(0, REPO)
    from quintnet_tpu.core.runtime import enable_compilation_cache

    cache_dir = enable_compilation_cache()  # before first backend use

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX found "
            f"{devices[0].platform!r} ({devices[0].device_kind}). "
            f"Nothing was run.")
    if args.multichip and len(devices) < 4:
        raise SystemExit(
            f"--multichip needs 4 chips; JAX found {len(devices)}")

    os.makedirs(args.out, exist_ok=True)
    size = full_size()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    with open(os.path.join(args.out, "phases.jsonl"), "a") as sink:
        run_phase("start", lambda: {
            "device": device, "seed": args.seed,
            "multichip": args.multichip, "jax": jax.__version__,
            "compile_cache": cache_dir,
            "cache_from_env": bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR"))},
            sink)
        if args.multichip:
            run_phase("mesh_train",
                      lambda: phase_mesh_train(size, args.seed), sink)
            run_phase("mesh_serve",
                      lambda: phase_mesh_serve(size, args.seed), sink)
        else:
            run_phase("train",
                      lambda: phase_train(size, args.out, args.seed),
                      sink)
            served = {}

            def serve():
                rec, served["engine"], served["prompts"] = phase_serve(
                    size, args.seed)
                return rec

            run_phase("serve", serve, sink)
            run_phase("kernels", lambda: phase_kernels(
                size, served["engine"], served["prompts"], args.seed),
                sink)
            run_phase("kda_moe", lambda: phase_kda_moe(args.seed), sink)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
