"""Driver of the training cells: ``get_strategy`` + ``Trainer`` on the
cell's mesh, fed packed documents, stepped for a fixed time.

The window is the stretch between two DRAINED points of the device
(``block_until_ready`` on everything the last step returned). Inside it
the loop is ``bench.py``'s: next batch, ``shard_batch``, the trainer's
own jitted step (``Trainer.step_fn``, which is ``strategy.
make_train_step`` under a recompile sentinel), with at most
``in_flight`` steps dispatched ahead of the device — the host never
drains the device inside the window, and never runs unboundedly ahead
of it either. ``Trainer.fit`` is not used for the window: it syncs on
its own schedule (``sync_every``, ``log_every``) and offers no drained
point at a time of the caller's choosing.
"""

from __future__ import annotations

import collections
import math
import time
from typing import Dict, Tuple

from benchmarks.lib.harness import annotate


def build(cell_spec: Dict, config: Dict, seed: int, devices=None):
    """(GPT2Config, ModelSpec, Strategy, Trainer) of a training cell on
    ``devices`` (attached, or described for an ahead-of-time compile)."""
    import jax.numpy as jnp

    from quintnet_tpu.core.config import Config
    from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_model_spec
    from quintnet_tpu.parallel.strategy import get_strategy
    from quintnet_tpu.train.trainer import Trainer

    t = cell_spec["trainer"]
    gcfg = GPT2Config.from_dict(config)
    cfg = Config.from_dict({
        "mesh_dim": list(t["mesh_dim"]), "mesh_name": list(t["mesh_name"]),
        "training": {"batch_size": int(t["batch"]), "epochs": 1,
                     "optimizer": t["optimizer"],
                     "learning_rate": float(t["learning_rate"]),
                     "grad_clip_norm": float(t["grad_clip_norm"]),
                     "dtype": t["dtype"], "remat": bool(t["remat"]),
                     "log_every": 0, "sync_every": 0,
                     "seed": seed & 0x7FFFFFFF},
    })
    model = gpt2_model_spec(gcfg, remat=cfg.training.remat_mode,
                            compute_dtype=jnp.dtype(t["dtype"]))
    strategy = get_strategy("auto", cfg, devices=devices)
    trainer = Trainer(cfg, model, strategy=strategy, task_type="clm",
                      log_fn=lambda _msg: None)
    return gcfg, model, strategy, trainer


class _Loop:
    """The step loop and its state."""

    def __init__(self, trainer, strategy, model, params, opt_state,
                 batches, seed: int, in_flight: int):
        self.trainer, self.strategy, self.model = trainer, strategy, model
        self.params, self.opt_state = params, opt_state
        self.batches = batches
        self.seed = seed & 0x7FFFFFFF
        self.in_flight = in_flight
        self.i = 0
        self.losses = []

    def step(self, batch=None):
        import jax.numpy as jnp

        if batch is None:
            with annotate("data_next"):
                batch = next(self.batches)
        with annotate("train_dispatch"):
            x, y = batch
            b = self.strategy.shard_batch(
                (jnp.asarray(x), jnp.asarray(y)), self.model)
            # Trainer.fit's per-step dropout seed (dropout is 0 here)
            seed = (self.seed * 2_000_003 + self.i) & 0x7FFFFFFF
            self.params, self.opt_state, loss = self.trainer.step_fn(
                self.params, self.opt_state, b, seed)
        self.i += 1
        self.losses.append(loss)
        return loss

    def drain(self) -> None:
        import jax

        with annotate("drain"):
            jax.block_until_ready((self.params, self.opt_state,
                                   self.losses[-1:]))

    def window(self, seconds: float) -> Tuple[int, float]:
        """Steps for ``seconds`` between two drained points. Returns
        (steps completed, wall seconds between the points)."""
        self.drain()
        ahead = collections.deque()
        n0 = self.i
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ahead.append(self.step())
            if len(ahead) > self.in_flight:
                ahead.popleft().block_until_ready()
        self.drain()
        return self.i - n0, time.perf_counter() - t0


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference, traffic
    from benchmarks.lib.harness import DeviceTrace
    from benchmarks.lib.weights import seeded_params
    from quintnet_tpu.data import prefetch_batches

    t_a = time.perf_counter()

    spec, t = ctx.cell.spec, ctx.cell.spec["trainer"]
    gcfg, model, strategy, trainer = build(
        spec, ctx.cell.config, ctx.seed, devices=ctx.devices)
    batch, seq = int(t["batch"]), int(ctx.cell.traffic["seq_len"])
    batches = prefetch_batches(traffic.document_batches(
        ctx.cell.traffic, gcfg.vocab_size, batch, ctx.seed), n=2)
    first = next(batches)

    # weights on the device in one jitted call from the seed; the
    # reference's loss on the first batch is taken on them before the
    # trainer's (donating) step consumes them
    p0 = seeded_params(model.init, ctx.seed)
    jax.block_until_ready(p0)
    t_b = time.perf_counter()
    ref_loss = reference.loss(p0, jnp.asarray(first[0]),
                              n_head=gcfg.n_head,
                              vocab_size=gcfg.vocab_size)
    t_c = time.perf_counter()
    params = strategy.shard_params(model, p0)
    del p0
    opt_state = strategy.init_opt_state(model, trainer.optimizer, params)

    loop = _Loop(trainer, strategy, model, params, opt_state, batches,
                 ctx.seed, int(t.get("in_flight", 2)))
    del params, opt_state
    loss0 = float(loop.step(first))
    for _ in range(int(t.get("warmup_steps", 3)) - 1):
        loop.step()
    loop.drain()
    t_d = time.perf_counter()
    tol = float(spec["correctness"]["loss_tolerance"])
    checks = {"loss_vs_reference": {
        "ok": bool(abs(loss0 - ref_loss) <= tol), "program": loss0,
        "reference": ref_loss, "tolerance": tol}}

    compiles0 = ctx.meter.compiles
    seconds = ctx.seconds - (ctx.trace_seconds if ctx.trace else 0.0)
    t_window = time.perf_counter()
    steps, wall = loop.window(seconds)
    reduced, traced_steps = None, 0
    if ctx.trace:
        tracer = DeviceTrace(ctx)
        tracer.start()
        traced_steps, _ = loop.window(ctx.trace_seconds)
        reduced = tracer.stop()
    compiles_in_window = ctx.meter.compiles - compiles0

    losses = [float(x) for x in loop.losses]
    bad = sum(1 for x in losses if not math.isfinite(x))
    trainer.assert_compile_count(steps=1)
    checks["no_compile_in_window"] = {"ok": compiles_in_window == 0,
                                      "compiles": compiles_in_window}
    chips = len(ctx.devices)
    tokens_per_step = batch * seq
    ctx.info({"train": {"steps": steps, "wall_s": wall,
                        "traced_steps": traced_steps,
                        "loss_first": losses[0], "loss_last": losses[-1],
                        "mesh": dict(strategy.mesh.shape), "batch": batch,
                        "seq": seq, "checks": checks,
                        "setup_parts_s": {
                            "to_driver": t_a - ctx.t_process_start,
                            "build_data_weights": t_b - t_a,
                            "reference_loss": t_c - t_b,
                            "state_and_warmup_steps": t_d - t_c}}})
    return {
        "checks": checks,
        "attempted": len(losses), "failed": bad,
        "setup_s": t_window - ctx.t_process_start,
        "end_to_end": {"train_tok_s": steps * tokens_per_step / wall / chips},
        "context": {
            "steps": steps, "window_s": wall, "chips": chips,
            "tokens_per_step": tokens_per_step, "seq_len": seq,
            "config": ctx.cell.config,
            "device_kind": ctx.devices[0].device_kind,
            "devices": ctx.devices, "trace": reduced,
            "traced_steps": traced_steps},
    }
