"""Trainers: config-driven epoch loops over strategy-built train steps.

Reference: ``Trainer`` (ViT classification, trainer.py:57-363) and
``GPT2Trainer`` (CLM/summarization, GPT2_Trainer.py:56-555). One class
covers both here (task_type switches metrics), because all parallelism
differences live in the Strategy — the loop does not care whether the
step underneath is single-device, DP, or a 3D 1F1B pipeline.

Differences from the reference worth knowing:
- metrics come back from the step already reduced (no MAX-allreduce
  metric propagation dance, trainer.py:168-187 — and no silent
  assumption that metrics are non-negative);
- checkpoints save sharded via train/checkpoint.py and RESUME works
  (the reference is save-only) — STEP-granular: the host-side cursor
  (epoch, step, epoch losses, History) rides in the checkpoint
  (quintnet_tpu/ft/), so a preempted run continues mid-epoch with
  bit-identical results to an uninterrupted one (tests/test_ft.py);
- a single process drives the whole mesh (SPMD), so "rank 0 only"
  logging guards are unnecessary.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from quintnet_tpu.core.config import Config
from quintnet_tpu.obs.spans import first_call, setup_span
from quintnet_tpu.parallel.strategy import ModelSpec, Strategy, get_strategy


def make_lr_schedule(cfg: Config):
    """LR schedule from config fields (the reference trains at constant
    lr only — trainer.py:89, GPT2_Trainer.py:100-104).

    ``lr_schedule``: constant | cosine | linear; ``warmup_steps``
    prepends a linear 0->peak ramp; cosine/linear decay to
    ``learning_rate * min_lr_ratio`` at step ``decay_steps`` (a TOTAL
    step count, warmup included). Returns a float for the plain
    constant case so optimizer states stay countless where possible.
    """
    t = cfg.training
    lr, name = t.learning_rate, t.lr_schedule.lower()
    if name == "constant":
        if not t.warmup_steps:
            return lr
        return optax.schedules.join_schedules(
            [optax.schedules.linear_schedule(0.0, lr, t.warmup_steps),
             optax.schedules.constant_schedule(lr)],
            [t.warmup_steps])
    if t.decay_steps <= t.warmup_steps:
        raise ValueError(
            f"lr_schedule={name!r} needs decay_steps > warmup_steps "
            f"(got decay_steps={t.decay_steps}, warmup={t.warmup_steps})")
    end = lr * t.min_lr_ratio
    if name == "cosine":
        return optax.schedules.warmup_cosine_decay_schedule(
            init_value=0.0 if t.warmup_steps else lr, peak_value=lr,
            warmup_steps=t.warmup_steps, decay_steps=t.decay_steps,
            end_value=end)
    if name == "linear":
        return optax.schedules.join_schedules(
            [optax.schedules.linear_schedule(
                0.0 if t.warmup_steps else lr, lr, max(t.warmup_steps, 1)),
             optax.schedules.linear_schedule(
                 lr, end, t.decay_steps - t.warmup_steps)],
            [t.warmup_steps])
    raise ValueError(f"unknown lr_schedule {t.lr_schedule!r}")


def masked_decay(weight_decay: float):
    """Decoupled weight decay skipping biases and norm scales/shifts.

    Standard practice (and what torch AdamW users hand-configure via
    param groups); the reference decays everything (GPT2_Trainer.py:100).
    Default mask: NAME-based — dict keys in core/pytree.DECAY_KEYS
    (weight matrices, embedding tables) decay; everything else is
    skipped. Name-based because an ndim test misclassifies
    stacked-block leaves (a stacked bias is [L, out] = ndim 2).

    Under ZeRO the optimizer runs on a flat chunk where per-leaf
    masking cannot see parameter boundaries, so the transform also
    accepts an ELEMENTWISE ``decay_mask`` extra arg (optax extra-args
    protocol); parallel/zero.py ravels the SAME mask alongside the
    params and passes its chunk — the two paths are bit-identical
    (tests/test_optimizer.py).
    """
    from quintnet_tpu.core.pytree import decay_mask as default_mask

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params, *, decay_mask=None, **extra):
        del extra
        if params is None:
            raise ValueError("masked_decay requires params")
        if decay_mask is None:
            decay_mask = default_mask(params)
        updates = jax.tree.map(
            lambda u, p, m: u + weight_decay * m.astype(u.dtype) * p,
            updates, params, decay_mask)
        return updates, state

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def make_optimizer(cfg: Config) -> optax.GradientTransformation:
    """Optimizer from config (reference: Adam in Trainer vs AdamW in
    GPT2Trainer — trainer.py:89 vs GPT2_Trainer.py:100; here one factory).
    zero1_* names shard the state over dp (parallel/zero.py). AdamW is
    built as scale_by_adam + masked_decay + lr so the decay composes
    with schedules exactly like optax.adamw (decay scaled by lr_t) while
    skipping LN/bias leaves."""
    t = cfg.training
    name = t.optimizer.lower()
    if name.startswith(("zero1_", "zero2_")):
        name = name[len("zero1_"):]
    lr = make_lr_schedule(cfg)
    # mu_dtype=bfloat16 halves the first-moment memory (planner: 'opt'
    # row); nu stays f32 (second moments span too many decades for bf16)
    mu = jnp.bfloat16 if t.adam_mu_dtype == "bfloat16" else None
    if name == "adam":
        return optax.adam(lr, mu_dtype=mu)
    if name == "adamw":
        return optax.chain(
            optax.scale_by_adam(mu_dtype=mu),
            masked_decay(0.01 if t.weight_decay is None
                         else t.weight_decay),
            optax.scale_by_learning_rate(lr),
        )
    if name == "sgd":
        return optax.sgd(lr)
    raise ValueError(f"unknown optimizer {t.optimizer!r}")


@dataclass
class History:
    train_loss: List[float] = field(default_factory=list)
    val_loss: List[float] = field(default_factory=list)
    train_metric: List[float] = field(default_factory=list)
    val_metric: List[float] = field(default_factory=list)
    wall_time_s: float = 0.0
    best_val_loss: float = float("inf")
    best_epoch: int = -1

    def to_jsonl(self, path: str):
        """One JSON line per epoch (loss/metrics) + a final summary line
        — greppable run record (the reference's only run record is
        stdout scrollback).

        Rewrites the whole file: safe because ``History`` is part of the
        checkpointed train cursor (ft/cursor.py), so after a restart the
        in-memory object holds the FULL run — pre-crash epochs included
        — and ``wall_time_s`` accumulates across restarts. (Before the
        cursor existed, this "w" open silently clobbered the pre-crash
        record with a fresh one.)"""
        import json

        with open(path, "w") as f:
            for i, tl in enumerate(self.train_loss):
                row = {"epoch": i, "train_loss": tl}
                for name, series in (("val_loss", self.val_loss),
                                     ("train_metric", self.train_metric),
                                     ("val_metric", self.val_metric)):
                    if i < len(series):
                        row[name] = series[i]
                f.write(json.dumps(row) + "\n")
            f.write(json.dumps({
                "wall_time_s": round(self.wall_time_s, 2),
                "best_val_loss": self.best_val_loss,
                "best_epoch": self.best_epoch}) + "\n")


def _call_batches_fn(fn, epoch: int, skip: int):
    """Call a train/val batches factory, passing the mid-epoch resume
    offset to factories that accept it.

    Returns ``(iterable, skip_consumed)``: the offset is handed to the
    factory ONLY when it declares a parameter literally named ``start``
    or ``start_batch`` (second positional, or keyword-only) — it then
    handles the skip itself (the map-style iterators in
    data/datasets.py slice the shuffled index — zero data touched).
    Matching by NAME, not arity, keeps unrelated two-argument factories
    (``lambda ep, shuffle=True: ...``) safe from a silently hijacked
    second parameter. Everything else gets the generic
    consume-and-discard skip in ``fit``. A matching offset parameter is
    passed even when the offset is 0, so it may be a required one.
    """
    names = ("start", "start_batch")
    try:
        import inspect

        ps = list(inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):  # builtins/partials w/o signature
        ps = None
    if ps is not None:
        if (len(ps) >= 2
                and ps[1].kind in (ps[1].POSITIONAL_ONLY,
                                   ps[1].POSITIONAL_OR_KEYWORD)
                and ps[1].name in names):
            return fn(epoch, skip), True
        kw = next((p.name for p in ps
                   if p.kind == p.KEYWORD_ONLY and p.name in names), None)
        if kw is not None:
            return fn(epoch, **{kw: skip}), True
    return fn(epoch), False


def _span(name: str):
    """Decorator: the call is a host span ``name`` on the profiler's
    clock — a no-op without a profiler session. The ``qn.train.*``
    vocabulary is in docs/observability.md."""
    return functools.partial(jax.profiler.annotate_function, name=name)


class Trainer:
    """fit() over (x, y) batch iterables.

    ``task_type``: 'classification' (metric: accuracy, pp=1 only) or
    'clm' (metric: perplexity).
    """

    @setup_span("build")
    def __init__(self, config: Config, model: ModelSpec,
                 *, strategy: Optional[Strategy] = None,
                 optimizer: Optional[optax.GradientTransformation] = None,
                 task_type: str = "classification",
                 checkpoint_dir: Optional[str] = None,
                 log_fn: Callable[[str], None] = print):
        self.config = config
        self.model = model
        self.strategy = strategy or get_strategy(config.strategy_name, config)
        self.optimizer = optimizer or make_optimizer(config)
        self.task_type = task_type
        self.checkpoint_dir = checkpoint_dir
        self.log = log_fn
        if self.strategy.is_multiprocess and jax.process_index() != 0:
            # one SPMD log per job, not per host (reference: rank-0 tqdm
            # guards); checkpoint saves stay collective on every process
            self.log = lambda msg: None

        # recompile sentinel (analysis/recompile.py): observe-only — a
        # legit recompile exists (a differently-shaped final batch), but
        # each one is logged with the signature diff so shape drift is
        # named in the log, not guessed from a slow step
        from quintnet_tpu.analysis.recompile import RecompileSentinel

        self.step_fn = RecompileSentinel(
            "train.step",
            self.strategy.make_train_step(self.model, self.optimizer),
            on_recompile=self._on_recompile)
        self._eval_fn = None
        self._last_ckpt_step = None  # newest orbax step written/restored
        # steps the restore fallback proved unreadable: replay re-reaches
        # them and must REWRITE (save force=True), or the corrupt step
        # would shadow every future save attempt at that step and each
        # new preemption would fall back to the same old good step
        self._bad_ckpt_steps: set = set()
        # whether the newest checkpoint carries a mid-epoch cursor —
        # lets the epoch-boundary save heal a cadence save that landed
        # on the epoch's final batch (same global_step, boundary shape)
        self._last_ckpt_midepoch = False

    def _on_recompile(self, name: str, count: int, diff: str) -> None:
        self.log(f"{name}: lowering #{count} — {diff}")

    def assert_compile_count(self, steps: int = 1,
                             evals: Optional[int] = None) -> None:
        """Enforce the one-compiled-program promise after a run: the
        step (and optionally eval) function lowered exactly N times.
        Raises RecompileError with a signature diff otherwise."""
        self.step_fn.assert_compile_count(steps)
        if evals is not None and self._eval_fn is not None:
            self._eval_fn.assert_compile_count(evals)

    # -- state -------------------------------------------------------------
    def init_state(self, seed: Optional[int] = None):
        seed = self.config.training.seed if seed is None else seed
        host_params = self.model.init(jax.random.key(seed))
        params = self.strategy.shard_params(self.model, host_params)
        opt_state = self.strategy.init_opt_state(self.model, self.optimizer,
                                                 params)
        return params, opt_state

    def resume_or_init(self, seed: Optional[int] = None):
        """Epoch-level view of :meth:`resume_state` kept for callers that
        only schedule whole epochs. Returns (params, opt_state,
        start_epoch). A MID-EPOCH checkpoint (cadence save / emergency
        snapshot) cannot be expressed as an epoch boundary — handing it
        back as one would make an external epoch loop re-apply the
        epoch's first steps on top of params that already contain them —
        so this raises instead; drive the run through :meth:`fit`
        (step-granular resume) or :meth:`resume_state` in that case."""
        params, opt_state, cursor = self.resume_state(seed)
        if cursor is not None and cursor.step_in_epoch:
            raise RuntimeError(
                f"latest checkpoint is mid-epoch (epoch {cursor.epoch} "
                f"step {cursor.step_in_epoch}, global step "
                f"{cursor.global_step}); resume_or_init only hands back "
                "epoch boundaries — resume via Trainer.fit() "
                "(step-granular), or resume_state() and pass its cursor "
                "to fit(params=..., opt_state=..., cursor=...)")
        return params, opt_state, (cursor.epoch if cursor is not None else 0)

    def resume_state(self, seed: Optional[int] = None, *, goodput=None,
                     chaos=None):
        """Restore the newest checkpoint that loads (corrupt steps fall
        back to the previous good one — ft/restore.py), else fresh init.

        Returns ``(params, opt_state, cursor)`` where ``cursor`` is a
        :class:`~quintnet_tpu.ft.cursor.TrainCursor` pointing at the
        next (epoch, step) to run — None on fresh init. Checkpoints
        written before the cursor existed degrade to epoch granularity.
        """
        params, opt_state = self.init_state(seed)
        if not self.checkpoint_dir:
            return params, opt_state, None
        mgr = self._manager()
        if mgr.latest_step() is None:
            return params, opt_state, None
        from quintnet_tpu.ft.cursor import TrainCursor
        from quintnet_tpu.ft.restore import restore_with_fallback

        t_restore = time.time()
        state, cursor_dict, step, skipped = restore_with_fallback(
            mgr, {"params": params, "opt": opt_state, "epoch": 0},
            chaos=chaos, log=self.log)
        self._last_ckpt_step = step
        self._bad_ckpt_steps = set(skipped)
        cursor = TrainCursor.from_dict(cursor_dict)
        self._last_ckpt_midepoch = (cursor is not None
                                    and cursor.step_in_epoch != 0)
        if cursor is None:
            # legacy cursor-less checkpoint: orbax steps were EPOCH
            # indices. Anchor global_step at the restored index so new
            # (global-step-indexed) saves — including an emergency
            # snapshot on the very first resumed steps — sort strictly
            # after it and are never skipped by the save_state guard.
            cursor = TrainCursor(epoch=int(state["epoch"]) + 1,
                                 global_step=step)
        if goodput is not None:
            goodput.on_resume(cursor.global_step, time.time() - t_restore,
                              len(skipped))
        self.log(f"resumed from checkpoint step {step}: continuing at "
                 f"epoch {cursor.epoch} step {cursor.step_in_epoch} "
                 f"(global step {cursor.global_step})")
        return state["params"], state["opt"], cursor

    def _manager(self, *, best: bool = False):
        """Cached CheckpointManager(s) — one per directory, reused across
        epochs (a fresh manager per save re-lists the directory and
        resets orbax's async machinery)."""
        from quintnet_tpu.train.checkpoint import CheckpointManager

        if not hasattr(self, "_mgrs"):
            self._mgrs = {}
        key = "best" if best else "main"
        if key not in self._mgrs:
            self._mgrs[key] = (
                CheckpointManager(self.checkpoint_dir.rstrip("/") + "-best",
                                  max_to_keep=1) if best
                else CheckpointManager(self.checkpoint_dir))
        return self._mgrs[key]

    def save(self, epoch: int, params, opt_state):
        """Epoch-indexed save without a cursor — external callers that
        drive their own loop. ``fit`` itself saves via
        :meth:`save_state` (global-step indexed, cursor attached)."""
        if not self.checkpoint_dir:
            return
        # async: orbax snapshots device arrays before returning, then
        # writes in the background — the next epoch's compute overlaps
        # the IO. fit() barriers at the end (wait_for_saves).
        self._manager().save(
            epoch, {"params": params, "opt": opt_state, "epoch": epoch},
            wait=False)

    @_span("qn.train.save")
    def save_state(self, params, opt_state, cursor, *,
                   wait: bool = False, boundary: bool = False) -> float:
        """Checkpoint arrays + train cursor at orbax step
        ``cursor.global_step``. Returns host-blocking seconds (goodput's
        checkpoint-overhead figure). Skips steps already on disk — a
        resumed run revisits the boundary it restored from (the state is
        identical by construction, rewriting it buys nothing) — with two
        exceptions: a step the restore fallback proved UNREADABLE is
        rewritten in place (force), and an epoch-boundary save
        (``boundary=True``) whose global step equals a just-written
        mid-epoch cadence save rewrites it synchronously so the newest
        on-disk cursor reflects the true epoch boundary
        (:meth:`resume_or_init` would otherwise refuse a run that in
        fact sits at one)."""
        if not self.checkpoint_dir:
            return 0.0
        step = cursor.global_step
        force = step in self._bad_ckpt_steps
        if self._last_ckpt_step is not None and step <= self._last_ckpt_step:
            heal = (boundary and step == self._last_ckpt_step
                    and self._last_ckpt_midepoch)
            if not heal:
                return 0.0
            # cadence landed on the epoch's final batch: same arrays,
            # but the cursor on disk is mid-epoch-shaped. Rewrite with
            # the boundary cursor (synchronous — the delete+rewrite
            # window must not outlive this call).
            force, wait = True, True
        t = time.time()
        # the state's "epoch" is the epoch the arrays were produced in
        # (end-of-epoch cursors already point at epoch+1) — what the
        # single-device verifiers report (tools/verify_vit.py)
        epoch = (cursor.epoch - 1 if cursor.step_in_epoch == 0
                 else cursor.epoch)
        self._manager().save(
            step, {"params": params, "opt": opt_state, "epoch": epoch},
            cursor=cursor.to_dict(), wait=wait, force=force)
        self._last_ckpt_step = step
        self._last_ckpt_midepoch = cursor.step_in_epoch != 0
        self._bad_ckpt_steps.discard(step)
        return time.time() - t

    @_span("qn.train.save")
    def save_best(self, epoch: int, params, opt_state, val_loss: float):
        """Best-by-val-loss retention in a sibling ``<dir>-best``
        directory (one kept), alongside the rolling epoch saves —
        reference: best-and-final per-shard save, GPT2_Trainer.py:453-507.
        Sibling, not subdir, so orbax's step listing of the main
        directory never sees a non-numeric entry."""
        if not self.checkpoint_dir:
            return
        self._manager(best=True).save(
            epoch, {"params": params, "opt": opt_state, "epoch": epoch,
                    "val_loss": val_loss}, wait=False)

    @_span("qn.train.save")
    def wait_for_saves(self):
        """Barrier on in-flight async checkpoint writes."""
        for mgr in getattr(self, "_mgrs", {}).values():
            mgr.wait_until_finished()

    # -- evaluation --------------------------------------------------------
    def _build_eval(self):
        """One jitted eval step returning ``{name: scalar}`` device
        metrics — loss always; accuracy for classification (incl. under
        pp, via the forward-only pipeline eval gathering last-stage
        metrics — the reference cannot report its headline 93.24% val
        accuracy under pp at all)."""
        if self._eval_fn is not None:
            return self._eval_fn
        from jax.sharding import PartitionSpec as P

        from quintnet_tpu.core import collectives as cc

        strat = self.strategy
        specs = strat.param_specs(self.model)
        tp_axis = strat.axis_or_none("tp")
        sp_axis = strat.axis_or_none("sp")
        ep_axis = strat.axis_or_none("ep")
        fsdp_kw = ({"fsdp_axis": strat.fsdp_axis}
                   if strat.fsdp_axis is not None else {})

        if strat.uses_pp:
            from quintnet_tpu.parallel.pp import (PipelineSpec,
                                                  make_afab_eval_fn)

            pspec = PipelineSpec(
                n_micro=self.config.training.gradient_accumulation_steps)
            if self.model.pipeline_eval_fns is not None:
                embed_fn, stage_fn, head_metrics_fn = \
                    self.model.pipeline_eval_fns(
                        tp_axis=tp_axis, sp_axis=sp_axis, ep_axis=ep_axis)
            else:
                from quintnet_tpu.parallel.pp import SplitHead

                embed_fn, stage_fn, head = self.model.pipeline_fns(
                    tp_axis=tp_axis, sp_axis=sp_axis, ep_axis=ep_axis)
                if isinstance(head, SplitHead):
                    head_metrics_fn = SplitHead(
                        head.local_fn,
                        lambda local, y, valid:
                            {"loss": head.reduce_fn(local, y, valid)})
                else:
                    def head_metrics_fn(p, h, y, _h=head):
                        return {"loss": _h(p, h, y)}

            metrics_fn = make_afab_eval_fn(
                embed_fn, stage_fn, head_metrics_fn, pspec)
        elif self.model.eval_metrics_fn is not None:
            def metrics_fn(p, b):
                return self.model.eval_metrics_fn(
                    p, b, tp_axis=tp_axis, sp_axis=sp_axis, ep_axis=ep_axis,
                    **fsdp_kw)
        else:
            def metrics_fn(p, b):
                return {"loss": self.model.loss_fn(
                    p, b, tp_axis=tp_axis, sp_axis=sp_axis, ep_axis=ep_axis,
                    **fsdp_kw)}

        # named for the device trace: the program reads ``jit_eval_step``
        # there, beside the train step's ``jit_local_step``
        def eval_step(p, b):
            mets = metrics_fn(p, b)
            if strat.batch_axes:
                mets = jax.tree.map(
                    lambda v: jax.lax.pmean(v, strat.batch_axes), mets)
            return mets

        from quintnet_tpu.analysis.recompile import RecompileSentinel

        batch_spec = strat.batch_partition_specs(self.model)
        # donate the batch: evaluate() ships a fresh device batch per
        # call and never touches it again, so its buffer can be freed
        # as soon as the forward consumes it instead of after the call
        # (the donation report flagged eval/validation loops as the
        # undonated ones — train steps already donate params/opt_state)
        self._eval_fn = RecompileSentinel(
            "train.eval",
            jax.jit(cc.shard_map_fn(
                eval_step, strat.mesh,
                in_specs=(specs, batch_spec),
                out_specs=P()), donate_argnums=(1,)),
            on_recompile=self._on_recompile)
        return self._eval_fn

    @_span("qn.train.eval")
    def evaluate(self, params, batches: Iterable) -> Dict[str, float]:
        import warnings

        eval_fn = self._build_eval()
        acc: Dict[str, list] = {}

        def fresh(v):
            # eval_fn donates the batch. For host inputs (the normal
            # case) shard_batch builds a new device buffer, so donation
            # is free; a DEVICE-resident input may pass through
            # device_put unchanged and donation would delete the
            # caller's array — copy those first (the copy is what
            # donation consumes).
            return jnp.copy(v) if isinstance(v, jax.Array) \
                else jnp.asarray(v)

        with warnings.catch_warnings():
            # metric outputs are scalars, so XLA cannot ALIAS the
            # donated batch and warns it went unaliased — expected;
            # scoped here so genuine donation mistakes elsewhere still
            # warn
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            for xb, yb in batches:
                b = self.strategy.shard_batch((fresh(xb), fresh(yb)),
                                              self.model)
                # the first call compiles or loads the program:
                # ``qn.setup.warmup/jit_eval_step`` (obs/spans.py)
                with (contextlib.nullcontext() if eval_fn.compile_count
                      else first_call("eval_step")):
                    mets = eval_fn(params, b)
                for k, v in mets.items():
                    acc.setdefault(k, []).append(v)  # device scalars
        out = {k: float(np.mean([float(v) for v in vs]))
               for k, vs in acc.items()}
        out.setdefault("loss", float("nan"))
        if self.task_type == "clm":
            out["perplexity"] = float(np.exp(min(out["loss"], 20.0)))
        return out

    # -- training ----------------------------------------------------------
    def fit(self, train_batches_fn: Callable[[int], Iterable],
            *, epochs: Optional[int] = None,
            val_batches_fn: Optional[Callable[[int], Iterable]] = None,
            params=None, opt_state=None, cursor=None, ft=None) -> History:
        """``train_batches_fn(epoch) -> iterable of (x, y)`` host batches
        (global batch size; sharding happens here). A factory whose
        second parameter is named ``start`` or ``start_batch`` (second
        positional or keyword-only) receives the mid-epoch resume
        offset and lets map-style data skip to it for free
        (data/datasets.py ``start_batch=``); other factories are
        skipped generically (each skipped batch is materialised and
        discarded).

        Explicit state: ``fit(params=..., opt_state=...)`` skips the
        automatic resume; pass the matching ``cursor`` from
        :meth:`resume_state` to continue that state's run mid-stream
        (without one, the explicit state is treated as a FRESH run from
        epoch 0).

        ``ft``: optional :class:`~quintnet_tpu.ft.FTContext` wiring in
        preemption handling, fault injection, and goodput accounting.
        Step-granular cadence saves are controlled by
        ``training.save_every_steps`` / ``save_every_seconds`` and work
        with or without an ``ft`` context.
        """
        from quintnet_tpu.ft.cursor import TrainCursor
        from quintnet_tpu.ft.preempt import (CadenceController,
                                             TrainingPreempted)

        epochs = epochs or self.config.training.epochs
        if ft is not None and ft.preemption is not None \
                and not self.checkpoint_dir:
            # the preemption contract is "emergency snapshot saved, exit
            # 75, relaunch me" — without a checkpoint_dir the snapshot
            # writes nowhere and every relaunch would silently restart
            # from epoch 0 while the logs claim snapshots were saved
            raise ValueError(
                "FTContext.preemption requires a checkpoint_dir: a "
                "preemption snapshot with nowhere to write would make "
                "the exit-75 relaunch contract silently discard the run "
                "— pass checkpoint_dir= to Trainer, or drop the "
                "PreemptionHandler from the context")
        if params is None:
            params, opt_state, cursor = self.resume_state(
                goodput=ft.goodput if ft is not None else None,
                chaos=ft.chaos if ft is not None else None)
        elif cursor is None:
            # explicit fresh state: its trajectory owes nothing to
            # whatever checkpoint this trainer touched earlier — don't
            # let a stale high-water mark suppress its saves
            self._last_ckpt_step = None
        if cursor is None:
            cursor = TrainCursor(seed=self.config.training.seed)
        if (cursor.seed is not None
                and cursor.seed != self.config.training.seed):
            raise RuntimeError(
                f"checkpoint was written with training.seed="
                f"{cursor.seed} but the config now says "
                f"{self.config.training.seed}; dropout seeds and data "
                "order derive from the seed, so resuming would silently "
                "diverge from the original run — restore the original "
                "seed (or start a fresh run directory)")
        hist = cursor.history
        # wall_time_s accumulates across restarts: this process adds its
        # own elapsed time on top of what the cursor carried in
        prior_wall = hist.wall_time_s
        global_step = cursor.global_step
        start_epoch, resume_step = cursor.epoch, cursor.step_in_epoch
        t0 = time.time()
        log_every = self.config.training.log_every
        cadence = CadenceController(self.config.training.save_every_steps,
                                    self.config.training.save_every_seconds)
        # arm from the restored step: the state at global_step was just
        # read from disk, re-saving it one step later buys nothing
        cadence.saved(global_step)

        for epoch in range(start_epoch, epochs):
            # losses stay DEVICE scalars during the epoch — no per-step
            # host sync blocking async dispatch (the reference blocks on
            # .item() every step; so did round 1's float(loss)). Host
            # reads (flushes into the running epoch sum) happen only at
            # checkpoint boundaries and epoch end.
            losses = []
            skip = resume_step if epoch == start_epoch else 0
            # running float64 sum/count of this epoch's host-synced step
            # losses. Sequential f64 accumulation is the SAME computation
            # in an uninterrupted and a resumed run (JSON round-trips
            # binary64 exactly), so the epoch mean is bit-identical while
            # the cursor stays O(1) — no per-step list rides in it.
            loss_sum = cursor.loss_sum if skip else 0.0
            loss_count = cursor.loss_count if skip else 0
            n_flushed = 0

            def flush():
                nonlocal n_flushed, loss_sum, loss_count
                with jax.profiler.TraceAnnotation("qn.train.sync"):
                    for dev_loss in losses[n_flushed:]:
                        # deliberate sync: flush runs only at checkpoint
                        # boundaries and epoch end, never per step
                        loss_sum += float(dev_loss)  # qtcheck: ok[QT104]
                        loss_count += 1
                n_flushed = len(losses)

            def cursor_at(next_epoch, next_step):
                hist.wall_time_s = prior_wall + (time.time() - t0)
                at_boundary = next_step == 0
                return TrainCursor(
                    epoch=next_epoch, step_in_epoch=next_step,
                    global_step=global_step,
                    # an epoch boundary starts the next epoch's record
                    # fresh; mid-epoch cursors carry the sum so far
                    loss_sum=0.0 if at_boundary else loss_sum,
                    loss_count=0 if at_boundary else loss_count,
                    history=hist, seed=self.config.training.seed)

            t_win = time.time()
            sync_every = self.config.training.sync_every
            batches, skip_consumed = _call_batches_fn(
                train_batches_fn, epoch, skip)
            if skip and not skip_consumed:
                from quintnet_tpu.data.datasets import skip_batches

                batches = skip_batches(batches, skip)
            if self.config.training.prefetch:
                from quintnet_tpu.data import prefetch_batches

                batches = prefetch_batches(
                    iter(batches), n=self.config.training.prefetch)
            it = iter(batches)
            for i in itertools.count(skip):
                # one step = one ``qn.train.step`` on the profiler's
                # clock, with the wait for data, the dispatch (inside
                # step_fn), the windowed syncs and the saves inside it
                with jax.profiler.StepTraceAnnotation(
                        "qn.train.step", step_num=global_step):
                    with jax.profiler.TraceAnnotation(
                            "qn.train.data_wait"):
                        item = next(it, None)
                    if item is None:
                        break
                    xb, yb = item
                    batch = self.strategy.shard_batch(
                        (jnp.asarray(xb), jnp.asarray(yb)), self.model)
                    # per-step dropout seed: deterministic in (config
                    # seed, epoch, step) so a step-granular resume
                    # (ft/TrainCursor) replays the exact same dropout
                    # sequence mid-epoch
                    seed = (self.config.training.seed * 2_000_003
                            + epoch * 1_000_003 + i) & 0x7FFFFFFF
                    params, opt_state, loss = self.step_fn(
                        params, opt_state, batch, seed)
                    losses.append(loss)
                    global_step += 1
                    if sync_every and (i + 1) % sync_every == 0:
                        # bound async run-ahead (training.sync_every docs)
                        with jax.profiler.TraceAnnotation("qn.train.sync"):
                            float(loss)  # qtcheck: ok[QT104] — windowed
                    if log_every and (i + 1) % log_every == 0:
                        # the float() is the device sync for the window,
                        # so the wall clock measured here is honest
                        # throughput
                        with jax.profiler.TraceAnnotation("qn.train.sync"):
                            window = float(  # qtcheck: ok[QT104] — sync
                                jnp.mean(jnp.stack(losses[-log_every:])))
                        dt = time.time() - t_win
                        sps = log_every * len(xb) / max(dt, 1e-9)
                        msg = (f"epoch {epoch} step {i + 1}: "
                               f"loss {window:.4f} | {sps:.1f} samples/s")
                        if self.task_type == "clm":
                            msg += (f" ({sps * xb.shape[1] / 1e3:.1f}k "
                                    f"tok/s)")
                        self.log(msg)
                        t_win = time.time()
                    # -- fault-tolerance boundary (after the step landed)
                    if ft is not None:
                        if ft.goodput is not None:
                            # the loss rides along so the meter can
                            # sync on the last step's device work before
                            # reading its wall clock (ft/goodput.py)
                            ft.goodput.on_step(global_step, loss)
                        if ft.chaos is not None:
                            # may os._exit / SIGTERM self / raise
                            # ChaosKilled
                            ft.chaos.on_step_end(global_step)
                    if ft is not None and ft.preemption_requested:
                        # finish-the-step-then-save: the in-flight step
                        # above already landed; one SYNCHRONOUS emergency
                        # snapshot
                        flush()
                        blocked = self.save_state(
                            params, opt_state, cursor_at(epoch, i + 1),
                            wait=True)
                        if ft.goodput is not None:
                            ft.goodput.on_save(blocked)
                        self.log(f"preempted: emergency snapshot at epoch "
                                 f"{epoch} step {i + 1} (global step "
                                 f"{global_step})")
                        raise TrainingPreempted(epoch, i + 1, global_step)
                    if cadence.should_save(global_step):
                        flush()
                        blocked = self.save_state(
                            params, opt_state, cursor_at(epoch, i + 1))
                        if ft is not None and ft.goodput is not None:
                            ft.goodput.on_save(blocked)
                        cadence.saved(global_step)
            flush()
            # host-side sequential f64 mean (not a device jnp.mean):
            # identical value whether the epoch ran in one process or
            # resumed mid-way from the checkpointed running sum
            train_loss = (loss_sum / loss_count if loss_count
                          else float("nan"))
            hist.train_loss.append(train_loss)
            msg = f"epoch {epoch}: train_loss {train_loss:.4f}"
            if self.task_type == "clm":
                ppl = float(np.exp(min(train_loss, 20.0)))
                hist.train_metric.append(ppl)
                msg += f" ppl {ppl:.2f}"
            if val_batches_fn is not None:
                ev = self.evaluate(params, val_batches_fn(epoch))
                hist.val_loss.append(ev["loss"])
                msg += f" | val_loss {ev['loss']:.4f}"
                for k in ("perplexity", "accuracy"):
                    if k in ev:
                        hist.val_metric.append(ev[k])
                        msg += f" val_{k} {ev[k]:.4f}"
                if ev["loss"] < hist.best_val_loss:
                    hist.best_val_loss = ev["loss"]
                    hist.best_epoch = epoch
                    self.save_best(epoch, params, opt_state, ev["loss"])
                    msg += " (best)"
            self.log(msg)
            blocked = self.save_state(params, opt_state,
                                      cursor_at(epoch + 1, 0),
                                      boundary=True)
            if ft is not None and ft.goodput is not None:
                ft.goodput.on_save(blocked)
            cadence.saved(global_step)
            if ft is not None and ft.preemption_requested:
                # SIGTERM landed during eval / epoch-boundary work (the
                # per-step poll only sees it after a step): the state at
                # this boundary is already written above — barrier it to
                # disk and hand control to the supervisor instead of
                # starting an epoch we will not finish
                t_b = time.time()
                self.wait_for_saves()
                if ft.goodput is not None:
                    ft.goodput.on_save(time.time() - t_b)
                self.log(f"preempted: epoch {epoch} checkpoint durable "
                         f"(global step {global_step})")
                raise TrainingPreempted(epoch + 1, 0, global_step)

        t_barrier = time.time()
        self.wait_for_saves()
        if ft is not None and ft.goodput is not None:
            ft.goodput.on_save(time.time() - t_barrier)
        hist.wall_time_s = prior_wall + (time.time() - t0)
        self._final_state = (params, opt_state)
        return hist

    @property
    def final_state(self):
        """(params, opt_state) after the last fit() epoch."""
        return getattr(self, "_final_state", None)
