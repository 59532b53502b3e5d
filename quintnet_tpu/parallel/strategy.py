"""Strategy facade: named parallelism bundles over one mesh.

Reference: ``get_strategy(name, pg_manager, config, ...)`` returning a
BaseStrategy whose ``apply(model)`` walks a coordinator that nests
wrappers in TP->PP->DP order (strategy/__init__.py:52-105,
coordinators/*.py). Seven strategies exist: dp, tp, pp, dp_tp, dp_pp,
tp_pp, 3d (coordinators/__init__.py:1-20).

Here a strategy is data, not machinery: which mesh axes participate in
what role. Composition is axis coexistence on a single mesh — there is
no wrapping order because there are no wrappers; the TP-innermost
preference survives only as mesh layout (tp on the fastest/minor axis,
core/mesh.py docstring).

A model plugs in through :class:`ModelSpec` (init / loss / specs /
pipeline fns); ``Strategy.make_train_step`` assembles the shard_map'd
step via parallel/train_step.py + parallel/pp.py.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from quintnet_tpu.core.config import Config
from quintnet_tpu.core.mesh import MeshSpec, build_mesh
from quintnet_tpu.obs.spans import setup_span
from quintnet_tpu.parallel.pp import (
    PipelineSpec,
    make_afab_loss_fn,
    make_1f1b_grad_fn,
    validate_pp,
)
from quintnet_tpu.parallel.train_step import (
    init_sharded_opt_state,
    init_zero1_opt_state,
    make_parallel_train_step,
    shard_pytree,
)

STRATEGY_AXES = {
    "single": (),
    "dp": ("dp",),
    "tp": ("tp",),
    "pp": ("pp",),
    "sp": ("sp",),
    "ep": ("ep",),
    "dp_tp": ("dp", "tp"),
    "dp_pp": ("dp", "pp"),
    "tp_pp": ("tp", "pp"),
    "dp_sp": ("dp", "sp"),
    "dp_ep": ("dp", "ep"),
    "ep_tp": ("ep", "tp"),
    "ep_pp": ("ep", "pp"),
    "3d": ("dp", "tp", "pp"),
    "3d_ep": ("dp", "tp", "pp", "ep"),
    "4d": ("dp", "tp", "pp", "sp"),
    "5d": ("dp", "tp", "pp", "sp", "ep"),
}


@dataclass
class ModelSpec:
    """What a model must provide to participate in any strategy.

    ``loss_fn(params, batch, tp_axis, sp_axis)`` -> scalar (whole model,
    non-pipelined); ``pipeline_fns(tp_axis, sp_axis)`` ->
    (embed_fn, stage_fn, head_loss_fn) per parallel/pp.py's convention;
    ``partition_specs(tp_axis, pp_axis)`` -> PartitionSpec pytree;
    ``to_tp_layout(params, tp)`` -> layout fixup (fused-QKV blocking);
    ``depth`` for pp divisibility validation.
    """

    init: Callable[[Any], Any]
    loss_fn: Callable
    partition_specs: Callable
    pipeline_fns: Callable
    to_tp_layout: Callable
    depth: int
    # optional: fn(batch_axes, sp_axis) -> PartitionSpec pytree for the
    # batch (e.g. GPT-2 shards the sequence dim over sp). Default: batch
    # dim over the data axes, everything else replicated.
    batch_specs: Optional[Callable] = None
    # optional eval hooks (Trainer.evaluate). Non-pp:
    # ``eval_metrics_fn(params, batch, tp_axis, sp_axis, ep_axis) ->
    # {name: scalar}`` (e.g. ViT adds accuracy — the metric the
    # reference headline reports, README 93.24%). Pipeline:
    # ``pipeline_eval_fns(tp_axis, sp_axis, ep_axis) ->
    # (embed_fn, stage_fn, head_metrics_fn)`` per
    # parallel/pp.py:make_afab_eval_fn. Defaults fall back to loss-only.
    eval_metrics_fn: Optional[Callable] = None
    pipeline_eval_fns: Optional[Callable] = None
    # True when loss_fn/pipeline fns take a dropout ``key`` kwarg that
    # must vary per step (the train step then derives per-device keys
    # from its ``seed`` argument — parallel/train_step.py).
    needs_rng: bool = False


@dataclass
class Strategy:
    name: str
    mesh: Mesh
    config: Config
    batch_axes: Tuple[str, ...]
    model_axes: Tuple[str, ...]   # redundant-loss axes (tp, sp)
    partial_axes: Tuple[str, ...]  # pipeline axes

    @property
    def uses_pp(self) -> bool:
        return any(self.mesh.shape.get(a, 1) > 1 for a in self.partial_axes)

    def axis_or_none(self, axis: str) -> Optional[str]:
        return axis if self.mesh.shape.get(axis, 1) > 1 else None

    @property
    def fsdp_axis(self) -> Optional[str]:
        """ZeRO-3/FSDP (training.fsdp): block params stored dp-sharded,
        per-layer all-gather inside the scan (nn/transformer.py)."""
        if self.config.training.fsdp and self.mesh.shape.get("dp", 1) > 1:
            return "dp"
        return None

    # -- placement helpers -------------------------------------------------
    def param_specs(self, model: ModelSpec):
        kw = {}
        if self.fsdp_axis is not None:
            kw["fsdp_axis"] = self.fsdp_axis
        return model.partition_specs(
            tp_axis=self.axis_or_none("tp"),
            pp_axis=self.axis_or_none("pp"),
            ep_axis=self.axis_or_none("ep"),
            **kw,
        )

    @property
    def is_multiprocess(self) -> bool:
        return jax.process_count() > 1

    def shard_params(self, model: ModelSpec, params):
        """Host/global params -> mesh-placed params (incl. tp layout fix).

        Multi-process: every process must hold the same host-global
        params (same init seed / same checkpoint); each materialises
        only its addressable shards (core/runtime.py) — the role the
        reference's per-rank sharded checkpoint reads play
        (distributed_loading.py:203-376).

        NOTE (single-process): ``jax.device_put`` may alias the input's
        buffers when a shard can reuse them in place; since
        ``make_train_step`` donates its params, the INPUT tree must be
        treated as consumed — copy first (``jax.tree.map(jnp.copy, ...)``)
        if you need it again.
        """
        tp = self.mesh.shape.get("tp", 1)
        params = model.to_tp_layout(params, tp)
        specs = self.param_specs(model)
        if self.is_multiprocess:
            from quintnet_tpu.core.runtime import global_array_from_host_data

            return jax.tree.map(
                lambda x, s: global_array_from_host_data(
                    NamedSharding(self.mesh, s), x),
                params, specs)
        return shard_pytree(self.mesh, params, specs)

    def batch_partition_specs(self, model: Optional[ModelSpec] = None):
        if model is not None and model.batch_specs is not None:
            return model.batch_specs(self.batch_axes,
                                     sp_axis=self.axis_or_none("sp"))
        return P(self.batch_axes if self.batch_axes else None)

    def shard_batch(self, batch, model: Optional[ModelSpec] = None):
        """HOST-GLOBAL batch -> mesh-placed batch. Multi-process: every
        process holds the global batch; only local shards transfer."""
        specs = self.batch_partition_specs(model)
        if isinstance(specs, P):
            specs = jax.tree.map(lambda _: specs, batch)
        with jax.profiler.TraceAnnotation("qn.train.shard_batch"):
            if self.is_multiprocess:
                from quintnet_tpu.core.runtime import \
                    global_array_from_host_data

                return jax.tree.map(
                    lambda x, s: global_array_from_host_data(
                        NamedSharding(self.mesh, s), x),
                    batch, specs)
            return jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                batch, specs,
            )

    def shard_batch_local(self, local_batch,
                          model: Optional[ModelSpec] = None,
                          global_batch_size: Optional[int] = None):
        """PROCESS-LOCAL batch slice -> global mesh-placed batch (true
        per-host feeding, the reference's DistributedSampler role —
        examples/full_3d.py:129-155). Each process passes only its own
        rows; see core/runtime.py:host_local_slice for which ones."""
        from quintnet_tpu.core.runtime import (
            global_array_from_process_data,
        )

        specs = self.batch_partition_specs(model)
        if isinstance(specs, P):
            specs = jax.tree.map(lambda _: specs, local_batch)
        return jax.tree.map(
            lambda x, s: global_array_from_process_data(
                NamedSharding(self.mesh, s), x),
            local_batch, specs)

    @property
    def zero1_axis(self) -> Optional[str]:
        """ZeRO-1/2 shard optimizer state over dp when the config asks
        for a zero1_*/zero2_* optimizer (reference stub:
        optimizers/zero.py)."""
        if (self.config.training.optimizer.startswith(("zero1", "zero2"))
                and self.mesh.shape.get("dp", 1) > 1):
            return "dp"
        return None

    @property
    def zero_stage(self) -> int:
        """2 = also reduce-scatter gradients (parallel/zero.make_zero2)."""
        return 2 if self.config.training.optimizer.startswith("zero2") else 1

    @setup_span("build")
    def init_opt_state(self, model: ModelSpec, optimizer, params):
        if self.zero1_axis is not None:
            state, _ = init_zero1_opt_state(
                optimizer, params, self.param_specs(model), self.mesh,
                axis=self.zero1_axis)
            return state
        state, _ = init_sharded_opt_state(
            optimizer, params, self.param_specs(model), self.mesh)
        return state

    # -- step construction -------------------------------------------------
    def make_train_step(self, model: ModelSpec,
                        optimizer: optax.GradientTransformation):
        cfg = self.config
        tp_axis = self.axis_or_none("tp")
        sp_axis = self.axis_or_none("sp")
        ep_axis = self.axis_or_none("ep")
        if self.config.training.fsdp and self.fsdp_axis is None:
            raise ValueError(
                "training.fsdp requires a dp mesh axis of size > 1 "
                f"(mesh: {dict(self.mesh.shape)}); with no dp axis "
                "there is nothing to shard over — remove the flag or "
                "add dp")
        if self.fsdp_axis is not None:
            if self.uses_pp:
                raise NotImplementedError(
                    "training.fsdp under pipeline parallelism is not "
                    "wired (stage fns receive raw block shards); use "
                    "dp/tp/sp/ep meshes, or zero1_*/zero2_* optimizers "
                    "with pp")
            if self.zero1_axis is not None:
                raise ValueError(
                    "training.fsdp already shards gradients and "
                    "optimizer state over dp (ZeRO-3 subsumes 1/2); "
                    "use a plain adam/adamw optimizer name with fsdp")
        specs = self.param_specs(model)

        if self.uses_pp:
            validate_pp(model.depth, self.mesh.shape["pp"])
            n_micro = cfg.training.gradient_accumulation_steps
            embed_fn, stage_fn, head_loss_fn = model.pipeline_fns(
                tp_axis=tp_axis, sp_axis=sp_axis, ep_axis=ep_axis)
            pspec = PipelineSpec(n_micro=n_micro, pp_axis="pp")
            sched = cfg.training.schedule.lower()
            if sched in ("1f1b", "one_f_one_b", "1f1b_stored"):
                grad_fn = make_1f1b_grad_fn(
                    embed_fn, stage_fn, head_loss_fn, pspec,
                    store_activations=(sched == "1f1b_stored"))
                return make_parallel_train_step(
                    self.mesh, None, optimizer, specs,
                    batch_axes=self.batch_axes,
                    model_axes=self.model_axes,
                    partial_axes=self.partial_axes,
                    grad_clip_norm=cfg.training.grad_clip_norm,
                    grad_fn=grad_fn,
                    zero1_axis=self.zero1_axis,
                    zero_stage=self.zero_stage,
                    batch_specs=self.batch_partition_specs(model),
                    needs_rng=model.needs_rng,
                )
            loss = make_afab_loss_fn(embed_fn, stage_fn, head_loss_fn, pspec)
            return make_parallel_train_step(
                self.mesh, loss, optimizer, specs,
                batch_axes=self.batch_axes,
                model_axes=self.model_axes,
                partial_axes=self.partial_axes,
                grad_clip_norm=cfg.training.grad_clip_norm,
                zero1_axis=self.zero1_axis,
                zero_stage=self.zero_stage,
                batch_specs=self.batch_partition_specs(model),
                needs_rng=model.needs_rng,
            )

        fsdp_kw = ({"fsdp_axis": self.fsdp_axis}
                   if self.fsdp_axis is not None else {})

        def loss(params, batch, key=None):
            return model.loss_fn(params, batch, tp_axis=tp_axis,
                                 sp_axis=sp_axis, ep_axis=ep_axis, key=key,
                                 **fsdp_kw)

        return make_parallel_train_step(
            self.mesh, loss, optimizer, specs,
            batch_axes=self.batch_axes,
            model_axes=self.model_axes,
            partial_axes=(),
            grad_accum_steps=cfg.training.gradient_accumulation_steps,
            grad_clip_norm=cfg.training.grad_clip_norm,
            zero1_axis=self.zero1_axis,
            zero_stage=self.zero_stage,
            batch_specs=self.batch_partition_specs(model),
            needs_rng=model.needs_rng,
        )


@setup_span("build")
def get_strategy(name: Optional[str] = None, config: Optional[Config] = None,
                 *, devices=None) -> Strategy:
    """Build a Strategy from a name + config (reference:
    strategy/__init__.py:52-105; names match the reference's seven plus
    the sp upgrades).

    ``name=None``/'auto' derives the strategy from which mesh axes have
    size > 1 in ``config.mesh``.
    """
    config = config or Config.from_dict({})
    sizes = dict(config.mesh.axis_sizes)

    if name in (None, "auto"):
        active = tuple(a for a, s in sizes.items() if s > 1)
        name = next(
            (k for k, v in STRATEGY_AXES.items() if tuple(sorted(v)) ==
             tuple(sorted(active))), None)
        if name is None:
            name = "custom"
    elif name not in STRATEGY_AXES:
        raise ValueError(
            f"unknown strategy {name!r}; known: {sorted(STRATEGY_AXES)}")

    if name != "custom":
        wanted = STRATEGY_AXES[name]
        for a in wanted:
            if sizes.get(a, 1) <= 1 and config.mesh.world_size > 1:
                raise ValueError(
                    f"strategy {name!r} needs mesh axis {a!r} > 1; mesh is "
                    f"{sizes}")

    # mesh always carries every configured axis (size-1 axes are free)
    spec = MeshSpec.from_config(config.mesh)
    mesh = build_mesh(spec, devices)

    # ep is a DATA axis: tokens are sharded over it (experts live on it);
    # see reduce_grads' sharded-over-data-axis rule in train_step.py
    batch_axes = tuple(a for a in ("dp", "ep") if a in sizes)
    model_axes = tuple(a for a in ("tp", "sp") if sizes.get(a, 1) > 1)
    partial_axes = tuple(a for a in ("pp",) if sizes.get(a, 1) > 1)

    return Strategy(
        name=name,
        mesh=mesh,
        config=config,
        batch_axes=batch_axes,
        model_axes=model_axes,
        partial_axes=partial_axes,
    )
