"""Generic SPMD train-step builder for DP x TP (x SP) meshes.

One shard_map'd function subsumes the reference's DataParallel wrapper,
TP coordinator and (non-pipeline) Trainer step: batch sharded over data
axes, params laid out by PartitionSpec rules, one grad-reduction pass,
optimizer update executed on local shards.

Grad reduction rule (parallel/tp.py docstring): a param's gradient is
- psummed over every *model* axis the param is replicated over (tp/sp
  shard the computation, so replicated-param grads arrive as partial
  sums — e.g. LayerNorms under TP; the reference omits this sync);
- pmeaned over the data axes (the reference's DDP bucket allreduce+mean,
  ddp.py:113-125, intended semantics per SURVEY §2.2).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from quintnet_tpu.core import collectives as cc
from quintnet_tpu.obs.spans import first_call
from quintnet_tpu.parallel.dp import accumulate_grads


def _spec_axes(spec) -> set:
    """Mesh axis names appearing in a PartitionSpec."""
    axes = set()
    for part in spec:
        if part is None:
            continue
        if isinstance(part, (tuple, list)):
            axes.update(part)
        else:
            axes.add(part)
    return axes


def reduce_grads(grads, param_specs, *, data_axes: Tuple[str, ...],
                 model_axes: Tuple[str, ...],
                 partial_axes: Tuple[str, ...] = ()):
    """Apply the grad-reduction rule leaf-by-leaf.

    ``model_axes`` (tp/sp): the loss is computed redundantly on every
    member (post-psum activations are replicated), so by psum's transpose
    rule EVERY grad leaf arrives scaled by prod(model axis sizes); we
    divide that factor back out. Leaves replicated over a model axis
    additionally hold only their rank's partial sum and get psummed over
    the axes missing from their spec.

    ``partial_axes`` (pp): the loss is NOT redundant (it is masked to one
    stage), but grads of axis-replicated params (embedding on stage 0,
    head on the last stage) are rank-partial — psum, no redundancy
    division.

    Finally data axes take the DP mean — EXCEPT leaves sharded over a
    data axis (MoE expert weights over ``ep``, nn/moe.py): the all_to_all
    transpose already delivered their grads summed over every
    token-source rank, so they are divided by the axis size instead of
    pmeaned (a pmean would blend different experts' grads).
    """
    redundancy = 1
    for a in model_axes:
        redundancy *= lax.axis_size(a)

    def red(g, spec):
        present = _spec_axes(spec)
        psum_axes = tuple(a for a in (*model_axes, *partial_axes)
                          if a not in present)
        if psum_axes:
            g = lax.psum(g, psum_axes)
        if redundancy != 1:
            g = g / redundancy
        mean_axes = tuple(a for a in data_axes if a not in present)
        if mean_axes:
            g = lax.pmean(g, mean_axes)
        for a in data_axes:
            if a in present:
                g = g / lax.axis_size(a)
        return g

    return jax.tree.map(red, grads, param_specs)


def sharded_global_norm(grads, param_specs, *, model_axes: Tuple[str, ...]):
    """Global L2 norm of a tp/sp-sharded grad tree (identical on all
    ranks). Local sum-of-squares of sharded leaves are partial and get
    psummed over their sharding axes before the final sqrt."""

    def leaf_sumsq(g, spec):
        ss = jnp.sum(jnp.square(g.astype(jnp.float32)))
        shard_axes = tuple(a for a in _spec_axes(spec) if a in model_axes)
        if shard_axes:
            ss = lax.psum(ss, shard_axes)
        return ss

    parts = jax.tree.leaves(jax.tree.map(leaf_sumsq, grads, param_specs))
    return jnp.sqrt(jnp.sum(jnp.stack(parts)))


def clip_sharded_grads(grads, param_specs, max_norm: float,
                       *, model_axes: Tuple[str, ...]):
    norm = sharded_global_norm(grads, param_specs, model_axes=model_axes)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda g: g * scale, grads), norm


def opt_state_specs(optimizer: optax.GradientTransformation, params,
                    param_specs):
    """PartitionSpec tree for an optimizer state: param-shaped slots (mu,
    nu, trace...) inherit the param's spec, scalars are replicated.
    Uses optax.tree_map_params so it works for any optax chain."""
    state_shape = jax.eval_shape(optimizer.init, params)
    return optax.tree_map_params(
        optimizer,
        lambda _leaf, spec: spec,
        state_shape,
        param_specs,
        transform_non_params=lambda _leaf: P(),
    )


def shard_pytree(mesh: Mesh, tree, specs):
    """Place a host pytree onto the mesh according to a spec tree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
    )


def init_sharded_opt_state(optimizer, params, param_specs, mesh: Mesh):
    """Initialise optimizer state directly with the right sharding."""
    specs = opt_state_specs(optimizer, params, param_specs)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    state = jax.jit(optimizer.init, out_shardings=shardings)(params)
    return state, specs


def init_zero1_opt_state(optimizer, params, param_specs, mesh: Mesh,
                         *, axis: str = "dp"):
    """Initialise a dp-sharded (ZeRO-1) optimizer state (parallel/zero.py)."""
    from quintnet_tpu.parallel import zero

    init_local, _ = zero.make_zero1(optimizer, axis=axis)
    p_template = jax.eval_shape(lambda t: t, params)
    local_t = zero.local_template(p_template, param_specs, mesh)
    specs = zero.state_specs(optimizer, local_t, mesh, axis=axis)
    fn = cc.shard_map_fn(init_local, mesh, in_specs=(param_specs,),
                         out_specs=specs)
    return jax.jit(fn)(params), specs


def device_dropout_key(seed, present_axes):
    """Per-device dropout key: fold the device's (dp, ep, sp) coordinate
    into the step seed — independent masks per token shard. NEVER folds
    tp (tp ranks compute replicated activations whose masks must agree)
    nor pp (schedules fold stage index themselves, parallel/pp.py).

    The fold is canonical over the fixed axis list with 0 for axes the
    mesh doesn't have, so the derived key depends only on the device's
    logical data coordinate, not on which axes exist — single-device and
    tp-only runs get bit-identical masks (tests/test_dropout.py)."""
    key = jax.random.key(seed)
    for a in ("dp", "ep", "sp"):
        idx = lax.axis_index(a) if a in present_axes else 0
        key = jax.random.fold_in(key, idx)
    return key


def make_parallel_train_step(
    mesh: Mesh,
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    param_specs,
    *,
    batch_axes: Sequence[str] = ("dp",),
    model_axes: Sequence[str] = ("tp", "sp"),
    partial_axes: Sequence[str] = ("pp",),
    grad_accum_steps: int = 1,
    grad_clip_norm: Optional[float] = None,
    has_aux: bool = False,
    donate: bool = True,
    grad_fn: Optional[Callable] = None,
    zero1_axis: Optional[str] = None,
    zero_stage: int = 1,
    batch_specs=None,
    needs_rng: bool = False,
):
    """Build a jitted train step over an arbitrary (dp, tp, pp[, sp]) mesh.

    ``loss_fn(params, batch)`` sees LOCAL param shards and the LOCAL batch
    shard and may itself use collectives (tp psums inside the model,
    pipeline ppermutes for a pp loss fn built by parallel/pp.py).

    ``grad_fn(params, batch) -> (loss_or_(loss,aux), grads)``: schedules
    that compute grads without outer AD (1F1B) plug in here, replacing
    value_and_grad + accumulate.

    ``needs_rng``: the model uses training dropout — ``loss_fn``/
    ``grad_fn`` take a trailing ``key`` argument and the returned step
    takes a ``seed`` (int) whose per-device key folds in dp/ep/sp
    indices (:func:`device_dropout_key`).

    Returns step(params, opt_state, batch[, seed]) ->
    (params, opt_state, loss[, aux]).
    """
    data_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    maxes = tuple(a for a in model_axes if a in mesh.axis_names)
    paxes = tuple(a for a in partial_axes if a in mesh.axis_names)
    mesh_axes = tuple(mesh.axis_names)

    def local_step(params, opt_state, batch, seed):
        key = device_dropout_key(seed, mesh_axes) if needs_rng else None
        zero2 = zero1_axis is not None and zero_stage == 2
        if zero2 and grad_fn is None and grad_accum_steps > 1:
            # ZeRO-2 chunk accumulation: the full-size grad buffer never
            # exists across microbatches; clipping + the optimizer run
            # in chunk space (parallel/zero.py accumulate_grads_zero2)
            from quintnet_tpu.parallel import zero

            with jax.named_scope("grads"):
                out, g_chunk = zero.accumulate_grads_zero2(
                    loss_fn, params, batch, grad_accum_steps,
                    axis=zero1_axis, data_axes=data_axes,
                    model_axes=maxes, partial_axes=paxes,
                    param_specs=param_specs, has_aux=has_aux, key=key)
            if data_axes:
                with jax.named_scope("grad_reduce"):
                    out = jax.tree.map(
                        lambda x: lax.pmean(x, data_axes), out)
            _, _, update_from_chunk = zero.make_zero2(
                optimizer, param_specs, axis=zero1_axis,
                mesh_axes=mesh_axes, clip_norm=grad_clip_norm)
            with jax.named_scope("optimizer"):
                params, opt_state = update_from_chunk(g_chunk, opt_state,
                                                      params)
            return params, opt_state, out
        # the scopes below are metadata on the compiled program: a
        # device trace names each operation by the part of the step it
        # belongs to (forward and backward need none of their own —
        # jvp(...)/transpose(...) is already in an operation's name)
        with jax.named_scope("grads"):
            if grad_fn is not None:
                out, grads = (grad_fn(params, batch, key) if needs_rng
                              else grad_fn(params, batch))
            else:
                out, grads = accumulate_grads(loss_fn, params, batch,
                                              grad_accum_steps, has_aux,
                                              key=key)
        with jax.named_scope("grad_reduce"):
            grads = reduce_grads(
                grads, param_specs,
                # ZeRO-2: the zero-axis mean happens inside update_local
                # as a reduce-scatter straight into the rank's chunk
                data_axes=(tuple(a for a in data_axes if a != zero1_axis)
                           if zero2 else data_axes),
                model_axes=maxes, partial_axes=paxes)
            if data_axes:
                out = jax.tree.map(lambda x: lax.pmean(x, data_axes), out)
        if grad_clip_norm is not None and not zero2:
            # pp-sharded leaves are partial across pp too, and MoE expert
            # leaves are sharded over a data axis (ep): include both so
            # the global norm sums every shard exactly once. (ZeRO-2
            # clips inside update_local, in chunk space.)
            with jax.named_scope("grad_clip"):
                grads, _ = clip_sharded_grads(
                    grads, param_specs, grad_clip_norm,
                    model_axes=maxes + paxes + data_axes)
        with jax.named_scope("optimizer"):
            if zero2:
                from quintnet_tpu.parallel import zero

                _, update_local, _ = zero.make_zero2(
                    optimizer, param_specs, axis=zero1_axis,
                    mesh_axes=mesh_axes, clip_norm=grad_clip_norm)
                params, opt_state = update_local(grads, opt_state, params)
            elif zero1_axis is not None:
                from quintnet_tpu.parallel import zero

                _, update_local = zero.make_zero1(optimizer,
                                                  axis=zero1_axis)
                params, opt_state = update_local(grads, opt_state, params)
            else:
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
        return params, opt_state, out

    # opt state specs need a params template; derive lazily on first call
    # so the builder does not require materialised params.
    compiled = {}

    def jitted(params):
        if "fn" not in compiled:
            if zero1_axis is not None:
                from quintnet_tpu.parallel import zero

                p_template = jax.eval_shape(lambda t: t, params)
                local_t = zero.local_template(p_template, param_specs, mesh)
                o_specs = zero.state_specs(optimizer, local_t, mesh,
                                           axis=zero1_axis)
            else:
                o_specs = opt_state_specs(optimizer, params, param_specs)
            batch_spec = (batch_specs if batch_specs is not None
                          else P(data_axes if data_axes else None))
            smapped = cc.shard_map_fn(
                local_step,
                mesh,
                in_specs=(param_specs, o_specs, batch_spec, P()),
                out_specs=(param_specs, o_specs, P()),
            )
            compiled["fn"] = jax.jit(
                smapped, donate_argnums=(0, 1) if donate else ()
            )
        return compiled["fn"]

    def dispatch(params, opt_state, batch, seed):
        with jax.profiler.TraceAnnotation("qn.train.dispatch"):
            return jitted(params)(
                params, opt_state, batch,
                jnp.uint32(seed if seed is not None else 0))

    def step(params, opt_state, batch, seed=None):
        if "fn" in compiled:
            return dispatch(params, opt_state, batch, seed)
        # the first call traces, lowers and compiles or loads the
        # program: ``qn.setup.warmup/jit_local_step`` on the start-up
        # record (obs/spans.py), until the call returns
        with first_call(local_step.__name__):
            return dispatch(params, opt_state, batch, seed)

    def lower(params, opt_state, batch, seed=None):
        """The step program lowered for these arguments (or their
        shapes), not run: ``.compile().as_text()`` is what
        obs/scopes.scope_map reads."""
        return jitted(params).lower(
            params, opt_state, batch,
            jnp.uint32(seed if seed is not None else 0))

    step.lower = lower
    return step
