"""Model-family adapters for the serving engine.

One tiny record per family (GPT-2, Llama) giving the engine a uniform
(chunked-prefill, paged-decode, partition-specs) surface. Nothing here
forks model math: every paged program — prefill, decode, verify — scans
ONE paged block body (nn/transformer.block_verify_paged /
models/llama.llama_block_verify_paged, ending in
nn/attention.paged_attend) at its own width: a decode step is one token
a row, a prefill one row, and embedding/logits reuse the generate
modules' vocab-parallel-aware helpers — a fix in any of those fixes
serving too.

The pool in the programs: ``k_pool``/``v_pool`` are the WHOLE pool
``[L, num_blocks * block_size, F]`` (serve/kv_pool.py). They ride the
layer scan's CARRY (:func:`_scan_layers`) and every layer writes and
reads them in place at ``(layer, slot)``; the programs return the
carried buffers, which the engine's donation aliases to the arguments.
No pool-shaped array is ever a scan's xs or ys
(analysis.pool_scan_operands).

Prefill contract (chunked, prefix-cache aware): ``prefill_from(params,
k_pool, v_pool, ids [1, P], start, t0, table_row [M], block_size,
tp_axis) -> (logits [1, V] at position t0-1, k_pool, v_pool)`` — ids
hold the UNCACHED TAIL ``tokens[start:t0]`` right-padded to the
engine's static bucket width P; positions ``[0, start)`` are already
resident in the pool blocks the table references (a prefix-cache hit,
or nothing when ``start == 0`` — cache-off and cache-on run the same
program). The tail's KV is scattered through the table, attention runs
against the gathered whole row, and the returned logits are read at
the DYNAMIC index ``t0 - 1 - start``, so one compiled program per
bucket width serves every (start, t0) split.

Decode contract: ``decode(params, k_pool, v_pool, tok [S], pos [S],
tables [S, M], block_size, tp_axis) -> (logits [S, V], k_pool, v_pool)``
— per-row positions, paged pool views, static S.

Verify contract (speculative decoding, serve/spec.py): ``verify(params,
k_pool, v_pool, ids [S, P], starts [S], tail_lens [S], tables [S, M],
block_size, tp_axis) -> (logits [S, P, V], k_pool, v_pool)`` — the
decode step widened from 1 to P tokens per row. Row s's ids hold its
last sampled token + up to P-1 drafted continuations at absolute
positions ``starts[s] + arange(P)``; columns at or beyond
``tail_lens[s]`` are pad (their KV scatters to the null block, their
logits are garbage the engine never reads). Logits come back for ALL P
positions — ``logits[s, i]`` is the next-token distribution after row
s's first i+1 run tokens — so one forward scores a whole draft + the
bonus token. The attention math is the gathered-view decode math
exactly (nn/attention.mha_verify_paged), which is what makes
verify-committed tokens bit-equal to plain decoded ones.

Quantized KV (serve/kv_quant.py): every contract additionally takes
``kv_scales=None, policy=None`` — under a SCALED layout policy (int8,
fake_quant) ``kv_scales`` is the ``(k_scale, v_scale)`` pair of
``[L, num_blocks, H_kv]`` per-block-per-head scale arrays that ride
the layer scan's carry beside the pools, and the return tuple widens
symmetrically to ``(logits, k_pool, v_pool, k_scale, v_scale)``. The
block bodies dequantize inside the gathered view and quantize on
scatter; ``kv_scales=None`` (the passthrough policies) is
byte-identical to the pre-policy programs.

Every contract additionally takes ``attn_kernel="xla"`` and reads it
nowhere: there is one paged attention (nn/attention.paged_attend, its
form chosen from dtypes and shapes), and the keyword stays only because
the benchmark's drivers pass ``attn_kernel=engine.attn_kernel`` to
these functions (ROADMAP names the debt).

Multi-tenant LoRA (serve/adapters.py): every contract additionally
takes ``lora=None, lora_scale=None`` — a nested pytree of PACKED
per-slot adapter factors, one ``{"a": [L, S_or_1, in, r], "b": [L,
S_or_1, r, out]}`` node per targeted matmul (leading L rides the layer
scan exactly like the block params), plus the per-slot ``alpha/rank``
scales. Each targeted matmul adds its row's low-rank delta
(nn/layers.lora_delta); zero rows ARE the base model. Decode/verify
take the full [S]-slot pack; prefill (one request at a time) takes the
admitted slot's [1]-row slice. ``lora=None`` is byte-identical to the
pre-adapter programs.

Recurrent families (``Family.state`` set: state-space layers among
attention ones, :func:`granite_hybrid_family`): the layers are of TWO
kinds in a repeated pattern (``Family.layer_pattern``), only the
attention layers hold paged KV (``n_layers`` counts those), and every
other layer keeps a fixed-size state per engine slot
(serve/kv_pool.StateShapes). Every contract additionally takes
``state=(ssm, conv)`` — the per-slot buffers ``[L_r, slots + 1, ...]``,
row = slot, the conv tail's rows flat — and returns ``(logits, k_pool, v_pool, ssm, conv)``;
``prefill_from`` takes ``slot``, the row its request owns. A prefill
that starts at position 0 starts from a ZERO state whatever the row
held (admission); one that starts later continues from the state its
predecessor left (chunked prefill). Pad columns of a bucket, rows with
``tail_len`` 0 and decode rows whose table is all null blocks leave
their state exactly as it was.

Latent families (``Family.latent`` set: multi-head latent attention,
:func:`pangu_moe_family`): a token caches ONE row ``[c | k_rope]``
(serve/kv_pool.py), so there is one pool buffer. The contracts keep
their positions — ``v_pool`` is passed as None — and return ``(logits,
k_pool, moe_stats)``. ``prefill_from`` runs the MATERIALIZED form of
the attention, ``decode`` and ``verify`` the ABSORBED one
(nn/attention.py, "The LATENT paged cache").

Latent AND recurrent (``Family.latent`` and ``Family.state`` both set:
linear-attention layers with one latent layer closing every group,
:func:`ling_hybrid_family`): the two above at once. ``v_pool`` is None,
``state=(ssm, conv)`` rides beside the one latent pool, and the
contracts return ``(logits, k_pool, ssm, conv, moe_stats)``.

Window families (``Family.window`` set: sliding-window layers among
global ones, :func:`laguna_family`, :func:`smallthinker_family`):
``n_layers`` counts the GLOBAL
layers, which page every position into the pool; each sliding layer
keeps a ring a slot (serve/kv_pool.WindowShapes; nn/attention.py, "The
WINDOW store"). Every contract additionally takes ``window=(wk, wv)``
and returns ``(logits, k_pool, v_pool, wk, wv, moe_stats)``;
``prefill_from`` takes ``slot``, the ring its request owns, as a
recurrent family's does. The layers differ in SHAPE, not in kind alone
(``W_q`` is wider on a sliding layer of Laguna's, a rotary table is
there or not on SmallThinker's), so the programs walk the model's runs
of consecutive layers of one kind, a scan each (:func:`_walk_runs`),
and the two families share their contracts (:func:`_window_contracts`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from quintnet_tpu.nn.attention import _pool_tuple
from quintnet_tpu.serve.kv_pool import (NULL_BLOCK, StateShapes,
                                        WindowShapes)


@dataclass(frozen=True)
class Family:
    name: str
    cfg: Any
    n_layers: int            # layers that hold paged KV (all, if uniform)
    n_kv_heads: int          # GLOBAL kv heads (pool head dim)
    head_dim: int
    max_positions: int
    prefill_from: Callable   # (params, kp, vp, ids, start, t0, row, bs,
    #                           tp_axis, ep_axis, lora, lora_scale)
    #                           -> (logits, kp, vp[, moe_stats])
    decode: Callable         # (params, kp, vp, tok, pos, tables, bs,
    #                           tp_axis, ep_axis, lora, lora_scale)
    verify: Callable         # (params, kp, vp, ids [S, P], starts [S],
    #                           tail_lens [S], tables, bs, tp_axis,
    #                           ep_axis, lora, lora_scale)
    #                           -> (logits [S, P, V], kp, vp[, moe_stats])
    partition_specs: Callable  # (tp_axis, ep_axis=None) -> param specs
    # MoE families (cfg.moe_args set) widen every contract's return by
    # one trailing routing-stats dict — per-expert routed counts,
    # capacity drops, assignments, router entropy, already reduced over
    # layers (_reduce_moe_stats) — and take ``ep_axis``: experts
    # sharded over the axis with one all_to_all each way per MoE layer
    # (nn/moe.py); None runs the dense-replicated MoE math.
    # sequence-parallel prefill (long-context serving, serve/longctx.py):
    # same contract as prefill_from except ids is THIS SP RANK's slice
    # [1, P/sp] of the bucket (the engine's shard_map splits dim 1) and
    # the body runs ring attention over sp_axis
    # (nn/attention.ring_paged_prefill). None = family has no sp path.
    prefill_from_sp: Optional[Callable] = None
    kv_dtype: Any = jnp.float32
    # default LoRA target names for this family's blocks (engine's
    # lora_targets default — models/lora.py ladder names)
    lora_targets: Tuple[str, ...] = ()
    # paths (relative to one block node) of the linear nodes a weight
    # layout policy packs (serve/weight_quant.py): the decode-bandwidth
    # matmuls. Embeddings, head and LNs stay full-precision, as do the
    # capacity router's raw expert leaves (the tiny MoE families); the
    # dropless router's experts are linear nodes ([held, in, out]) and
    # ARE packed (pangu_moe_family).
    weight_targets: Tuple[Tuple[str, ...], ...] = ()
    # host-side layout hook: (path, b_factor [L, r, out], tp) -> the
    # factor permuted into the layout the SERVING weights use under tp.
    # GPT-2's fused qkv stores tp-BLOCKED columns (gpt2_to_tp_layout);
    # an adapter's b trained against the standard [q|k|v] layout must
    # be re-blocked the same way before packing, or its delta would
    # land on the wrong columns. None = identity (llama: separate
    # q/k/v, column order preserved per rank).
    lora_layout: Optional[Callable] = None
    # every layer's kind in model order, where the layers differ (the
    # serving scan then runs over periods of the pattern); None = one
    # uniform layer
    layer_pattern: Optional[Tuple[str, ...]] = None
    # per-slot recurrent state beside the paged pool (module docstring);
    # None = sequences are KV only
    state: Optional[StateShapes] = None
    # latent families (module docstring): the features of the ONE row a
    # token caches; ``n_kv_heads`` is then 1 and ``head_dim`` this
    # number, and the programs take and return no ``v_pool``
    latent: Optional[int] = None
    # window families (module docstring): the sliding layers' rings
    # beside the pool; None = every layer pages all of a sequence
    window: Optional[WindowShapes] = None


# --------------------------------------------------------------------
# GPT-2
# --------------------------------------------------------------------

def _scan_layers(step, h, pools, blocks, lora, moe: bool):
    """THE layer scan of every uniform family's serving program, under
    the scope ``blocks``. The pool buffers ride the CARRY, whole —
    ``step(blk, layer, lora_l, h, pools) -> (h, *pools[, moe_stats])``
    writes and reads them in place at ``(layer, slot)`` — so the scan
    slices nothing of the pool in and stacks nothing of it out. The xs
    are the block weights, the layer index and the packed lora tree
    (every leaf leading L; None when no adapters ride); the ys the MoE
    routing stats only. Returns ``(h, *pools[, reduced stats])``."""
    n = len(pools)
    depth = jax.tree.leaves(blocks)[0].shape[0]

    def body(carry, xs):
        blk, layer, lr = xs
        out = step(blk, layer, lr, carry[0], carry[1:])
        return out[:1 + n], (out[1 + n] if moe else None)

    with jax.named_scope("blocks"):
        out, st = lax.scan(body, (h, *pools),
                           (blocks, jnp.arange(depth), lora))
    if moe:
        return (*out, _reduce_moe_stats(st))
    return out


def _paged_contracts(run, logits):
    """``prefill_from`` / ``decode`` / ``verify`` of a uniform family
    (module docstring) from its ONE layer run at three widths:
    ``run(params, ids [S, P], pools, positions [S, P], lens [S], tables
    [S, M], block_size, **kw) -> (h [S, P, D], *pools[, moe_stats])``
    and ``logits(params, h, tp_axis)``. A prefill is one row of the
    bucket's width, a decode step one token a row."""
    def call(params, k_pool, v_pool, kv_scales, ids, positions, lens,
             tables, block_size, tp_axis, **kw):
        return run(params, ids, _pool_tuple(k_pool, v_pool, kv_scales),
                   positions, lens, tables, block_size, tp_axis=tp_axis,
                   **kw)

    def prefill_from(params, k_pool, v_pool, ids, start, t0, table_row,
                     block_size, tp_axis=None, ep_axis=None, lora=None,
                     lora_scale=None, kv_scales=None, policy=None,
                     attn_kernel="xla"):
        positions = (start + jnp.arange(ids.shape[1], dtype=jnp.int32))[None]
        h, *pools = call(
            params, k_pool, v_pool, kv_scales, ids, positions,
            jnp.reshape(t0 - start, (1,)), table_row[None], block_size,
            tp_axis, ep_axis=ep_axis, lora=lora, lora_scale=lora_scale,
            policy=policy)
        h_last = lax.dynamic_slice_in_dim(h, t0 - 1 - start, 1, axis=1)
        return (logits(params, h_last, tp_axis)[:, 0, :], *pools)

    def decode(params, k_pool, v_pool, tok, pos, tables, block_size,
               tp_axis=None, ep_axis=None, lora=None, lora_scale=None,
               kv_scales=None, policy=None, attn_kernel="xla"):
        h, *pools = call(
            params, k_pool, v_pool, kv_scales, tok[:, None], pos[:, None],
            jnp.ones(pos.shape, jnp.int32), tables, block_size, tp_axis,
            ep_axis=ep_axis, lora=lora, lora_scale=lora_scale,
            policy=policy)
        return (logits(params, h, tp_axis)[:, 0, :], *pools)

    def verify(params, k_pool, v_pool, ids, starts, tail_lens, tables,
               block_size, tp_axis=None, ep_axis=None, lora=None,
               lora_scale=None, kv_scales=None, policy=None,
               attn_kernel="xla"):
        positions = (starts[:, None] + jnp.arange(
            ids.shape[1], dtype=jnp.int32)[None, :])           # [S, P]
        h, *pools = call(
            params, k_pool, v_pool, kv_scales, ids, positions, tail_lens,
            tables, block_size, tp_axis, ep_axis=ep_axis, lora=lora,
            lora_scale=lora_scale, policy=policy)
        return (logits(params, h, tp_axis), *pools)

    return prefill_from, decode, verify


def _reduce_moe_stats(st):
    """Layer-stacked routing stats (each leaf leading [L], the scan's
    ys) -> per-program totals: counts summed over layers, entropy
    meaned. Every value is replicated across ep/tp ranks (routing is
    computed on the replicated token batch), so the engine's shard_map
    emits them with a replicated out-spec."""
    return {
        "expert_tokens": jnp.sum(st["expert_tokens"], axis=0),
        "dropped": jnp.sum(st["dropped"]),
        "assigned": jnp.sum(st["assigned"]),
        "entropy": jnp.mean(st["entropy"]),
        # the dropless router's own counts (nn/moe.py), where it ran
        **{k: jnp.sum(st[k]) for k in ("held_rows", "touched", "elsewhere",
                                       "tile_visits", "no_held_group")
           if k in st},
    }


def gpt2_family(cfg) -> Family:
    from quintnet_tpu.models.gpt2 import gpt2_partition_specs
    from quintnet_tpu.models.gpt2_generate import (_embed_tok, _local_heads,
                                                   _logits)
    from quintnet_tpu.models.lora import DEFAULT_TARGETS
    from quintnet_tpu.nn.attention import sp_last_hidden
    from quintnet_tpu.nn.layers import gelu
    from quintnet_tpu.nn.transformer import (block_prefill_paged_sp,
                                             block_verify_paged)

    moe = cfg.moe_args is not None

    def embed(params, ids, positions, tp_axis):
        emb = params["embedding"]
        # pad rows may sit past n_positions; clip their (ignored) wpe read
        safe_pos = jnp.clip(positions, 0, emb["wpe"].shape[0] - 1)
        with jax.named_scope("embed"):
            return (_embed_tok(emb, ids, cfg, tp_axis)
                    + jnp.take(emb["wpe"], safe_pos, axis=0))

    def run(params, ids, pools, positions, lens, tables, block_size, *,
            tp_axis, ep_axis, lora, lora_scale, policy):
        heads = _local_heads(cfg, tp_axis)

        def step(blk, layer, lr, x, pools):
            return block_verify_paged(
                blk, x, *pools[:2], positions, lens, num_heads=heads,
                layer=layer, act=gelu, moe_args=cfg.moe_args,
                ep_axis=ep_axis, tp_axis=tp_axis, block_tables=tables,
                block_size=block_size, lora=lr, lora_scale=lora_scale,
                kv_scales=pools[2:] or None, policy=policy)

        return _scan_layers(step, embed(params, ids, positions, tp_axis),
                            pools, params["blocks"], lora, moe)

    prefill_from, decode, verify = _paged_contracts(
        run, lambda params, h, tp_axis: _logits(params, h, cfg, tp_axis))

    def prefill_from_sp(params, k_pool, v_pool, ids, start, t0,
                        table_row, block_size, *, sp_axis: str,
                        tp_axis=None, kv_scales=None, policy=None):
        # ids: [1, P/sp] — THIS sp rank's slice of the padded chunk
        # (the engine shard_maps the bucket over sp); positions are the
        # rank's absolute offsets, so embedding/rope/masking all land
        # exactly where the single-device program puts them
        Pl = ids.shape[1]
        positions = (start + lax.axis_index(sp_axis) * Pl
                     + jnp.arange(Pl, dtype=jnp.int32))[None]
        heads = _local_heads(cfg, tp_axis)

        def step(blk, layer, _lr, x, pools):
            return block_prefill_paged_sp(
                blk, x, *pools[:2], start, t0, num_heads=heads,
                sp_axis=sp_axis, layer=layer, act=gelu,
                moe_args=cfg.moe_args, tp_axis=tp_axis,
                block_tables=table_row, block_size=block_size,
                kv_scales=pools[2:] or None, policy=policy)

        h, *pools = _scan_layers(
            step, embed(params, ids, positions, tp_axis),
            _pool_tuple(k_pool, v_pool, kv_scales), params["blocks"],
            None, False)
        h_last = sp_last_hidden(h, start, t0, sp_axis=sp_axis)
        return (_logits(params, h_last, cfg, tp_axis)[:, 0, :], *pools)

    def lora_layout(path, b, tp):
        # fused qkv columns are tp-BLOCKED in the serving layout
        # (parallel/tp.py gpt2_to_tp_layout); re-block the adapter's b
        # the same way so its delta lands on the matching columns
        if path[-1] == "qkv" and tp > 1:
            from quintnet_tpu.parallel.tp import qkv_blocked_from_standard

            return qkv_blocked_from_standard(b, cfg.n_head, tp)
        return b

    return Family(
        name="gpt2", cfg=cfg, n_layers=cfg.n_layer, n_kv_heads=cfg.n_head,
        head_dim=cfg.n_embd // cfg.n_head, max_positions=cfg.n_positions,
        prefill_from=prefill_from, decode=decode, verify=verify,
        prefill_from_sp=prefill_from_sp,
        partition_specs=lambda tp_axis, ep_axis=None: gpt2_partition_specs(
            cfg, tp_axis=tp_axis, ep_axis=ep_axis),
        lora_targets=DEFAULT_TARGETS, lora_layout=lora_layout,
        weight_targets=(("attn", "qkv"), ("attn", "proj"),
                        ("mlp", "fc"), ("mlp", "proj")),
    )


# --------------------------------------------------------------------
# Llama (GQA: the pool holds UNrepeated kv heads)
# --------------------------------------------------------------------

def llama_family(cfg) -> Family:
    from quintnet_tpu.models.llama import (llama_block_prefill_paged_sp,
                                           llama_block_verify_paged,
                                           llama_partition_specs,
                                           llama_rope_tables)
    from quintnet_tpu.models.llama_generate import _embed, _full_logits
    from quintnet_tpu.models.lora import LLAMA_TARGETS
    from quintnet_tpu.nn.attention import sp_last_hidden

    moe = cfg.moe_args is not None

    def run(params, ids, pools, positions, lens, tables, block_size, *,
            tp_axis, ep_axis, lora, lora_scale, policy):
        cos, sin = llama_rope_tables(positions, cfg)          # [S, P, hd]
        cos, sin = cos[:, None], sin[:, None]                 # [S,1,P,hd]

        def step(blk, layer, lr, x, pools):
            x, pools = llama_block_verify_paged(
                blk, x, *pools[:2], positions, lens, cfg, cos, sin,
                layer=layer, tp_axis=tp_axis, ep_axis=ep_axis,
                block_tables=tables, block_size=block_size, lora=lr,
                lora_scale=lora_scale, kv_scales=pools[2:] or None,
                policy=policy)
            return (x, *pools)

        return _scan_layers(step, _embed(params, ids, cfg, tp_axis), pools,
                            params["blocks"], lora, moe)

    prefill_from, decode, verify = _paged_contracts(
        run, lambda params, h, tp_axis: _full_logits(params, h, cfg,
                                                     tp_axis))

    def prefill_from_sp(params, k_pool, v_pool, ids, start, t0,
                        table_row, block_size, *, sp_axis: str,
                        tp_axis=None, kv_scales=None, policy=None):
        # ids: [1, P/sp] — this sp rank's chunk slice; rope tables come
        # from the rank's LOCAL absolute positions
        Pl = ids.shape[1]
        positions = (start + lax.axis_index(sp_axis) * Pl
                     + jnp.arange(Pl, dtype=jnp.int32))
        cos, sin = llama_rope_tables(positions, cfg)      # [Pl, hd]

        def step(blk, layer, _lr, x, pools):
            x, pools = llama_block_prefill_paged_sp(
                blk, x, *pools[:2], start, t0, cfg, cos, sin,
                sp_axis=sp_axis, layer=layer, tp_axis=tp_axis,
                block_tables=table_row, block_size=block_size,
                kv_scales=pools[2:] or None, policy=policy)
            return (x, *pools)

        h, *pools = _scan_layers(
            step, _embed(params, ids, cfg, tp_axis),
            _pool_tuple(k_pool, v_pool, kv_scales), params["blocks"],
            None, False)
        h_last = sp_last_hidden(h, start, t0, sp_axis=sp_axis)
        return (_full_logits(params, h_last, cfg, tp_axis)[:, 0, :],
                *pools)

    return Family(
        name="llama", cfg=cfg, n_layers=cfg.n_layers,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        max_positions=cfg.n_positions,
        prefill_from=prefill_from, decode=decode, verify=verify,
        prefill_from_sp=prefill_from_sp,
        partition_specs=lambda tp_axis, ep_axis=None: llama_partition_specs(
            cfg, tp_axis=tp_axis, ep_axis=ep_axis),
        lora_targets=LLAMA_TARGETS,
        weight_targets=(("attn", "q"), ("attn", "k"), ("attn", "v"),
                        ("attn", "o"), ("mlp", "gate"), ("mlp", "up"),
                        ("mlp", "down")),
    )


# --------------------------------------------------------------------
# Granite 4.0-H (Mamba-2 layers with a GQA attention layer among every
# few): a layer pattern in the scan, recurrent state beside the pool
# --------------------------------------------------------------------

def granite_hybrid_family(cfg) -> Family:
    from quintnet_tpu.models.granite_hybrid import (
        WEIGHT_TARGETS, attn_block_chunk, granite_embed,
        granite_hybrid_partition_specs, granite_logits, mamba_block_chunk,
        mamba_block_step)

    periods, before, after = cfg.pattern
    per = before + after
    dims = cfg.mamba

    def only_plain(tp_axis, ep_axis, lora, kv_scales, state):
        if (tp_axis is not None or ep_axis is not None or lora is not None
                or kv_scales is not None or state is None):
            raise NotImplementedError(
                "the granite hybrid programs run on one device, without "
                "adapters, on an unscaled pool and with their state "
                "buffers (ServeEngine refuses the rest at construction)")

    def run_layers(params, k_pool, v_pool, state, h, row0, rows,
                   mamba_fn, attn_fn):
        """The scan over the layer pattern: one step a PERIOD — a short
        scan of Mamba layers, the attention layer, a second short scan
        of Mamba layers. Every per-sequence buffer — the attention
        layers' pools and the two state buffers — rides every loop's
        CARRY, whole: an attention layer writes and reads the pool at
        ``(period, slot)``, a Mamba layer reads rows ``[row0, row0 +
        rows)`` of its own slice and writes them back in place —
        sliced in as xs and stacked as ys they would be a second copy
        of the buffer. The Mamba layers' weights are indexed by layer
        inside the inner loop, as a scan indexes its xs."""
        mblocks = params["blocks"]["mamba"]

        def mamba_run(carry, first, count):
            def body(c, j):
                x, ssm, conv, *kv = c
                layer = first + j
                blk = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(
                        a, layer, keepdims=False), mblocks)
                s = lax.dynamic_slice(
                    ssm, (layer, row0, 0, 0, 0),
                    (1, rows, *ssm.shape[2:]))[0]
                t = lax.dynamic_slice(
                    conv, (layer, row0, 0), (1, rows, conv.shape[2]))[0]
                x, s, t = mamba_fn(
                    blk, x, s, t.reshape(rows, dims.d_conv - 1, dims.d_xbc))
                with jax.named_scope("mamba"), \
                        jax.named_scope("state_update"):
                    ssm = lax.dynamic_update_slice(
                        ssm, s[None], (layer, row0, 0, 0, 0))
                    conv = lax.dynamic_update_slice(
                        conv, t.reshape(1, rows, -1), (layer, row0, 0))
                return (x, ssm, conv, *kv), None

            if count == 0:
                return carry
            return lax.scan(body, carry, jnp.arange(count))[0]

        def period(carry, xs):
            i, ablk = xs
            x, ssm, conv, kp, vp = mamba_run(carry, i * per, before)
            x, kp, vp = attn_fn(ablk, x, kp, vp, i)
            return mamba_run((x, ssm, conv, kp, vp), i * per + before,
                             after), None

        with jax.named_scope("blocks"):
            (h, ssm, conv, k_pool, v_pool), _ = lax.scan(
                period, (h, *state, k_pool, v_pool),
                (jnp.arange(periods), params["blocks"]["attn"]))
        return h, k_pool, v_pool, ssm, conv

    def run_chunk(params, k_pool, v_pool, state, ids, positions, lens,
                  tables, block_size, policy, row0, fresh):
        """A run of tokens a row (prefill, chunked prefill, verify)."""
        def mamba_fn(blk, x, s, t):
            s = jnp.where(fresh, jnp.zeros_like(s), s)
            t = jnp.where(fresh, jnp.zeros_like(t), t)
            return mamba_block_chunk(blk, x, s, t, lens, cfg)

        def attn_fn(blk, x, kp, vp, layer):
            return attn_block_chunk(blk, x, kp, vp, layer, positions,
                                    lens, tables, block_size, cfg, policy)

        return run_layers(params, k_pool, v_pool, state,
                          granite_embed(params, ids, cfg), row0,
                          ids.shape[0], mamba_fn, attn_fn)

    def prefill_from(params, k_pool, v_pool, ids, start, t0, table_row,
                     block_size, tp_axis=None, ep_axis=None, lora=None,
                     lora_scale=None, kv_scales=None, policy=None,
                     attn_kernel="xla", state=None, slot=None):
        only_plain(tp_axis, ep_axis, lora, kv_scales, state)
        P = ids.shape[1]
        positions = (start + jnp.arange(P, dtype=jnp.int32))[None]
        h, *bufs = run_chunk(
            params, k_pool, v_pool, state, ids, positions,
            jnp.reshape(t0 - start, (1,)), table_row[None], block_size,
            policy, slot, start == 0)
        h_last = lax.dynamic_slice_in_dim(h, t0 - 1 - start, 1, axis=1)
        return (granite_logits(params, h_last, cfg)[:, 0, :], *bufs)

    def verify(params, k_pool, v_pool, ids, starts, tail_lens, tables,
               block_size, tp_axis=None, ep_axis=None, lora=None,
               lora_scale=None, kv_scales=None, policy=None,
               attn_kernel="xla", state=None):
        # the chunk program for P tokens a row, every row from its
        # CURRENT state. Not a speculative verify: nothing rolls a
        # state back, and the engine refuses ``spec`` for this family
        only_plain(tp_axis, ep_axis, lora, kv_scales, state)
        P = ids.shape[1]
        positions = (starts[:, None]
                     + jnp.arange(P, dtype=jnp.int32)[None, :])
        h, *bufs = run_chunk(
            params, k_pool, v_pool, state, ids, positions, tail_lens,
            tables, block_size, policy, 0, False)
        return (granite_logits(params, h, cfg), *bufs)

    def decode(params, k_pool, v_pool, tok, pos, tables, block_size,
               tp_axis=None, ep_axis=None, lora=None, lora_scale=None,
               kv_scales=None, policy=None, attn_kernel="xla",
               state=None):
        only_plain(tp_axis, ep_axis, lora, kv_scales, state)
        # a row whose table is all null blocks is not decoding (an
        # empty slot, or one in the middle of a chunked prefill): its
        # state stays what it was
        live = tables[:, 0] != NULL_BLOCK

        def mamba_fn(blk, x, s, t):
            x, s2, t2 = mamba_block_step(blk, x, s, t, cfg)
            return (x, jnp.where(live[:, None, None, None], s2, s),
                    jnp.where(live[:, None, None], t2, t))

        def attn_fn(blk, x, kp, vp, layer):
            return attn_block_chunk(
                blk, x, kp, vp, layer, pos[:, None],
                jnp.ones(pos.shape, jnp.int32), tables, block_size, cfg,
                policy)

        h, *bufs = run_layers(
            params, k_pool, v_pool, state,
            granite_embed(params, tok[:, None], cfg), 0, tok.shape[0],
            mamba_fn, attn_fn)
        return (granite_logits(params, h, cfg)[:, 0, :], *bufs)

    return Family(
        name="granite_hybrid", cfg=cfg, n_layers=periods,
        n_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        max_positions=cfg.max_position_embeddings,
        prefill_from=prefill_from, decode=decode, verify=verify,
        partition_specs=granite_hybrid_partition_specs,
        weight_targets=WEIGHT_TARGETS,
        layer_pattern=cfg.layer_types,
        state=StateShapes(
            n_layers=cfg.n_mamba_layers,
            ssm=(dims.n_heads, dims.d_head, dims.d_state),
            conv=((dims.d_conv - 1) * dims.d_xbc,)),
    )


# --------------------------------------------------------------------
# openPangu-Ultra-MoE (latent attention, a leading dense stack before
# the MoE stack, sandwich norms): one latent row a token in the pool
# --------------------------------------------------------------------

def pangu_moe_family(cfg) -> Family:
    from quintnet_tpu.models.pangu_moe import (
        ABSORBED, MATERIALIZED, WEIGHT_TARGETS, pangu_block, pangu_embed,
        pangu_logits, pangu_moe_partition_specs)
    from quintnet_tpu.nn.attention import rope_cos_sin

    def only_plain(v_pool, tp_axis, ep_axis, lora, kv_scales):
        if (v_pool is not None or tp_axis is not None
                or ep_axis is not None or lora is not None
                or kv_scales is not None):
            raise NotImplementedError(
                "the pangu_ultra_moe programs run on one device, on the "
                "one unscaled latent pool (v_pool=None), without "
                "adapters (ServeEngine refuses the rest at "
                "construction)")

    def run(params, ids, pool, positions, lens, tables, block_size, form):
        """Both stacks in turn, each a uniform :func:`_scan_layers`:
        the leading dense layers, then the MoE layers at the pool's
        layers after them. Returns (h, pool, moe_stats)."""
        cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim,
                                theta=cfg.rope_theta)       # [S, P, rope]

        def step(first, experts=None):
            def one(blk, layer, _lr, x, pools):
                return pangu_block(
                    blk, x, pools[0], first + layer, positions, lens,
                    tables, block_size, cfg, cos, sin, form=form,
                    experts=experts,
                    expert_layer=None if experts is None else layer)
            return one

        h, pool = _scan_layers(step(0), pangu_embed(params, ids), (pool,),
                               params["blocks"]["dense"], None, False)
        # the routed experts stay out of the scan's xs, whole: the
        # grouped matmul picks the layer's by group (nn/moe.py)
        moe = params["blocks"]["moe"]
        rest = {**moe, "moe": {k: v for k, v in moe["moe"].items()
                               if k != "experts"}}
        return _scan_layers(
            step(cfg.n_dense_layers, moe["moe"]["experts"]), h, (pool,),
            rest, None, True)

    def prefill_from(params, k_pool, v_pool, ids, start, t0, table_row,
                     block_size, tp_axis=None, ep_axis=None, lora=None,
                     lora_scale=None, kv_scales=None, policy=None,
                     attn_kernel="xla"):
        only_plain(v_pool, tp_axis, ep_axis, lora, kv_scales)
        positions = (start + jnp.arange(ids.shape[1], dtype=jnp.int32))[None]
        h, pool, stats = run(
            params, ids, k_pool, positions, jnp.reshape(t0 - start, (1,)),
            table_row[None], block_size, MATERIALIZED)
        h_last = lax.dynamic_slice_in_dim(h, t0 - 1 - start, 1, axis=1)
        return pangu_logits(params, h_last, cfg)[:, 0, :], pool, stats

    def decode(params, k_pool, v_pool, tok, pos, tables, block_size,
               tp_axis=None, ep_axis=None, lora=None, lora_scale=None,
               kv_scales=None, policy=None, attn_kernel="xla"):
        only_plain(v_pool, tp_axis, ep_axis, lora, kv_scales)
        # a row whose table is all null blocks is not decoding (an empty
        # slot, or one in the middle of a chunked prefill): its token is
        # padding, which the router sends nowhere
        live = (tables[:, 0] != NULL_BLOCK).astype(jnp.int32)
        h, pool, stats = run(params, tok[:, None], k_pool, pos[:, None],
                             live, tables, block_size, ABSORBED)
        return pangu_logits(params, h, cfg)[:, 0, :], pool, stats

    def verify(params, k_pool, v_pool, ids, starts, tail_lens, tables,
               block_size, tp_axis=None, ep_axis=None, lora=None,
               lora_scale=None, kv_scales=None, policy=None,
               attn_kernel="xla"):
        only_plain(v_pool, tp_axis, ep_axis, lora, kv_scales)
        positions = (starts[:, None]
                     + jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :])
        h, pool, stats = run(params, ids, k_pool, positions, tail_lens,
                             tables, block_size, ABSORBED)
        return pangu_logits(params, h, cfg), pool, stats

    return Family(
        name="pangu_ultra_moe", cfg=cfg, n_layers=cfg.num_hidden_layers,
        n_kv_heads=1, head_dim=cfg.latent_width,
        max_positions=cfg.max_position_embeddings,
        prefill_from=prefill_from, decode=decode, verify=verify,
        partition_specs=pangu_moe_partition_specs,
        weight_targets=WEIGHT_TARGETS, latent=cfg.latent_width,
    )


def _walk_runs(runs, blocks, h, caches, layer):
    """The layers of a model whose layers differ in SHAPE by kind, in
    model order: a :func:`_scan_layers` a RUN (consecutive layers of one
    kind, whose weights stack: models/laguna.Run), all the cache buffers
    riding every scan's carry. ``layer(run, blk, i, x, caches) -> (x,
    caches[, moe_stats])`` is one layer, the ``i``-th of its run, with
    ``blk`` its weights out of ``blocks[run.kind]``; a run whose
    ``expert_first`` is set returns stats. Returns (h, *caches,
    moe_stats): the runs' stats are already reduced over their layers,
    so counts add and the entropy is a mean over the runs weighted by
    their layers. The window families' walker (:func:`laguna_family`,
    :func:`smallthinker_family`)."""
    stats = []
    for r in runs:
        def step(blk, i, _lr, x, caches, r=r):
            x, caches, *st = layer(r, blk, i, x, caches)
            return (x, *caches, *st)

        stack = jax.tree.map(lambda a, r=r: a[r.first:r.first + r.count],
                             blocks[r.kind])
        sparse = r.expert_first is not None
        h, *caches = _scan_layers(step, h, tuple(caches), stack, None, sparse)
        if sparse:
            stats.append(caches.pop())
    n = [r.count for r in runs if r.expert_first is not None]
    total = {k: sum(st[k] for st in stats) for k in stats[0]
             if k != "entropy"}
    total["entropy"] = sum(
        st["entropy"] * c for st, c in zip(stats, n)) / sum(n)
    return (h, *caches, total)


# --------------------------------------------------------------------
# Laguna (sliding-window layers among global ones, more query heads on
# the former, a rotary setting a kind, a per-head gate, every expert
# held): two attention SHAPES in one program, a ring a slot beside the
# pool
# --------------------------------------------------------------------

def _window_contracts(name: str, block_size: int, run, logits):
    """``prefill_from`` / ``decode`` / ``verify`` of a window family
    (module docstring) from its walk of the layers, ``run(params,
    k_pool, v_pool, window, ids, positions, lens, tables, row0, chunk)
    -> (h, k_pool, v_pool, wk, wv, moe_stats)``, and ``logits(params,
    h)``. ``block_size``: the engine's (the rings are ``window +
    block_size`` rows)."""
    def only_plain(tp_axis, ep_axis, lora, kv_scales, window, bs):
        if (tp_axis is not None or ep_axis is not None or lora is not None
                or kv_scales is not None or window is None
                or bs != block_size):
            raise NotImplementedError(
                f"the {name} programs run on one device, without "
                f"adapters, on an unscaled pool of block_size "
                f"{block_size} and with their window buffers "
                f"(ServeEngine refuses the rest at construction)")

    def prefill_from(params, k_pool, v_pool, ids, start, t0, table_row,
                     block_size, tp_axis=None, ep_axis=None, lora=None,
                     lora_scale=None, kv_scales=None, policy=None,
                     attn_kernel="xla", window=None, slot=None):
        only_plain(tp_axis, ep_axis, lora, kv_scales, window, block_size)
        positions = (start + jnp.arange(ids.shape[1], dtype=jnp.int32))[None]
        h, *bufs = run(params, k_pool, v_pool, window, ids, positions,
                       jnp.reshape(t0 - start, (1,)), table_row[None],
                       slot, True)
        h_last = lax.dynamic_slice_in_dim(h, t0 - 1 - start, 1, axis=1)
        return (logits(params, h_last)[:, 0, :], *bufs)

    def decode(params, k_pool, v_pool, tok, pos, tables, block_size,
               tp_axis=None, ep_axis=None, lora=None, lora_scale=None,
               kv_scales=None, policy=None, attn_kernel="xla",
               window=None):
        only_plain(tp_axis, ep_axis, lora, kv_scales, window, block_size)
        # a row whose table is all null blocks is not decoding (an empty
        # slot, or one in the middle of a chunked prefill): its token is
        # padding — the router sends it nowhere, and its ring (a
        # chunked prefill's, half built) is not written
        live = (tables[:, 0] != NULL_BLOCK).astype(jnp.int32)
        h, *bufs = run(params, k_pool, v_pool, window, tok[:, None],
                       pos[:, None], live, tables, 0, False)
        return (logits(params, h)[:, 0, :], *bufs)

    def verify(params, k_pool, v_pool, ids, starts, tail_lens, tables,
               block_size, tp_axis=None, ep_axis=None, lora=None,
               lora_scale=None, kv_scales=None, policy=None,
               attn_kernel="xla", window=None):
        # the decode program at P tokens a row (P <= block_size + 1:
        # what a ring's spare rows allow, nn/attention.window_attend).
        # Not yet a speculative verify: a rejected draft's rows have
        # overwritten the ring's oldest, and nothing rolls that back —
        # the engine refuses ``spec`` for a window family
        only_plain(tp_axis, ep_axis, lora, kv_scales, window, block_size)
        if ids.shape[1] > block_size + 1:
            raise ValueError(
                f"a verify run of {ids.shape[1]} tokens overwrites ring "
                f"rows its own first query still needs; at most "
                f"block_size + 1 = {block_size + 1}")
        positions = (starts[:, None]
                     + jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :])
        h, *bufs = run(params, k_pool, v_pool, window, ids, positions,
                       tail_lens, tables, 0, False)
        return (logits(params, h), *bufs)

    return prefill_from, decode, verify


def laguna_family(cfg, *, block_size: int = 16) -> Family:
    """``block_size``: the engine's (the rings are ``sliding_window +
    block_size`` rows: :class:`~quintnet_tpu.serve.kv_pool.WindowShapes`);
    the engine refuses a family built for another."""
    from quintnet_tpu.models.laguna import (
        FULL, SLIDING, WEIGHT_TARGETS, laguna_block, laguna_embed,
        laguna_logits, laguna_partition_specs, laguna_rope_tables)

    def run(params, k_pool, v_pool, window, ids, positions, lens, tables,
            row0, chunk):
        """The layers in model order through :func:`_walk_runs`: a full
        layer addresses the pool at its index among the full layers, a
        sliding one the window store at its index among the sliding
        ones."""
        rope = {kind: laguna_rope_tables(positions, cfg, kind)
                for kind in (FULL, SLIDING)}              # [S, P, rot]
        experts = params["blocks"]["experts"]

        def layer(r, blk, i, x, caches):
            return laguna_block(
                blk, x, caches, r.cache_first + i, positions, lens, tables,
                row0, block_size, cfg, *rope[r.attn], attn=r.attn,
                chunk=chunk, experts=experts,
                expert_layer=(None if r.expert_first is None
                              else r.expert_first + i))

        return _walk_runs(cfg.runs, params["blocks"],
                          laguna_embed(params, ids),
                          (k_pool, v_pool, *window), layer)

    prefill_from, decode, verify = _window_contracts(
        "laguna", block_size, run,
        lambda params, h: laguna_logits(params, h, cfg))
    return Family(
        name="laguna", cfg=cfg, n_layers=cfg.n_layers_of(FULL),
        n_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        max_positions=cfg.max_position_embeddings,
        prefill_from=prefill_from, decode=decode, verify=verify,
        partition_specs=laguna_partition_specs,
        weight_targets=WEIGHT_TARGETS,
        layer_pattern=tuple(f"{a}_{m}" for a, m in zip(
            cfg.attn_kinds, cfg.mlp_layer_types)),
        window=WindowShapes(n_layers=cfg.n_layers_of(SLIDING),
                            window=cfg.sliding_window,
                            ring=cfg.sliding_window + block_size),
    )


# --------------------------------------------------------------------
# SmallThinker (window layers at 4,096 beside a NoPE global layer a
# period, a router that reads the layer's INPUT, softmax top-6 ReGLU
# experts, every layer sparse): the window families' walker and
# contracts over its own block
# --------------------------------------------------------------------

def smallthinker_family(cfg, *, block_size: int = 16) -> Family:
    """``block_size``: the engine's (the rings are ``sliding_window_size
    + block_size`` rows); the engine refuses a family built for
    another."""
    from quintnet_tpu.models.smallthinker import (
        GLOBAL, SLIDING, WEIGHT_TARGETS, smallthinker_block,
        smallthinker_embed, smallthinker_logits,
        smallthinker_partition_specs, smallthinker_rope_tables)

    def run(params, k_pool, v_pool, window, ids, positions, lens, tables,
            row0, chunk):
        """As :func:`laguna_family`'s; every run is sparse."""
        rope = smallthinker_rope_tables(positions, cfg)   # [S, P, d]
        experts = params["blocks"]["experts"]

        def layer(r, blk, i, x, caches):
            return smallthinker_block(
                blk, x, caches, r.cache_first + i, positions, lens, tables,
                row0, block_size, cfg, rope if r.attn == SLIDING else None,
                attn=r.attn, chunk=chunk, experts=experts,
                expert_layer=r.expert_first + i)

        return _walk_runs(cfg.runs, params["blocks"],
                          smallthinker_embed(params, ids),
                          (k_pool, v_pool, *window), layer)

    prefill_from, decode, verify = _window_contracts(
        "smallthinker", block_size, run,
        lambda params, h: smallthinker_logits(params, h, cfg))
    return Family(
        name="smallthinker", cfg=cfg, n_layers=cfg.n_layers_of(GLOBAL),
        n_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        max_positions=cfg.max_position_embeddings,
        prefill_from=prefill_from, decode=decode, verify=verify,
        partition_specs=smallthinker_partition_specs,
        weight_targets=WEIGHT_TARGETS, layer_pattern=cfg.attn_kinds,
        window=WindowShapes(n_layers=cfg.n_layers_of(SLIDING),
                            window=cfg.sliding_window_size,
                            ring=cfg.sliding_window_size + block_size),
    )


# --------------------------------------------------------------------
# Ling 3.0 (Kimi-Delta-Attention layers with ONE latent layer closing
# every group of six, leading dense layers, a group-limited mixture):
# a per-slot state AND a latent row a position, in one sequence
# --------------------------------------------------------------------

def ling_hybrid_family(cfg) -> Family:
    from quintnet_tpu.models.ling_hybrid import (
        ABSORBED, MATERIALIZED, WEIGHT_TARGETS, ffn_dense, ffn_moe,
        kda_mixer_chunk, kda_mixer_step, ling_embed,
        ling_hybrid_partition_specs, ling_logits, mla_mixer)
    from quintnet_tpu.nn.attention import rope_cos_sin

    periods, per = cfg.periods, cfg.layer_group_size
    before, dims = cfg.kda_per_period, cfg.kda

    def only_plain(v_pool, tp_axis, ep_axis, lora, kv_scales, state):
        if (v_pool is not None or tp_axis is not None
                or ep_axis is not None or lora is not None
                or kv_scales is not None or state is None):
            raise NotImplementedError(
                "the bailing_hybrid programs run on one device, on the "
                "one unscaled latent pool (v_pool=None) with their state "
                "buffers, without adapters (ServeEngine refuses the "
                "rest at construction)")

    def pick(stack, i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), stack)

    def run_layers(params, pool, state, h, row0, rows, lens, kda_fn,
                   mla_fn):
        """The walk over the layer pattern. The FIRST group is written
        out — its leading KDA layers carry the dense SwiGLU, a short
        scan, then the rest of its KDA layers, a second, then its latent
        layer — and the groups after it are one scan over groups, each
        a short scan of KDA layers and the latent layer, as the hybrid
        family scans periods. Every per-sequence buffer — the latent
        pool and the two state buffers — rides every loop's CARRY,
        whole, beside the mixtures' routing counts; a KDA layer reads
        rows ``[row0, row0 + rows)`` of its own slice and writes them
        back in place, the latent layer writes and reads the pool at
        ``(group, slot)``. The stacks' weights are indexed by layer
        inside the loops; the routed experts stay whole (nn/moe.py)."""
        blocks = params["blocks"]
        experts = blocks["moe"]["moe"]["experts"]
        moe_rest = {"ln2": blocks["moe"]["ln2"],
                    "moe": {k: v for k, v in blocks["moe"]["moe"].items()
                            if k != "experts"}}

        def moe_half(x, stats, layer):
            m = layer - cfg.n_dense_layers
            x, st = ffn_moe(pick(moe_rest, m), x, lens, cfg, experts, m)
            return x, jax.tree.map(jnp.add, stats, st)

        def kda_run(carry, first_layer, first_kda, count, dense):
            def body(c, j):
                x, pool, ssm, conv, stats = c
                ki = first_kda + j
                s = lax.dynamic_slice(
                    ssm, (ki, row0, 0, 0, 0), (1, rows, *ssm.shape[2:]))[0]
                t = lax.dynamic_slice(
                    conv, (ki, row0, 0), (1, rows, conv.shape[2]))[0]
                x, s, t = kda_fn(
                    pick(blocks["kda"], ki), x, s,
                    t.reshape(rows, dims.d_conv - 1, dims.d_qkv))
                with jax.named_scope("kda"), \
                        jax.named_scope("state_update"):
                    ssm = lax.dynamic_update_slice(
                        ssm, s[None], (ki, row0, 0, 0, 0))
                    conv = lax.dynamic_update_slice(
                        conv, t.reshape(1, rows, -1), (ki, row0, 0))
                if dense:
                    x = ffn_dense(pick(blocks["dense"], first_layer + j),
                                  x, cfg)
                else:
                    x, stats = moe_half(x, stats, first_layer + j)
                return (x, pool, ssm, conv, stats), None

            if count == 0:
                return carry
            return lax.scan(body, carry, jnp.arange(count))[0]

        def group(carry, i, n_dense):
            carry = kda_run(carry, i * per, i * before, n_dense, True)
            x, pool, ssm, conv, stats = kda_run(
                carry, i * per + n_dense, i * before + n_dense,
                before - n_dense, False)
            x, pool = mla_fn(pick(blocks["mla"], i), x, pool, i)
            x, stats = moe_half(x, stats, i * per + before)
            return x, pool, ssm, conv, stats

        zero = jnp.zeros((), jnp.float32)
        stats = {"expert_tokens": jnp.zeros(
                     (cfg.num_experts_published,), jnp.float32),
                 **{k: zero for k in (
                     "dropped", "assigned", "entropy", "held_rows",
                     "touched", "elsewhere", "tile_visits",
                     "no_held_group")}}
        with jax.named_scope("blocks"):
            carry = group((h, pool, *state, stats), 0, cfg.n_dense_layers)
            if periods > 1:
                carry, _ = lax.scan(
                    lambda c, i: (group(c, i, 0), None), carry,
                    jnp.arange(1, periods))
        h, pool, ssm, conv, stats = carry
        # counts add over the layers, the entropy is their mean
        stats = {**stats, "entropy": stats["entropy"] / cfg.n_moe_layers}
        return h, pool, ssm, conv, stats

    def run_chunk(params, pool, state, ids, positions, lens, tables,
                  block_size, row0, fresh, form):
        """A run of tokens a row (prefill, chunked prefill, verify)."""
        cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim,
                                theta=cfg.rope_theta)       # [S, P, rope]

        def kda_fn(blk, x, s, t):
            s = jnp.where(fresh, jnp.zeros_like(s), s)
            t = jnp.where(fresh, jnp.zeros_like(t), t)
            return kda_mixer_chunk(blk, x, s, t, lens, cfg)

        def mla_fn(blk, x, pool, layer):
            return mla_mixer(blk, x, pool, layer, positions, lens, tables,
                             block_size, cfg, cos, sin, form=form)

        return run_layers(params, pool, state, ling_embed(params, ids),
                          row0, ids.shape[0], lens, kda_fn, mla_fn)

    def prefill_from(params, k_pool, v_pool, ids, start, t0, table_row,
                     block_size, tp_axis=None, ep_axis=None, lora=None,
                     lora_scale=None, kv_scales=None, policy=None,
                     attn_kernel="xla", state=None, slot=None):
        only_plain(v_pool, tp_axis, ep_axis, lora, kv_scales, state)
        positions = (start + jnp.arange(ids.shape[1], dtype=jnp.int32))[None]
        h, *bufs = run_chunk(
            params, k_pool, state, ids, positions,
            jnp.reshape(t0 - start, (1,)), table_row[None], block_size,
            slot, start == 0, MATERIALIZED)
        h_last = lax.dynamic_slice_in_dim(h, t0 - 1 - start, 1, axis=1)
        return (ling_logits(params, h_last, cfg)[:, 0, :], *bufs)

    def verify(params, k_pool, v_pool, ids, starts, tail_lens, tables,
               block_size, tp_axis=None, ep_axis=None, lora=None,
               lora_scale=None, kv_scales=None, policy=None,
               attn_kernel="xla", state=None):
        # the chunk program for P tokens a row, every row from its
        # CURRENT state, the latent layer in its absorbed form. Not a
        # speculative verify: nothing rolls a state back, and the
        # engine refuses ``spec`` for this family
        only_plain(v_pool, tp_axis, ep_axis, lora, kv_scales, state)
        positions = (starts[:, None]
                     + jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :])
        h, *bufs = run_chunk(params, k_pool, state, ids, positions,
                             tail_lens, tables, block_size, 0, False,
                             ABSORBED)
        return (ling_logits(params, h, cfg), *bufs)

    def decode(params, k_pool, v_pool, tok, pos, tables, block_size,
               tp_axis=None, ep_axis=None, lora=None, lora_scale=None,
               kv_scales=None, policy=None, attn_kernel="xla",
               state=None):
        only_plain(v_pool, tp_axis, ep_axis, lora, kv_scales, state)
        # a row whose table is all null blocks is not decoding (an
        # empty slot, or one in the middle of a chunked prefill): its
        # state stays what it was, its latent row goes to the null
        # block and the router sends its token nowhere
        live = tables[:, 0] != NULL_BLOCK
        lens = live.astype(jnp.int32)
        cos, sin = rope_cos_sin(pos[:, None], cfg.qk_rope_head_dim,
                                theta=cfg.rope_theta)

        def kda_fn(blk, x, s, t):
            x, s2, t2 = kda_mixer_step(blk, x, s, t, cfg)
            return (x, jnp.where(live[:, None, None, None], s2, s),
                    jnp.where(live[:, None, None], t2, t))

        def mla_fn(blk, x, pool, layer):
            return mla_mixer(blk, x, pool, layer, pos[:, None], lens,
                             tables, block_size, cfg, cos, sin,
                             form=ABSORBED)

        h, *bufs = run_layers(
            params, k_pool, state, ling_embed(params, tok[:, None]), 0,
            tok.shape[0], lens, kda_fn, mla_fn)
        return (ling_logits(params, h, cfg)[:, 0, :], *bufs)

    return Family(
        name="bailing_hybrid", cfg=cfg, n_layers=periods, n_kv_heads=1,
        head_dim=cfg.latent_width,
        max_positions=cfg.max_position_embeddings,
        prefill_from=prefill_from, decode=decode, verify=verify,
        partition_specs=ling_hybrid_partition_specs,
        weight_targets=WEIGHT_TARGETS, layer_pattern=cfg.layer_types,
        state=StateShapes(
            n_layers=cfg.n_kda_layers,
            ssm=(dims.n_heads, dims.d_k, dims.d_v),
            conv=((dims.d_conv - 1) * dims.d_qkv,)),
        latent=cfg.latent_width,
    )
