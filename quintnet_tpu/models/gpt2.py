"""GPT-2 family (124M "base" through XL) for causal LM / finetuning.

TPU-native re-design of the reference's GPT-2 stack
(utils/GPT2/{gpt2_config,gpt2_embeddings,gpt2_attention,gpt2_mlp,
gpt2_block,gpt2_stage}.py). Notable differences:

- One whole-model definition (the reference has no full-model class —
  gpt2_model.py is a 3-line placeholder; GPT-2 exists only as pipeline
  stages). Pipelining here is a view over the same param tree.
- Weights stored [in, out] so forward is x @ w; HF GPT-2's Conv1D
  weights are already [in, out], so the import path needs NO transpose
  (the reference transposes every matrix to torch Linear layout —
  core/distributed_loading.py:295-306, 331-341).
- Weight tying: ``lm_head = wte`` is literally the same array. Under
  pipeline parallelism wte is replicated across pp; stage 0 produces the
  embedding grad, the last stage the lm-head grad, and the standard
  partial_axes psum (parallel/train_step.py) sums them — the reference
  needs a dedicated ``sync_tied_weights_grad`` allreduce after every
  backward (gpt2_stage.py:112-141, GPT2_Trainer.py:290-291, 347-348).
- Blocks reuse nn/transformer.py (same pytree schema as ViT): pre-LN,
  fused QKV, GELU(tanh) — matching HF gpt2's gelu_new.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from quintnet_tpu.core.pytree import tree_stack
from quintnet_tpu.nn.layers import (
    cast_floating,
    keep_router_f32,
    embedding_init,
    gelu,
    layer_norm_apply,
    layer_norm_init,
)
from quintnet_tpu.nn.transformer import block_init, stacked_blocks_apply

IGNORE_INDEX = -100  # reference: CE ignore_index=-100 (GPT2_Trainer.py:109)


def _cast_tree(tree, dtype):
    """Mixed-precision cast keeping the MoE router at f32 (its gate
    ordering is bf16-sensitive — nn/moe.py, nn/layers.py)."""
    return cast_floating(tree, dtype, exclude=keep_router_f32)


@dataclass(frozen=True)
class GPT2Config:
    """Sizes follow the reference's presets (gpt2_config.py:22-168)."""

    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    dropout: float = 0.0
    # per-site rates (reference gpt2_config.yaml:31-33 attn_pdrop /
    # embd_pdrop / resid_pdrop); None falls back to ``dropout``
    embd_pdrop: Optional[float] = None
    attn_pdrop: Optional[float] = None
    resid_pdrop: Optional[float] = None
    # --- MoE (0 experts = dense; the reference has no MoE/EP at all,
    # SURVEY.md §2.2 "EP — Absent"). Every block's MLP becomes a top-k
    # routed MoE FFN (nn/moe.py), expert-shardable over the ``ep`` axis.
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    expert_capacity: Optional[int] = None
    aux_loss_weight: float = 1e-2
    router_z_weight: float = 0.0
    # "topk" (Switch/Mixtral) | "expert_choice" (perfect load balance,
    # no aux loss — nn/moe.py)
    router_type: str = "topk"
    # --- vocab parallelism: shard wte over tp (the reference DEFINES
    # VocabParallelEmbedding but never uses it, layers.py:224-297 —
    # GPT-2 replicates embeddings there). With it on, the lm-head loss
    # is a sharded cross-entropy (local logsumexp + psum) so full
    # [B, T, V] logits are NEVER materialised on any rank. Requires
    # vocab_size % tp == 0 (pad, e.g. 50257 -> 50304, Megatron-style;
    # padded columns are masked out of the softmax so the loss is
    # bit-comparable to the unpadded model). Ignored when tp is off.
    vocab_parallel: bool = False
    # wte table rows when padding the vocab to a tp multiple;
    # ``vocab_size`` stays the REAL vocab (labels/ids range, softmax
    # support). None = no padding (table rows == vocab_size).
    padded_vocab_size: Optional[int] = None
    # --- chunked CE (replicated-activation paths): compute the CLM loss
    # in sequence chunks of this many positions so full [B, S, V] f32
    # logits never materialise (clm_loss_chunked). 0 = off. Ignored
    # under sp (clm_loss_sp) / vocab_parallel (clm_loss_vp), which
    # already avoid full logits their own way.
    loss_chunk: int = 0
    # --- packed-document isolation: when set, attention segment ids are
    # derived on the fly from input_ids (a new segment starts AFTER each
    # occurrence of this token) and threaded into every attention layer
    # incl. the Pallas kernels (ops/pallas_attention segment_ids) —
    # positions never attend across packed-document boundaries. None =
    # the GPT-2 convention (cross-document attention accepted).
    segment_eos_id: Optional[int] = None
    # --- lax.scan unroll factor for the layer stack (>1 lets XLA
    # software-pipeline adjacent layers; a knob awaiting its one chip
    # measurement — ROADMAP S5/D4)
    scan_unroll: int = 1

    @property
    def mlp_hidden(self) -> int:
        return 4 * self.n_embd

    @property
    def table_vocab_size(self) -> int:
        """wte rows (padded vocab when padding is configured)."""
        return self.padded_vocab_size or self.vocab_size

    @property
    def pdrops(self):
        """(embd, attn, resid) dropout rates with ``dropout`` fallback."""
        d = self.dropout
        return (d if self.embd_pdrop is None else self.embd_pdrop,
                d if self.attn_pdrop is None else self.attn_pdrop,
                d if self.resid_pdrop is None else self.resid_pdrop)

    @property
    def needs_dropout(self) -> bool:
        return any(p > 0.0 for p in self.pdrops)

    @property
    def moe_args(self):
        """nn/moe.py MoEArgs for this config, or None when dense."""
        if self.n_experts <= 0:
            return None
        if self.router_type == "expert_choice":
            # EC selects over the whole flattened sequence — position t
            # would see later positions (nn/moe.py MoEArgs.router docs).
            raise ValueError(
                "expert_choice routing is non-causal and unsupported "
                "for the causal LM families; use router_type='topk' "
                "(expert_choice remains available at the nn/moe.py "
                "layer for non-autoregressive models)")
        from quintnet_tpu.nn.moe import MoEArgs

        return MoEArgs(
            n_experts=self.n_experts,
            top_k=self.expert_top_k,
            capacity_factor=self.capacity_factor,
            capacity=self.expert_capacity,
            aux_weight=self.aux_loss_weight,
            z_weight=self.router_z_weight,
            router=self.router_type,
        )

    @staticmethod
    def base() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def medium() -> "GPT2Config":
        return GPT2Config(n_embd=1024, n_layer=24, n_head=16)

    @staticmethod
    def large() -> "GPT2Config":
        return GPT2Config(n_embd=1280, n_layer=36, n_head=20)

    @staticmethod
    def xl() -> "GPT2Config":
        return GPT2Config(n_embd=1600, n_layer=48, n_head=25)

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        """Test-scale config (not in the reference; used by the simulated-
        mesh test suite)."""
        d = dict(vocab_size=128, n_positions=64, n_embd=32, n_layer=4,
                 n_head=4)
        d.update(kw)
        return GPT2Config(**d)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "GPT2Config":
        names = {f.name for f in dataclasses.fields(GPT2Config)}
        return GPT2Config(**{k: v for k, v in d.items() if k in names})


def gpt2_init(key, cfg: GPT2Config, *, dtype=jnp.float32):
    k_wte, k_wpe, k_blocks = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layer)
    blocks = tree_stack(
        [block_init(bk, cfg.n_embd, mlp_hidden=cfg.mlp_hidden, dtype=dtype,
                    moe=cfg.moe_args)
         for bk in block_keys]
    )
    return {
        "embedding": {
            "wte": embedding_init(k_wte, cfg.table_vocab_size, cfg.n_embd,
                                  dtype=dtype)["table"],
            "wpe": embedding_init(k_wpe, cfg.n_positions, cfg.n_embd,
                                  scale=0.01, dtype=dtype)["table"],
        },
        "blocks": blocks,
        "head": {"ln_f": layer_norm_init(cfg.n_embd, dtype)},
    }


def gpt2_upcycle_to_moe(params, cfg: GPT2Config, key=None):
    """Sparse upcycling: dense GPT-2 params -> MoE params for a config
    with ``n_experts > 0``. Every expert starts as a copy of the dense
    MLP; routers start near-zero so initial routing is ~uniform and the
    upcycled model's function approximates the dense one. Used by the
    finetune entry point when --experts is combined with --checkpoint
    (there is no reference analogue — the reference has no MoE)."""
    if cfg.n_experts <= 0:
        return params
    if "moe" in params["blocks"]:
        return params  # already MoE
    key = key if key is not None else jax.random.key(0)
    E = cfg.n_experts
    blocks = dict(params["blocks"])
    mlp = blocks.pop("mlp")
    L = mlp["fc"]["w"].shape[0]

    def per_expert(x):  # [L, ...] -> [L, E, ...]
        return jnp.repeat(x[:, None], E, axis=1)

    blocks["moe"] = {
        "router": {"w": 1e-2 * jax.random.normal(
            key, (L, cfg.n_embd, E), jnp.float32)},
        "w1": per_expert(mlp["fc"]["w"]),
        "b1": per_expert(mlp["fc"]["b"]),
        "w2": per_expert(mlp["proj"]["w"]),
        "b2": per_expert(mlp["proj"]["b"]),
    }
    return {**params, "blocks": blocks}


def gpt2_embed(params, input_ids, *, sp_axis: Optional[str] = None,
               embd_pdrop: float = 0.0, key=None,
               vp_axis: Optional[str] = None):
    """[B, T_local] ids -> [B, T_local, D] (reference GPT2Embedding,
    replicated across TP — gpt2_embeddings.py:16-103, including its
    post-sum embedding dropout :100-101 when ``key`` is given).

    With ``sp_axis`` the sequence dim is sharded: this rank's position
    embeddings start at axis_index * T_local. With ``vp_axis`` the wte
    VOCAB dim is sharded over that (tp) axis: out-of-shard ids
    contribute zeros and one psum assembles the embedding
    (parallel/tp.py:vocab_parallel_embedding semantics; the reference
    defined-but-unused VocabParallelEmbedding, layers.py:224-297)."""
    emb = params["embedding"]
    T = input_ids.shape[-1]
    with jax.named_scope("embed"):
        if vp_axis is not None:
            from quintnet_tpu.parallel.tp import vocab_parallel_embedding

            tok = vocab_parallel_embedding({"table": emb["wte"]},
                                           input_ids, axis=vp_axis)
        else:
            tok = jnp.take(emb["wte"], input_ids, axis=0)
        start = 0
        if sp_axis is not None:
            start = jax.lax.axis_index(sp_axis) * T
        pos = jax.lax.dynamic_slice_in_dim(emb["wpe"], start, T, axis=0)
        h = tok + pos[None, :, :]
        if key is not None and embd_pdrop > 0.0:
            from quintnet_tpu.nn.layers import dropout

            h = dropout(key, h, embd_pdrop, deterministic=False)
    return h


def gpt2_blocks(params_blocks, h, cfg: GPT2Config, *,
                tp_axis: Optional[str] = None,
                sp_axis: Optional[str] = None, sp_mode: str = "ring",
                ep_axis: Optional[str] = None,
                remat: "bool | str" = False,
                key=None, segment_ids=None, fsdp=None):
    """Returns ``h`` for dense configs, ``(h, moe_aux)`` when
    ``cfg.n_experts > 0``. ``key`` enables training dropout."""
    tp = 1 if tp_axis is None else jax.lax.axis_size(tp_axis)
    _, attn_p, resid_p = cfg.pdrops
    with jax.named_scope("blocks"):
        return stacked_blocks_apply(
            params_blocks, h,
            num_heads=cfg.n_head // tp,
            causal=True,
            act=gelu,
            tp_axis=tp_axis,
            sp_axis=sp_axis,
            sp_mode=sp_mode,
            remat=remat,
            moe_args=cfg.moe_args,
            ep_axis=ep_axis,
            attn_pdrop=attn_p,
            resid_pdrop=resid_p,
            key=key,
            scan_unroll=cfg.scan_unroll,
            segment_ids=segment_ids,
            fsdp=fsdp,
        )


def gpt2_logits(params, h, cfg: GPT2Config):
    """ln_f then tied lm_head: logits = ln_f(h) @ wte^T
    (reference: lm_head is a copy of wte synced by hand,
    gpt2_stage.py:112-141; here it IS wte).

    With a padded vocab and an UNSHARDED table (wte rows ==
    table_vocab_size: no-tp fallback of a vocab_parallel config, or
    single-device generation), the padded columns are masked to -inf
    here so they never enter any softmax and argmax-decoding can never
    emit an id >= vocab_size. Vocab-SHARDED tables (local rows under
    vp) are masked inside clm_loss_vp instead, which knows the shard
    offset."""
    with jax.named_scope("final_norm"):
        h = layer_norm_apply(params["head"]["ln_f"], h,
                             eps=cfg.layer_norm_epsilon)
    with jax.named_scope("lm_head"):
        logits = jnp.dot(h, params["embedding"]["wte"].T
                         ).astype(jnp.float32)
        if (cfg.padded_vocab_size
                and params["embedding"]["wte"].shape[0]
                == cfg.table_vocab_size):
            logits = mask_padded_cols(logits, cfg)
    return logits


def mask_padded_cols(logits, cfg: "GPT2Config"):
    """-inf the vocab-padding columns of FULL-width logits so they never
    enter a softmax or win an argmax (single place for the semantics:
    used by gpt2_logits, clm_loss_chunked and the tp decoder)."""
    col = jnp.arange(logits.shape[-1])
    return jnp.where(col < cfg.vocab_size, logits,
                     jnp.finfo(jnp.float32).min)


def gpt2_hidden(params, input_ids, cfg: GPT2Config, *,
                tp_axis: Optional[str] = None,
                sp_axis: Optional[str] = None, sp_mode: str = "ring",
                ep_axis: Optional[str] = None,
                remat: "bool | str" = False,
                key=None, fsdp=None):
    """embed + blocks -> (final hidden states [B, T, D], moe_aux); the
    pre-lm-head half of :func:`gpt2_forward` (chunked-CE computes the
    loss straight from these, never building full logits)."""
    k_embd = k_blocks = None
    if key is not None and cfg.needs_dropout:
        k_embd, k_blocks = jax.random.split(key)
    vp_axis = tp_axis if (cfg.vocab_parallel and tp_axis) else None
    h = gpt2_embed(params, input_ids, sp_axis=sp_axis,
                   embd_pdrop=cfg.pdrops[0], key=k_embd, vp_axis=vp_axis)
    seg = segment_ids_from_input(input_ids, cfg, sp_axis=sp_axis)
    out = gpt2_blocks(params["blocks"], h, cfg, tp_axis=tp_axis,
                      sp_axis=sp_axis, sp_mode=sp_mode, ep_axis=ep_axis,
                      remat=remat, key=k_blocks,
                      segment_ids=seg, fsdp=fsdp)
    return out if cfg.n_experts > 0 else (out, jnp.zeros((), jnp.float32))


def gpt2_forward(params, input_ids, cfg: GPT2Config, *,
                 tp_axis: Optional[str] = None,
                 sp_axis: Optional[str] = None, sp_mode: str = "ring",
                 ep_axis: Optional[str] = None,
                 remat: "bool | str" = False,
                 key=None, fsdp=None):
    """-> (logits, moe_aux). ``moe_aux`` is 0.0 for dense configs.
    ``key``: training-dropout key (None -> deterministic/eval)."""
    h, aux = gpt2_hidden(params, input_ids, cfg, tp_axis=tp_axis,
                         sp_axis=sp_axis, sp_mode=sp_mode, ep_axis=ep_axis,
                         remat=remat, key=key,
                         fsdp=fsdp)
    return gpt2_logits(params, h, cfg), aux


def gpt2_apply(params, input_ids, cfg: GPT2Config, *,
               tp_axis: Optional[str] = None,
               sp_axis: Optional[str] = None, sp_mode: str = "ring",
               ep_axis: Optional[str] = None,
               remat: "bool | str" = False):
    logits, _ = gpt2_forward(params, input_ids, cfg, tp_axis=tp_axis,
                             sp_axis=sp_axis, sp_mode=sp_mode,
                             ep_axis=ep_axis, remat=remat)
    return logits


def clm_loss(logits, labels):
    """Shifted causal-LM cross entropy with IGNORE_INDEX masking, mean
    over valid tokens (reference: HF-internal shift + CE ignore_index=-100,
    GPT2_Trainer.py:105-118)."""
    with jax.named_scope("loss"):
        logits = logits[:, :-1]
        targets = labels[:, 1:]
        valid = targets != IGNORE_INDEX
        safe = jnp.where(valid, targets, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, nll, 0.0)
        count = jnp.maximum(jnp.sum(valid), 1)
        return jnp.sum(nll) / count


def clm_loss_chunked(params, h, labels, cfg: "GPT2Config", *, chunk: int):
    """CLM loss computed in sequence chunks straight from the final
    hidden states: the full [B, S, V] logits / log-softmax (f32: ~823MB
    for the bs-8/seq-512 bench config) NEVER materialize — each scan
    step computes one [B, chunk, V] slab, reduces it to (nll_sum,
    count), and the jax.checkpoint'd body recomputes the slab in
    backward instead of storing it. Same math as clm_loss to float
    reassociation (tests/test_gpt2.py golden).

    Single-device / dp/tp-replicated-activation path only (sp shards
    the sequence -> clm_loss_sp; vocab_parallel -> clm_loss_vp)."""
    with jax.named_scope("final_norm"):
        h = layer_norm_apply(params["head"]["ln_f"], h,
                             eps=cfg.layer_norm_epsilon)
    wte = params["embedding"]["wte"]
    h_pred = h[:, :-1]
    targets = labels[:, 1:]
    B, S, D = h_pred.shape
    pad = (-S) % chunk
    if pad:
        h_pred = jnp.pad(h_pred, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=IGNORE_INDEX)
    nc = (S + pad) // chunk
    h_c = h_pred.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    t_c = targets.reshape(B, nc, chunk).transpose(1, 0, 2)
    mask_pad_cols = (cfg.padded_vocab_size
                     and wte.shape[0] == cfg.table_vocab_size)

    @jax.checkpoint
    def body(carry, xs):
        hc, tc = xs
        with jax.named_scope("lm_head"):
            logits = jnp.dot(hc, wte.T).astype(jnp.float32)
            if mask_pad_cols:
                logits = mask_padded_cols(logits, cfg)
        valid = tc != IGNORE_INDEX
        safe = jnp.where(valid, tc, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        nll_sum, count = carry
        return (nll_sum + jnp.sum(jnp.where(valid, nll, 0.0)),
                count + jnp.sum(valid)), None

    with jax.named_scope("loss"):
        (nll_sum, count), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (h_c, t_c))
        return nll_sum / jnp.maximum(count, 1)


def _sp_shift_targets(labels, sp_axis: str):
    """Next-token target shift when the sequence dim is sharded: each
    rank's last position targets the FIRST label of the next rank's
    chunk (one ppermute); the global-final position (last rank's last
    column) is invalidated. Shared by :func:`clm_loss_sp` and
    :func:`clm_loss_vp` so the shift semantics cannot diverge."""
    sp = jax.lax.axis_size(sp_axis)
    idx = jax.lax.axis_index(sp_axis)
    # rank i+1 sends its first label column to rank i
    perm = [(i + 1, i) for i in range(sp - 1)]
    first_next = jax.lax.ppermute(labels[:, :1], sp_axis, perm)
    targets = jnp.concatenate([labels[:, 1:], first_next], axis=1)
    col = jnp.arange(targets.shape[1])
    boundary = (idx == sp - 1) & (col == targets.shape[1] - 1)
    return jnp.where(boundary[None, :], IGNORE_INDEX, targets)


def clm_loss_sp(logits, labels, *, sp_axis: str):
    """CLM loss when the sequence dim is sharded over ``sp_axis``.

    The next-token shift crosses chunk boundaries
    (:func:`_sp_shift_targets`). Token-count normalisation is global
    (psum of sums / psum of counts), so the result equals
    :func:`clm_loss` on the gathered sequence exactly.
    """
    targets = _sp_shift_targets(labels, sp_axis)

    valid = targets != IGNORE_INDEX
    safe = jnp.where(valid, targets, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    total = jax.lax.psum(jnp.sum(nll), sp_axis)
    count = jax.lax.psum(jnp.sum(valid), sp_axis)
    return total / jnp.maximum(count, 1)


def clm_loss_vp(local_logits, labels, *, tp_axis: str,
                sp_axis: Optional[str] = None,
                vocab_size: Optional[int] = None):
    """CLM loss from VOCAB-SHARDED logits [B, T, V/tp] — the sharded
    cross-entropy: full logits are never materialised on any rank.

    Global logsumexp = log(psum(sum(exp(local - max)))) + max with the
    max pmax'd over tp (stop_gradient on the shift — the true softmax
    gradient flows through the exp/psum path). The target's logit is
    picked by the one rank whose shard holds it and psummed. Equals
    :func:`clm_loss` (resp. :func:`clm_loss_sp` when ``sp_axis``) on the
    gathered logits exactly. ``vocab_size`` masks padded vocab columns
    (Megatron-style padding to a tp multiple) out of the softmax so the
    padded and unpadded models give identical losses."""
    if sp_axis is None:
        logits = local_logits[:, :-1]
        targets = labels[:, 1:]
    else:
        targets = _sp_shift_targets(labels, sp_axis)
        logits = local_logits

    logits = logits.astype(jnp.float32)
    vp = logits.shape[-1]
    start = jax.lax.axis_index(tp_axis) * vp
    if vocab_size is not None:
        col_ids = start + jnp.arange(vp)
        logits = jnp.where(col_ids < vocab_size, logits,
                           jnp.finfo(jnp.float32).min)
    valid = targets != IGNORE_INDEX
    # stop_gradient BEFORE the pmax (pmax has no JVP rule; the shift is
    # a constant anyway — the true softmax grad flows via exp/psum)
    m = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(logits, axis=-1)),
                     tp_axis)
    se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    lse = jnp.log(jax.lax.psum(se, tp_axis)) + m
    local_t = jnp.where(valid, targets, 0) - start
    in_shard = (local_t >= 0) & (local_t < vp)
    safe = jnp.clip(local_t, 0, vp - 1)
    tl = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    tl = jax.lax.psum(jnp.where(in_shard, tl, 0.0), tp_axis)
    nll = jnp.where(valid, lse - tl, 0.0)
    total = jnp.sum(nll)
    count = jnp.sum(valid)
    if sp_axis is not None:
        total = jax.lax.psum(total, sp_axis)
        count = jax.lax.psum(count, sp_axis)
    return total / jnp.maximum(count, 1)


def perplexity(loss):
    """exp(loss) with the reference's overflow guard at 20
    (GPT2_Trainer.py:316-318, schedule.py:505-516)."""
    return jnp.exp(jnp.minimum(loss, 20.0))


def gpt2_partition_specs(cfg: Optional[GPT2Config] = None, *,
                         tp_axis: Optional[str] = "tp",
                         pp_axis: Optional[str] = None,
                         ep_axis: Optional[str] = None,
                         fsdp_axis: Optional[str] = None):
    from jax.sharding import PartitionSpec as P

    from quintnet_tpu.parallel.tp import block_specs

    bspecs = block_specs(tp_axis=tp_axis, stacked=True, pp_axis=pp_axis)
    if cfg is not None and cfg.n_experts > 0:
        from quintnet_tpu.nn.moe import moe_specs

        del bspecs["mlp"]
        bspecs["moe"] = moe_specs(ep_axis=ep_axis, tp_axis=tp_axis,
                                  stacked=True, pp_axis=pp_axis)
    if fsdp_axis is not None:
        from quintnet_tpu.parallel.tp import fsdp_shard_specs

        bspecs = fsdp_shard_specs(bspecs, fsdp_axis)
    wte_spec = P()
    if cfg is not None and cfg.vocab_parallel and tp_axis is not None:
        # vocab dim sharded over tp; grads stay un-psummed over tp by
        # reduce_grads' spec rule (train_step.py) — the vp loss/embed
        # psums supply the tp cotangent factor exactly once.
        wte_spec = P(tp_axis, None)
    return {
        "embedding": {"wte": wte_spec, "wpe": P()},
        "blocks": bspecs,
        "head": {"ln_f": {"scale": P(), "bias": P()}},
    }


def gpt2_to_tp_layout(params, cfg: GPT2Config, tp: int):
    """Standard [q|k|v] fused-QKV columns -> tp-blocked layout
    (parallel/tp.py docstring). Identity at tp=1."""
    from quintnet_tpu.parallel.tp import qkv_blocked_from_standard

    if cfg.vocab_parallel and tp > 1 and cfg.table_vocab_size % tp != 0:
        raise ValueError(
            f"vocab_parallel needs (padded_)vocab_size % tp == 0; got "
            f"{cfg.table_vocab_size} % {tp}. Set padded_vocab_size "
            f"(e.g. 50257 -> 50304); padded columns are masked out of "
            f"the loss.")
    if tp == 1:
        return params
    out = jax.tree.map(lambda x: x, params)
    qkv = out["blocks"]["attn"]["qkv"]
    qkv["w"] = qkv_blocked_from_standard(qkv["w"], cfg.n_head, tp)
    if "b" in qkv:
        qkv["b"] = qkv_blocked_from_standard(qkv["b"], cfg.n_head, tp)
    return out


def gpt2_from_tp_layout(params, cfg: GPT2Config, tp: int):
    """Inverse of :func:`gpt2_to_tp_layout` — back to the standard
    [q|k|v] fused-QKV column order (for export and for single-device
    generation on trained tp-sharded params)."""
    from quintnet_tpu.parallel.tp import qkv_standard_from_blocked

    if tp == 1:
        return params
    out = jax.tree.map(lambda x: x, params)
    qkv = out["blocks"]["attn"]["qkv"]
    qkv["w"] = qkv_standard_from_blocked(qkv["w"], cfg.n_head, tp)
    if "b" in qkv:
        qkv["b"] = qkv_standard_from_blocked(qkv["b"], cfg.n_head, tp)
    return out


def segment_ids_from_input(input_ids, cfg: GPT2Config, *,
                           sp_axis: Optional[str] = None):
    """[B, S] token ids -> [B, S] int32 attention segment ids, or None
    when ``cfg.segment_eos_id`` is unset. Device-side equivalent of
    data/datasets.segments_from_tokens: exclusive running count of the
    separator (each EOS closes its own document).

    ``sp_axis``: the sequence dim is a SHARD of the global sequence —
    the local count is offset by the total separator count of all
    earlier shards (one tiny [sp, B] all-gather), so ids are globally
    consistent and the sp attention modes can compare them across
    chunks."""
    if cfg.segment_eos_id is None:
        return None
    is_eos = (input_ids == cfg.segment_eos_id).astype(jnp.int32)
    seg = jnp.cumsum(is_eos, axis=1) - is_eos
    if sp_axis is not None:
        sp = jax.lax.axis_size(sp_axis)
        idx = jax.lax.axis_index(sp_axis)
        counts = jax.lax.all_gather(jnp.sum(is_eos, axis=1),
                                    sp_axis)               # [sp, B]
        prefix = jnp.sum(
            jnp.where(jnp.arange(sp)[:, None] < idx, counts, 0), axis=0)
        seg = seg + prefix[:, None]
    return seg


def gpt2_pipeline_fns(cfg: GPT2Config, *, tp_axis: Optional[str] = None,
                      sp_axis: Optional[str] = None, sp_mode: str = "ring",
                      ep_axis: Optional[str] = None,
                      remat: "bool | str" = False,
                      compute_dtype=None):
    """(embed_fn, stage_fn, head_loss_fn) for parallel/pp.py.

    ``compute_dtype=jnp.bfloat16``: params are cast at use (storage stays
    f32 master copies; the cast's transpose accumulates grads back in
    f32) — the TPU mixed-precision default. Softmax/LN/loss stay f32.

    MoE configs make ``stage_fn`` return ``(h, aux)`` — the schedules in
    parallel/pp.py accumulate each stage's aux into the loss.

    ``key`` kwargs on embed/stage enable training dropout; the schedules
    pass per-(microbatch, stage) keys (parallel/pp.py) so the 1F1B
    vjp-recompute reproduces the forward masks exactly.
    """
    if cfg.segment_eos_id is not None:
        raise NotImplementedError(
            "segment_eos_id under pipeline parallelism is not wired "
            "(stage fns receive hidden states, not token ids, so the "
            "segment vector cannot be derived mid-pipeline); use "
            "dp/tp/ep meshes for packed-document isolation")

    def embed_fn(params, input_ids, key=None):
        return gpt2_embed(_cast_tree(params, compute_dtype), input_ids,
                          sp_axis=sp_axis, embd_pdrop=cfg.pdrops[0],
                          key=key,
                          vp_axis=(tp_axis if cfg.vocab_parallel else None))

    def stage_fn(blocks_local, h, key=None):
        return gpt2_blocks(_cast_tree(blocks_local, compute_dtype), h, cfg,
                           tp_axis=tp_axis, sp_axis=sp_axis, sp_mode=sp_mode,
                           ep_axis=ep_axis, remat=remat,
                           key=key)

    vp = cfg.vocab_parallel and tp_axis is not None
    if vp or sp_axis is not None:
        # the loss contains collectives (vp lse psums / sp shift+psum),
        # which may not sit inside the schedules' lax.cond gate — split:
        # gated collective-free lm-head matmul, unconditional reduction
        # (parallel/pp.py SplitHead)
        from quintnet_tpu.parallel.pp import SplitHead

        def head_local_fn(params, h, labels):
            return gpt2_logits(_cast_tree(params, compute_dtype), h, cfg)

        def head_reduce_fn(logits, labels, valid):
            if vp:
                loss = clm_loss_vp(
                    logits, labels, tp_axis=tp_axis, sp_axis=sp_axis,
                    vocab_size=(cfg.vocab_size if cfg.padded_vocab_size
                                else None))
            else:
                loss = clm_loss_sp(logits, labels, sp_axis=sp_axis)
            return jnp.where(valid, loss, 0.0)

        return embed_fn, stage_fn, SplitHead(head_local_fn, head_reduce_fn)

    def head_loss_fn(params, h, labels):
        p = _cast_tree(params, compute_dtype)
        if cfg.loss_chunk > 0:
            return clm_loss_chunked(p, h, labels, cfg,
                                    chunk=cfg.loss_chunk)
        return clm_loss(gpt2_logits(p, h, cfg), labels)

    return embed_fn, stage_fn, head_loss_fn


def _fsdp_info(cfg: "GPT2Config", tp_axis, ep_axis, fsdp_axis):
    from quintnet_tpu.parallel.tp import fsdp_info

    return fsdp_info(functools.partial(gpt2_partition_specs, cfg),
                     fsdp_axis, tp_axis=tp_axis, ep_axis=ep_axis)


def gpt2_model_spec(cfg: GPT2Config, *, remat: "bool | str" = False,
                    sp_mode: str = "ring",
                    compute_dtype=None):
    from jax.sharding import PartitionSpec as P

    from quintnet_tpu.parallel.strategy import ModelSpec

    def loss_fn(params, batch, tp_axis=None, sp_axis=None, ep_axis=None,
                key=None, fsdp_axis=None):
        input_ids, labels = batch
        p = _cast_tree(params, compute_dtype)
        fsdp = _fsdp_info(cfg, tp_axis, ep_axis, fsdp_axis)
        vp = cfg.vocab_parallel and tp_axis is not None
        if cfg.loss_chunk > 0 and not vp and sp_axis is None:
            h, aux = gpt2_hidden(p, input_ids, cfg, tp_axis=tp_axis,
                                 sp_axis=sp_axis, sp_mode=sp_mode,
                                 ep_axis=ep_axis, remat=remat,
                                 key=key, fsdp=fsdp)
            return clm_loss_chunked(p, h, labels, cfg,
                                    chunk=cfg.loss_chunk) + aux
        logits, aux = gpt2_forward(p, input_ids, cfg, tp_axis=tp_axis,
                                   sp_axis=sp_axis, sp_mode=sp_mode,
                                   ep_axis=ep_axis, remat=remat,
                                   key=key,
                                   fsdp=fsdp)
        if vp:
            with jax.named_scope("loss"):
                return clm_loss_vp(
                    logits, labels, tp_axis=tp_axis, sp_axis=sp_axis,
                    vocab_size=(cfg.vocab_size if cfg.padded_vocab_size
                                else None)) + aux
        if sp_axis is not None:
            with jax.named_scope("loss"):
                return clm_loss_sp(logits, labels, sp_axis=sp_axis) + aux
        return clm_loss(logits, labels) + aux

    def pipeline_fns(tp_axis=None, sp_axis=None, ep_axis=None):
        return gpt2_pipeline_fns(cfg, tp_axis=tp_axis, sp_axis=sp_axis,
                                 sp_mode=sp_mode, ep_axis=ep_axis,
                                 remat=remat,
                                 compute_dtype=compute_dtype)

    def batch_specs(batch_axes, sp_axis=None):
        # (input_ids, labels): batch dim over dp (+ep), sequence dim over sp
        spec = P(tuple(batch_axes) if batch_axes else None, sp_axis)
        return (spec, spec)

    return ModelSpec(
        init=lambda key: gpt2_init(key, cfg),
        loss_fn=loss_fn,
        partition_specs=lambda tp_axis=None, pp_axis=None, ep_axis=None, \
                fsdp_axis=None:
            gpt2_partition_specs(cfg, tp_axis=tp_axis, pp_axis=pp_axis,
                                 ep_axis=ep_axis, fsdp_axis=fsdp_axis),
        pipeline_fns=pipeline_fns,
        to_tp_layout=lambda p, tp: gpt2_to_tp_layout(p, cfg, tp),
        depth=cfg.n_layer,
        batch_specs=batch_specs,
        needs_rng=cfg.needs_dropout,
    )
