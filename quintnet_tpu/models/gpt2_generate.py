"""KV-cache autoregressive generation for GPT-2 (dense and MoE).

The reference generates by re-running the FULL prefix through the model
for every new token (greedy loop in utils/metrics.py:74-149) — O(T^2)
attention work per token and a fresh compile-sized dispatch each step.
Here decoding is TPU-shaped:

- **prefill**: one causal forward over the prompt that also emits every
  layer's (k, v) into a [L, B, H, T_max, Dh] cache (nn/transformer.py
  block_prefill);
- **decode**: a single jitted ``lax.scan`` over new-token steps, each
  step one cached block pass per layer (nn/attention.py mha_decode) —
  O(T) per token, static shapes throughout, one compilation total;
- **EOS** handling inside the scan: finished rows keep emitting
  ``eos_token_id`` (same observable behavior as the reference's early
  exit, without dynamic shapes).

Greedy by default; ``temperature > 0`` switches to sampling.

Generation runs single-device by default, and TP-SHARDED via
:func:`gpt2_generate_tp`: head-sharded prefill+decode with the
RowParallel psum in every cached attention step and (for
``cfg.vocab_parallel``) vocab-sharded logits assembled by all-gather.
The reference cannot generate under ANY parallelism (gen eval skipped,
GPT2_Trainer.py:509-555) — anything bigger than one chip's HBM can't
eval there; here the same tp mesh that trains also decodes.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_logits
from quintnet_tpu.nn.layers import gelu, layer_norm_apply
from quintnet_tpu.nn.transformer import block_decode, block_prefill


def sample_logits(logits, key, *, temperature: float = 1.0,
                  top_k: int = 0, top_p: float = 1.0):
    """Sample next tokens from [B, V] logits: temperature, then top-k
    truncation, then nucleus (top-p). ``temperature <= 0`` is greedy
    argmax regardless of the filters (matches HF semantics; the
    reference supports greedy only, utils/metrics.py:74-149).

    Static-shape throughout: top-k thresholds against the k-th largest
    logit; top-p sorts the full vocab once per step (eval-time cost,
    fine off the training path)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    neg = jnp.finfo(logits.dtype).min
    if top_k and top_k < logits.shape[-1]:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if 0.0 < top_p < 1.0:
        srt, idx = lax.top_k(logits, logits.shape[-1])  # desc sort
        probs = jax.nn.softmax(srt, axis=-1)
        # drop tokens whose preceding cumulative mass already reached
        # top_p (the first token crossing the threshold is KEPT). The
        # few-ulp slack keeps the boundary decision stable across jax
        # versions: softmax(log(p)) can land a hair under an exactly-
        # representable threshold (e.g. 0.79999995 vs top_p=0.8).
        tol = 16 * jnp.finfo(probs.dtype).eps
        drop = jnp.cumsum(probs, axis=-1) - probs > top_p - tol
        srt = jnp.where(drop, neg, srt)
        # un-sort: position j of the sorted row goes back to column
        # idx[j]; argsort(idx) inverts the permutation
        inv = jnp.argsort(idx, axis=-1)
        logits = jnp.take_along_axis(srt, inv, axis=-1)
    return jax.random.categorical(key, logits, axis=-1)


def _local_heads(cfg: GPT2Config, tp_axis: Optional[str]) -> int:
    if tp_axis is None:
        return cfg.n_head
    return cfg.n_head // lax.axis_size(tp_axis)


def _embed_tok(emb, ids, cfg: GPT2Config, tp_axis: Optional[str]):
    """Token embedding; vocab-sharded lookup + psum under vp."""
    if tp_axis is not None and cfg.vocab_parallel:
        from quintnet_tpu.parallel.tp import vocab_parallel_embedding

        return vocab_parallel_embedding({"table": emb["wte"]}, ids,
                                        axis=tp_axis)
    return jnp.take(emb["wte"], ids, axis=0)


def _logits(params, h, cfg: GPT2Config, tp_axis: Optional[str]):
    """Full-vocab logits. Under vocab_parallel the local [.., V/tp]
    shard is all-gathered on the vocab dim (parallel/tp.py
    vocab_parallel_logits) and padded columns masked."""
    if tp_axis is None or not cfg.vocab_parallel:
        return gpt2_logits(params, h, cfg)
    from quintnet_tpu.models.gpt2 import mask_padded_cols
    from quintnet_tpu.parallel.tp import vocab_parallel_logits

    with jax.named_scope("final_norm"):
        h = layer_norm_apply(params["head"]["ln_f"], h,
                             eps=cfg.layer_norm_epsilon)
    with jax.named_scope("lm_head"):
        logits = vocab_parallel_logits(
            params["embedding"]["wte"].T, h,
            axis=tp_axis).astype(jnp.float32)
        if cfg.padded_vocab_size:
            logits = mask_padded_cols(logits, cfg)
    return logits


def gpt2_prefill(params, input_ids, cfg: GPT2Config, *, cache_len: int,
                 tp_axis: Optional[str] = None):
    """[B, T0] prompt -> (last-position logits [B, V],
    (k_cache, v_cache) each [L, B, H, cache_len, Dh]).
    Under ``tp_axis`` H is LOCAL heads (H/tp)."""
    B, T0 = input_ids.shape
    emb = params["embedding"]
    h = _embed_tok(emb, input_ids, cfg, tp_axis) + emb["wpe"][None, :T0, :]
    heads = _local_heads(cfg, tp_axis)

    def body(x, blk):
        x, (k, v) = block_prefill(blk, x, num_heads=heads, act=gelu,
                                  moe_args=cfg.moe_args, tp_axis=tp_axis)
        return x, (k, v)

    h, (ks, vs) = lax.scan(body, h, params["blocks"])
    pad = [(0, 0), (0, 0), (0, 0), (0, cache_len - T0), (0, 0)]
    return (_logits(params, h[:, -1:, :], cfg, tp_axis)[:, 0, :],
            (jnp.pad(ks, pad), jnp.pad(vs, pad)))


def gpt2_decode_step(params, tok, pos, caches, cfg: GPT2Config,
                     tp_axis: Optional[str] = None):
    """One cached decode step: tok [B] int32, pos scalar, caches
    [L, B, H, T, Dh] -> (logits [B, V], updated caches)."""
    emb = params["embedding"]
    x = (_embed_tok(emb, tok[:, None], cfg, tp_axis)
         + lax.dynamic_slice_in_dim(emb["wpe"], pos, 1, axis=0)[None])

    ks, vs = caches
    heads = _local_heads(cfg, tp_axis)

    def body(h, layer):
        blk, kc, vc = layer
        h, kc, vc = block_decode(blk, h, kc, vc, pos,
                                 num_heads=heads, act=gelu,
                                 moe_args=cfg.moe_args, tp_axis=tp_axis)
        return h, (kc, vc)

    h, (ks, vs) = lax.scan(body, x, (params["blocks"], ks, vs))
    return _logits(params, h, cfg, tp_axis)[:, 0, :], (ks, vs)


def autoregress(prefill_fn, decode_fn, input_ids, key, *,
                max_new_tokens: int, eos_token_id: Optional[int],
                temperature: float, top_k: int = 0, top_p: float = 1.0):
    """Model-agnostic jittable decode loop: ``prefill_fn(ids) ->
    (last-pos logits [B, V], caches)``; ``decode_fn(tok [B], pos,
    caches) -> (logits, caches)``. Sampling/EOS semantics shared by
    every family (GPT-2 here, Llama in models/llama_generate.py)."""
    B, T0 = input_ids.shape
    logits0, caches = prefill_fn(input_ids)

    def pick(logits, k):
        # same key on every tp rank (replicated inputs) -> same
        # sample; no cross-rank divergence to reconcile
        return sample_logits(logits, k, temperature=temperature,
                             top_k=top_k, top_p=top_p)

    def step(carry, _):
        tok, pos, caches, done, k = carry
        k, sub = jax.random.split(k)
        logits, caches = decode_fn(tok, pos, caches)
        nxt = pick(logits, sub).astype(jnp.int32)
        if eos_token_id is not None:
            nxt = jnp.where(done, eos_token_id, nxt)
            done = done | (nxt == eos_token_id)
        return (nxt, pos + 1, caches, done, k), nxt

    key0, sub0 = jax.random.split(key)
    first = pick(logits0, sub0).astype(jnp.int32)
    done0 = jnp.zeros((B,), bool)
    if eos_token_id is not None:
        done0 = first == eos_token_id
    (_, _, _, _, _), rest = lax.scan(
        step, (first, jnp.int32(T0), caches, done0, key0),
        None, length=max_new_tokens - 1)
    return jnp.concatenate(
        [input_ids, first[:, None], rest.T.astype(jnp.int32)], axis=1)


def _generate_body(params, input_ids, key, cfg: GPT2Config,
                   max_new_tokens: int, eos_token_id: Optional[int],
                   temperature: float, tp_axis: Optional[str] = None,
                   top_k: int = 0, top_p: float = 1.0):
    cache_len = input_ids.shape[1] + max_new_tokens
    return autoregress(
        lambda ids: gpt2_prefill(params, ids, cfg, cache_len=cache_len,
                                 tp_axis=tp_axis),
        lambda tok, pos, caches: gpt2_decode_step(params, tok, pos,
                                                  caches, cfg,
                                                  tp_axis=tp_axis),
        input_ids, key, max_new_tokens=max_new_tokens,
        eos_token_id=eos_token_id, temperature=temperature,
        top_k=top_k, top_p=top_p)


_generate_jit = partial(jax.jit, static_argnames=(
    "cfg", "max_new_tokens", "eos_token_id", "temperature",
    "top_k", "top_p"))(_generate_body)


def gpt2_generate(params, input_ids, cfg: GPT2Config, *,
                  max_new_tokens: int, eos_token_id: Optional[int] = None,
                  temperature: float = 0.0, top_k: int = 0,
                  top_p: float = 1.0, key=None) -> np.ndarray:
    """input_ids [B, T0] -> [B, T0 + max_new_tokens] (greedy when
    ``temperature == 0``; ``top_k``/``top_p`` filter the sampling
    distribution). One jitted program: prefill + scan decode."""
    if max_new_tokens < 1:
        return np.asarray(input_ids)
    if input_ids.shape[1] + max_new_tokens > cfg.n_positions:
        raise ValueError(
            f"prompt {input_ids.shape[1]} + max_new {max_new_tokens} "
            f"exceeds n_positions={cfg.n_positions}")
    key = key if key is not None else jax.random.key(0)
    out = _generate_jit(params, jnp.asarray(input_ids, jnp.int32), key,
                        cfg, int(max_new_tokens), eos_token_id,
                        float(temperature), top_k=int(top_k),
                        top_p=float(top_p))
    return np.asarray(out)


def beam_autoregress(prefill_fn, decode_fn, input_ids, *, beams: int,
                     vocab: int, max_new_tokens: int,
                     eos_token_id: Optional[int],
                     length_penalty: float):
    """Model-agnostic beam decode (same prefill_fn/decode_fn contract
    as :func:`autoregress`; ``vocab`` = logits width). GPT-2 wires it
    below; Llama in models/llama_generate.py."""
    B, T0 = input_ids.shape
    K = beams
    V = vocab
    neg = jnp.float32(-1e30)

    logits0, caches = prefill_fn(input_ids)
    # expand to B*K rows (beam-major inside each batch row)
    caches = jax.tree.map(
        lambda c: jnp.repeat(c, K, axis=1), caches)   # [L, B*K, H, T, Dh]
    logp0 = jax.nn.log_softmax(logits0.astype(jnp.float32), axis=-1)

    # first expansion: top-K distinct tokens seed the K beams (scoring
    # all beams from identical states would return K copies of one beam)
    s0, t0 = lax.top_k(logp0, K)                      # [B, K]
    scores = s0
    done = (jnp.zeros((B, K), bool) if eos_token_id is None
            else t0 == eos_token_id)
    toks = jnp.full((B, K, max_new_tokens), 0, jnp.int32)
    toks = toks.at[:, :, 0].set(t0)

    def step(carry, i):
        scores, done, toks, caches = carry
        tok = lax.dynamic_index_in_dim(toks, i - 1, axis=2,
                                       keepdims=False)  # [B, K]
        logits, caches = decode_fn(tok.reshape(B * K),
                                   jnp.int32(T0) + i - 1, caches)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logp = logp.reshape(B, K, V)
        if eos_token_id is not None:
            # finished beams may only re-emit EOS at zero cost, so their
            # score freezes and they stay comparable to live beams
            only_eos = jnp.full((V,), neg).at[eos_token_id].set(0.0)
            logp = jnp.where(done[:, :, None], only_eos[None, None, :],
                             logp)
        total = scores[:, :, None] + logp               # [B, K, V]
        flat_s, flat_i = lax.top_k(total.reshape(B, K * V), K)
        parent = flat_i // V                             # [B, K]
        token = (flat_i % V).astype(jnp.int32)

        # reindex beam state to the selected parents
        batch_idx = jnp.arange(B)[:, None]
        toks = toks[batch_idx, parent]                   # [B, K, T_new]
        toks = toks.at[:, :, i].set(token)
        done = done[batch_idx, parent]
        if eos_token_id is not None:
            done = done | (token == eos_token_id)
        flat_parent = (parent + jnp.arange(B)[:, None] * K).reshape(-1)
        caches = jax.tree.map(lambda c: c[:, flat_parent], caches)
        return (flat_s, done, toks, caches), None

    (scores, done, toks, _), _ = lax.scan(
        step, (scores, done, toks, caches),
        jnp.arange(1, max_new_tokens))

    # pick the best beam by length-normalised score (GNMT-style);
    # length = tokens up to and including the first EOS
    if eos_token_id is not None:
        first_eos = jnp.argmax(toks == eos_token_id, axis=2)  # 0 if none
        has_eos = jnp.any(toks == eos_token_id, axis=2)
        lengths = jnp.where(has_eos, first_eos + 1, max_new_tokens)
    else:
        lengths = jnp.full((B, K), max_new_tokens)
    norm = scores / (lengths.astype(jnp.float32) ** length_penalty)
    best = jnp.argmax(norm, axis=1)                      # [B]
    best_toks = toks[jnp.arange(B), best]                # [B, T_new]
    if eos_token_id is not None:
        # pad everything after the first EOS with EOS (same observable
        # convention as sampling/greedy decode)
        pos = jnp.arange(max_new_tokens)[None, :]
        cut = jnp.where(jnp.any(best_toks == eos_token_id, axis=1),
                        jnp.argmax(best_toks == eos_token_id, axis=1),
                        max_new_tokens)[:, None]
        best_toks = jnp.where(pos > cut, eos_token_id, best_toks)
    return jnp.concatenate([input_ids, best_toks], axis=1)


def _beam_body(params, input_ids, cfg: GPT2Config, beams: int,
               max_new_tokens: int, eos_token_id: Optional[int],
               length_penalty: float):
    cache_len = input_ids.shape[1] + max_new_tokens
    return beam_autoregress(
        lambda ids: gpt2_prefill(params, ids, cfg,
                                 cache_len=cache_len),
        lambda tok, pos, caches: gpt2_decode_step(params, tok, pos,
                                                  caches, cfg),
        input_ids, beams=beams,
        vocab=(cfg.table_vocab_size if cfg.padded_vocab_size
               else cfg.vocab_size),
        max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
        length_penalty=length_penalty)


_beam_jit = partial(jax.jit, static_argnames=(
    "cfg", "beams", "max_new_tokens", "eos_token_id",
    "length_penalty"))(_beam_body)


def gpt2_beam_search(params, input_ids, cfg: GPT2Config, *, beams: int = 4,
                     max_new_tokens: int,
                     eos_token_id: Optional[int] = None,
                     length_penalty: float = 1.0) -> np.ndarray:
    """Beam-search decode with the KV cache: [B, T0] ->
    [B, T0 + max_new_tokens], best of ``beams`` by length-normalised
    log-probability (GNMT penalty).

    One jitted program, static shapes: beams ride a B*K row dimension,
    each step re-indexes the caches to the selected parents inside the
    scan. ``beams=1`` reduces exactly to greedy decode
    (tests/test_beam.py golden). The reference has greedy only
    (utils/metrics.py:74-149).
    """
    if max_new_tokens < 1:
        return np.asarray(input_ids)
    if input_ids.shape[1] + max_new_tokens > cfg.n_positions:
        raise ValueError(
            f"prompt {input_ids.shape[1]} + max_new {max_new_tokens} "
            f"exceeds n_positions={cfg.n_positions}")
    out = _beam_jit(params, jnp.asarray(input_ids, jnp.int32), cfg,
                    int(beams), int(max_new_tokens), eos_token_id,
                    float(length_penalty))
    return np.asarray(out)


def gpt2_generate_tp(params, input_ids, cfg: GPT2Config, *, mesh,
                     tp_axis: str = "tp", max_new_tokens: int,
                     eos_token_id: Optional[int] = None,
                     temperature: float = 0.0, top_k: int = 0,
                     top_p: float = 1.0, key=None) -> np.ndarray:
    """TP-sharded generation over a live mesh.

    ``params`` must be in the tp layout (gpt2_to_tp_layout) and sharded
    per gpt2_partition_specs(cfg, tp_axis=tp_axis) — i.e. exactly the
    training layout, so a training run can evaluate generation without
    re-gathering anything. The whole prefill + decode scan runs inside
    one shard_map: head-sharded attention with a psum per cached step
    (nn/attention.py mha_decode), TP mlp, and vocab-sharded logits
    all-gathered under ``cfg.vocab_parallel``. Output tokens are
    replicated — bit-identical to single-device decode
    (tests/test_generate.py golden).

    The reference SKIPS generation eval under any parallelism
    (GPT2_Trainer.py:509-555); 124M fits one chip, but its >1-chip
    models would simply have no eval story.
    """
    if max_new_tokens < 1:
        return np.asarray(input_ids)
    if input_ids.shape[1] + max_new_tokens > cfg.n_positions:
        raise ValueError(
            f"prompt {input_ids.shape[1]} + max_new {max_new_tokens} "
            f"exceeds n_positions={cfg.n_positions}")
    key = key if key is not None else jax.random.key(0)
    fn = _tp_generate_fn(cfg, mesh, tp_axis, int(max_new_tokens),
                         eos_token_id, float(temperature), int(top_k),
                         float(top_p))
    return np.asarray(fn(params, jnp.asarray(input_ids, jnp.int32), key))


@functools.lru_cache(maxsize=32)
def _tp_generate_fn(cfg: GPT2Config, mesh, tp_axis: str,
                    max_new_tokens: int, eos_token_id: Optional[int],
                    temperature: float, top_k: int = 0,
                    top_p: float = 1.0):
    """One cached jitted shard_map program per (cfg, mesh, decode
    params) — a fresh closure per call would defeat the jit cache and
    recompile the whole prefill+decode every generation batch."""
    from jax.sharding import PartitionSpec as P

    from quintnet_tpu.core import collectives as cc
    from quintnet_tpu.models.gpt2 import gpt2_partition_specs

    specs = gpt2_partition_specs(cfg, tp_axis=tp_axis)

    def local_gen(p, ids, k):
        return _generate_body(p, ids, k, cfg, max_new_tokens,
                              eos_token_id, temperature, tp_axis=tp_axis,
                              top_k=top_k, top_p=top_p)

    return jax.jit(cc.shard_map_fn(
        local_gen, mesh,
        in_specs=(specs, P(), P()),
        out_specs=P()))
