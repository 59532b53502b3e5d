"""Engine builders for PROCESS-fleet tests (quintnet_tpu/fleet/proc.py).

Replica processes load this module by FILE PATH (the fleet's engine
spec: ``{"file": __file__, "func": "build_tiny_gpt2", "kwargs":
{...}}``) and call the named builder — a spawn child cannot unpickle a
test's closure, and must construct its own engine anyway: that is what
guarantees every replica holds the same (family, params), the
precondition of the migration contract. Builders are DETERMINISTIC in
their kwargs (params come from ``gpt2_init(jax.random.key(seed))``),
so the parent test can build the byte-identical oracle engine/params
in its own process.
"""

import jax


def build_tiny_gpt2(*, seed: int = 0, n_layer: int = 2, max_slots: int = 2,
                    block_size: int = 4, num_blocks: int = 24,
                    max_seq_len: int = 24, temperature: float = 0.0,
                    top_k: int = 0, eos_token_id=None,
                    n_positions=None, prefill_len=None,
                    chunked_prefill: bool = False,
                    prefill_chunk_budget=None,
                    kv_dtype=None, weights_dtype=None,
                    prefix_cache: bool = True,
                    kv_tier_bytes: int = 0,
                    n_experts: int = 0, expert_top_k: int = 2,
                    expert_capacity=None):
    from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
    from quintnet_tpu.serve import ServeEngine, gpt2_family

    # n_experts > 0 makes the replica an MoE engine (dense-replicated:
    # a fleet replica process owns no ep mesh) — its routing ledger
    # rides the stats frame like every other ServeMetrics field
    cfg = GPT2Config.tiny(n_layer=n_layer, n_experts=n_experts,
                          expert_top_k=expert_top_k,
                          expert_capacity=expert_capacity,
                          **({} if n_positions is None
                             else {"n_positions": n_positions}))
    params = gpt2_init(jax.random.key(seed), cfg)
    return ServeEngine(gpt2_family(cfg), params, max_slots=max_slots,
                       block_size=block_size, num_blocks=num_blocks,
                       max_seq_len=max_seq_len, prefill_len=prefill_len,
                       chunked_prefill=chunked_prefill,
                       prefill_chunk_budget=prefill_chunk_budget,
                       kv_dtype=kv_dtype, weights_dtype=weights_dtype,
                       prefix_cache=prefix_cache,
                       temperature=temperature,
                       top_k=top_k, eos_token_id=eos_token_id,
                       kv_tier_bytes=kv_tier_bytes)
