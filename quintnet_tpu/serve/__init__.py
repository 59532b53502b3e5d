"""Continuous-batching inference engine with a paged KV-cache pool.

The batch decoders (models/gpt2_generate.py, models/llama_generate.py)
serve ONE request batch at a time: every prompt padded to the longest,
one dense [L, B, H, T_max, Dh] cache sized for the worst case, no way to
admit work while a batch is mid-decode. This package turns the same
TP-sharded prefill/decode kernels into an engine that sustains many
concurrent, variably-sized requests (Orca-style iteration-level
scheduling; vLLM-style paged KV blocks):

- :mod:`kv_pool` — fixed-size KV blocks per layer, refcounted
  acquire/release, per-request block tables (no per-batch T_max
  padding), and a PREFIX CACHE: a token-keyed block index (literal
  prefix bytes, not a hash digest — collisions impossible) with LRU
  retention of refcount-zero blocks and copy-on-write sharing, so
  requests with a common prompt prefix (and preemption-resumes /
  migrations) reuse resident KV instead of recomputing it;
- :mod:`kv_quant` — KV-pool LAYOUT POLICIES: f32/bf16/fp8 passthrough,
  int8 blocks with per-block-per-head absmax scales (dequantized
  inside the gathered-view attention kernels, quantized on scatter —
  the same pool bytes hold ~4x the blocks), and the fake-quant
  identity policy whose engine is bit-identical to f32 (the proof the
  scaled code path is numerically inert); also home of the shared
  :class:`~quintnet_tpu.serve.kv_quant.LayoutPolicy` protocol;
- :mod:`weight_quant` — WEIGHT layout policies on the same protocol:
  int8/fp8 per-output-channel absmax weights packed once at engine
  build and dequantized INSIDE the serving matmuls
  (nn/layers.quantized_matmul — one per-column multiply, the wide
  weight never materialized), f32/bf16 passthrough, and the same
  fake-quant bit-identity proof; the LoRA delta path stays
  full-precision on top;
- :mod:`scheduler` — waiting queue, admission by UNCACHED-block budget,
  FCFS + optional priority, preemption-by-eviction of the youngest
  request when the pool is exhausted;
- :mod:`engine` — the step loop: ONE jitted decode-step program over a
  static MAX_SLOTS batch (masked empty slots — no recompiles as
  requests come and go), bucketed chunked prefill for newly admitted
  requests (powers-of-two padded lengths, at most one compiled program
  per bucket), EOS / max-len retirement;
- :mod:`families` — the GPT-2 / Llama model adapters (thin reuse of
  nn/attention.paged_attend and the generate modules' embed/logits
  helpers);
- :mod:`adapters` — multi-tenant LoRA: an adapter registry (host-side
  LRU of safetensors adapter weights, refcount pinning) + per-slot
  packed low-rank factors so heterogeneous-adapter requests batch into
  the SAME decode step (S-LoRA/Punica style), token-identical to
  dedicated merged-weight engines;
- :mod:`longctx` — long-context serving: Sarathi-style chunked prefill
  (a prompt longer than the largest compiled bucket is admitted whole
  and streamed through the existing bucket programs under a per-step
  token budget, so concurrent decodes never starve) and the planning
  half of the ring-attention sequence-parallel prefill path (chunk K/V
  sharded over an ``sp`` mesh axis while scoring);
- :mod:`api` — blocking ``generate()`` + streaming per-token callbacks;
- :mod:`metrics` — per-step counters and TTFT / tok/s percentiles.

How fast it is on the chip: ``benchmarks/`` (the serving cells of
``BENCHMARK.json``) and ``PERF.md``.
"""

from quintnet_tpu.serve.adapters import AdapterEntry, AdapterRegistry
from quintnet_tpu.serve.api import generate, generate_stream
from quintnet_tpu.serve.engine import (ServeEngine, check_admissible)
from quintnet_tpu.serve.families import (gpt2_family,
                                          granite_hybrid_family,
                                          laguna_family,
                                          ling_hybrid_family,
                                          llama_family, pangu_moe_family)
from quintnet_tpu.serve.kv_pool import AdmitPlan, KVPool, WindowShapes
from quintnet_tpu.serve.kv_quant import (KVLayoutPolicy, LayoutPolicy,
                                         make_policy)
from quintnet_tpu.serve.weight_quant import (WeightLayoutPolicy,
                                             make_weight_policy)
from quintnet_tpu.serve.longctx import ChunkState, plan_chunks
from quintnet_tpu.serve.metrics import ServeMetrics, aggregate
from quintnet_tpu.serve.scheduler import (DeadlineExceeded, Request,
                                          RequestProgress, Scheduler)
from quintnet_tpu.serve.spec import NgramDrafter, SpecConfig

__all__ = [
    "AdapterEntry",
    "AdapterRegistry",
    "AdmitPlan",
    "WindowShapes",
    "ChunkState",
    "DeadlineExceeded",
    "KVLayoutPolicy",
    "KVPool",
    "LayoutPolicy",
    "NgramDrafter",
    "Request",
    "RequestProgress",
    "Scheduler",
    "ServeEngine",
    "ServeMetrics",
    "SpecConfig",
    "WeightLayoutPolicy",
    "aggregate",
    "check_admissible",
    "generate",
    "generate_stream",
    "gpt2_family",
    "granite_hybrid_family",
    "laguna_family",
    "ling_hybrid_family",
    "llama_family",
    "pangu_moe_family",
    "make_policy",
    "make_weight_policy",
    "plan_chunks",
]
