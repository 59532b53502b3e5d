"""Single-command Llama ground-truth verifier vs HF transformers.

Builds a random-weight HF ``LlamaForCausalLM`` locally (no network),
imports its state dict through :func:`llama_from_hf_state`, and compares
logits + CLM loss between this framework and torch on the same batch —
the same oracle tests/test_llama.py pins in CI, packaged as a CLI
(reference analogue: test.py:28-113, which verifies merged GPT-2
checkpoints against HF).

  python -m quintnet_tpu.tools.verify_llama            # tiny geometry
  python -m quintnet_tpu.tools.verify_llama --rope-scaling  # llama3 rope
  python -m quintnet_tpu.tools.verify_llama --hf-dir /path/to/llama
      # a real downloaded checkpoint directory, when one is available
"""

from __future__ import annotations

import argparse
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hf-dir", default=None,
                    help="local HF Llama checkpoint dir (optional; "
                         "default builds a random tiny model)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--rope-scaling", action="store_true",
                    help="exercise llama3 rope scaling in the tiny model")
    ap.add_argument("--tol", type=float, default=2e-4)
    args = ap.parse_args()

    import numpy as np
    import torch
    import transformers

    import jax

    # ground truth is single-device CPU math — the verifier must not
    # depend on (or take) a chip
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from quintnet_tpu.models.gpt2 import clm_loss
    from quintnet_tpu.models.llama import (LlamaConfig, llama_apply,
                                           llama_from_hf_state)

    if args.hf_dir:
        hf = transformers.LlamaForCausalLM.from_pretrained(
            args.hf_dir, torch_dtype=torch.float32).eval()
        hf_cfg = hf.config
    else:
        tiny = LlamaConfig.tiny()
        scaling = ({"rope_type": "llama3", "factor": 8.0,
                    "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                    "original_max_position_embeddings": 32}
                   if args.rope_scaling else None)
        hf_cfg = transformers.LlamaConfig(
            vocab_size=tiny.vocab_size, hidden_size=tiny.dim,
            intermediate_size=tiny.intermediate_size,
            num_hidden_layers=tiny.n_layers,
            num_attention_heads=tiny.n_heads,
            num_key_value_heads=tiny.n_kv_heads,
            max_position_embeddings=max(64, args.seq + 1),
            rope_theta=tiny.rope_theta, rms_norm_eps=tiny.rms_eps,
            tie_word_embeddings=False, attention_bias=False,
            mlp_bias=False, rope_scaling=scaling)
        torch.manual_seed(0)
        hf = transformers.LlamaForCausalLM(hf_cfg).eval()

    cfg = LlamaConfig.from_hf_config(hf_cfg)
    params = llama_from_hf_state(hf.state_dict(), cfg)

    # shared seeded fixture: both frameworks must score the SAME batch
    from quintnet_tpu.tools.fixtures import random_token_ids

    ids = random_token_ids(cfg.vocab_size, args.batch, args.seq)
    with torch.no_grad():
        t = torch.from_numpy(ids).long()
        out = hf(t, labels=t)
        ref_logits = out.logits.numpy()
        ref_loss = float(out.loss)

    logits = np.asarray(llama_apply(params, jnp.asarray(ids), cfg))
    loss = float(clm_loss(jnp.asarray(logits), jnp.asarray(ids)))

    max_abs = float(np.max(np.abs(logits - ref_logits)))
    denom = float(np.max(np.abs(ref_logits))) or 1.0
    rel = max_abs / denom
    print(f"logits: max|diff| {max_abs:.3e} (rel {rel:.3e}); "
          f"loss here {loss:.6f} vs torch {ref_loss:.6f} "
          f"(diff {abs(loss - ref_loss):.2e})")
    ok = rel < args.tol and abs(loss - ref_loss) < 1e-3
    print("VERIFY", "PASS" if ok else "FAIL",
          f"(tol {args.tol}, rope_scaling="
          f"{cfg.rope_scaling is not None})")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
