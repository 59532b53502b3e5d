#!/usr/bin/env python3
"""Do the serving programs of the OLDER families trace as they did at
another commit? sha256 of the jaxpr of every program (prefill buckets,
decode, verify buckets) of a tiny engine of each family that both trees
have — GPT-2 (bf16 + speculation, int8 KV), Llama, the Mamba-2 hybrid,
the latent MoE (speculation, chunked prefill) and the window MoE — on
the CPU, in a child process a tree (``PYTHONPATH``), so that neither
tree's modules meet the other's:

    git archive <parent> | tar -x -C _parent
    python tools/program_hashes.py --against _parent

prints one JSON line: the programs compared, and those whose hash
differs (none: a change that adds a family has left the others'
programs alone; exit code 1 otherwise). Without ``--against``: this
tree's hashes, one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def hashes() -> dict:
    import hashlib

    import jax

    from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
    from quintnet_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                    granite_hybrid_init)
    from quintnet_tpu.models.laguna import LagunaConfig, laguna_init
    from quintnet_tpu.models.llama import LlamaConfig, llama_init
    from quintnet_tpu.models.pangu_moe import PanguMoEConfig, pangu_moe_init
    from quintnet_tpu.serve import (ServeEngine, SpecConfig, gpt2_family,
                                    granite_hybrid_family, laguna_family,
                                    llama_family, pangu_moe_family)

    k = jax.random.key(0)
    bf16 = dict(kv_dtype="bf16", weights_dtype="bf16")
    small = dict(max_slots=3, block_size=8, num_blocks=48)
    fine = dict(max_slots=3, block_size=4, num_blocks=96)
    chunked = dict(chunked_prefill=True, prefill_len=16)

    def engines():
        g = GPT2Config.tiny(n_positions=128, n_layer=2)
        yield "gpt2.bf16.spec", ServeEngine(
            gpt2_family(g), gpt2_init(k, g), **small, max_seq_len=96,
            **bf16, spec=SpecConfig())
        yield "gpt2.int8kv", ServeEngine(
            gpt2_family(g), gpt2_init(k, g), **small, max_seq_len=96,
            kv_dtype="int8")
        c = LlamaConfig.tiny()
        yield "llama.bf16", ServeEngine(
            llama_family(c), llama_init(k, c), **small, max_seq_len=64,
            **bf16)
        c = GraniteHybridConfig.tiny()
        yield "granite.bf16", ServeEngine(
            granite_hybrid_family(c), granite_hybrid_init(k, c), **small,
            max_seq_len=64, **bf16, prefix_cache=False)
        c = PanguMoEConfig.tiny()
        yield "pangu.bf16.spec", ServeEngine(
            pangu_moe_family(c), pangu_moe_init(k, c), **fine,
            max_seq_len=88, **bf16, spec=SpecConfig(), **chunked)
        c = LagunaConfig.tiny()
        yield "laguna.bf16", ServeEngine(
            laguna_family(c, block_size=4), laguna_init(k, c), **fine,
            max_seq_len=64, **bf16, prefix_cache=False, **chunked)

    out = {}
    for name, eng in engines():
        for sentinel, args in eng._warmup_calls():
            text = str(jax.make_jaxpr(sentinel.fn)(*args))
            out[f"{name}/{sentinel.fn.__name__}"] = hashlib.sha256(
                text.encode()).hexdigest()[:16]
    return out


def _of_tree(tree: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": tree}
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env, cwd=tree,
        check=True, capture_output=True, text=True)
    return json.loads(done.stdout.splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", default=None,
                    help="another checkout of this repository")
    args = ap.parse_args()
    if args.against is None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps(hashes()))
        return 0
    here, there = _of_tree(ROOT), _of_tree(os.path.abspath(args.against))
    differ = sorted(k for k in here.keys() | there.keys()
                    if here.get(k) != there.get(k))
    print(json.dumps({"programs": len(here), "differ": differ,
                      "hashes": here}))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
