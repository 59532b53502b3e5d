"""Names on the device trace: from an operation back to the scope of
the program that issued it.

The programs open ``jax.named_scope`` at each layer boundary (the
vocabulary is :data:`SCOPES`). A scope is metadata only: it lands in
``metadata={op_name="jit(serve_decode)/blocks/while/body/attn/qkv/
dot_general"}`` of the compiled program's instructions and changes
nothing the program computes. A device trace names an operation by its
HLO instruction (``fusion.573``), and the reader the benchmark shares
with ``tools/trace_view.py`` keeps only that name and the times — so
the way back from ``fusion.573`` to ``blocks/attn/qkv`` is a map made
from the compiled text, written beside the xplane by the process that
traced (:func:`write_scope_maps`) and read by ``trace_view --xplane``.

Nothing here imports jax: it is text in, dict out.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Iterable

# The scope vocabulary. Model programs: embed, blocks (the layer scan),
# per block attn (qkv, sdpa or paged_attn, kv_gather, kv_write, proj)
# and mlp, then final_norm, lm_head, loss, sample; a Mamba-2 block:
# mamba (in_proj, conv, ssd, state_update, gate_norm, out_proj); a
# latent-attention block: mla (q_down, q_up, kv_down, kv_write,
# kv_gather, absorb, scores, values, kv_up, proj); a dropless
# mixture: moe (router, sort, experts, shared, combine); a model of
# sliding-window and global layers opens its layer's kind (full,
# sliding) around attn (qkv, rope, kv_write, kv_gather or
# window_gather, sdpa, gate, proj); a linear-attention block: kda
# (qkv, conv, gate, delta_chunk or delta_step, state_update, norm_gate,
# proj). The
# train step:
# grads, grad_reduce, grad_clip, optimizer. ``rematted_computation`` is
# jax.checkpoint's own mark on what the backward pass recomputes.
SCOPES = frozenset({
    "embed", "blocks", "attn", "qkv", "sdpa", "paged_attn", "kv_gather",
    "kv_write", "proj", "mlp", "final_norm", "lm_head", "loss", "sample",
    "mamba", "in_proj", "conv", "ssd", "state_update", "gate_norm",
    "out_proj",
    "mla", "q_down", "q_up", "kv_down", "absorb", "scores", "values",
    "kv_up",
    "moe", "router", "sort", "experts", "shared", "combine",
    "full", "sliding", "rope", "window_gather", "gate",
    "kda", "delta_chunk", "delta_step", "norm_gate",
    "grads", "grad_reduce", "grad_clip", "optimizer",
    "rematted_computation",
})
# core/collectives.py's wrappers open ``<wrapper>_<axis>``
COLLECTIVE_SCOPES = ("all_reduce_mean_", "all_reduce_", "all_gather_",
                     "reduce_scatter_", "all_to_all_", "ppermute_shift_",
                     "broadcast_from_")

SCOPES_FILE = "qn_scopes.json"

_WRAPPED = re.compile(r"^(?:\w+\()*([^()]*)\)*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_OPERANDS = re.compile(r"\s[a-z][\w\-]*\((.*?)\)(?:, |$)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def _in_vocabulary(component: str) -> bool:
    m = _WRAPPED.match(component)
    core = m.group(1) if m else component
    return core in SCOPES or core.startswith(COLLECTIVE_SCOPES)


def scope_path(op_name: str) -> str:
    """``jit(local_step)/shard_map/grads/transpose(jvp(blocks))/while/
    body/closed_call/checkpoint/attn/proj/all_reduce_tp/psum`` ->
    ``grads/transpose(jvp(blocks))/attn/proj/all_reduce_tp``: the
    components that are scopes of the vocabulary, as JAX wrote them
    (``jvp(...)`` is the forward pass of a differentiated scope,
    ``transpose(jvp(...))`` its backward pass). ``""`` where there is
    none. XLA joins the names of operations it merged with ``;``: the
    first that carries a scope speaks for the instruction."""
    for name in op_name.split(";"):
        kept = [c for c in name.split("/")[:-1] if _in_vocabulary(c)]
        if kept:
            return "/".join(kept)
    return ""


def scope_map(hlo_text: str) -> Dict[str, str]:
    """``{HLO instruction name: scope path}`` of one compiled program
    (``jitted.lower(*args).compile().as_text()``), instructions outside
    every scope left out.

    An instruction the compiler made itself carries no metadata at all
    (the ``convert`` it split off a gather's result, a ``copy`` for a
    layout): it is given the scope of the nearest operand that has one,
    through other such instructions if need be — the cast of a gathered
    view belongs with the gather. An instruction that HAS a name of
    JAX's, but no scope in it, is outside every scope and stays out.

    An operation the compiler REWROTE carries the compiler's own name,
    with no path in it: on the TPU ``lax.ragged_dot`` becomes the
    custom calls ``op_name="ragged-dot-metadata"`` (group offsets) and
    ``op_name="ragged-dot-none"`` (the grouped matmul, most of an MoE
    program's time), and the scope JAX gave the operation is gone. Such
    an instruction inherits like a bare one, but takes its scope before
    any bare instruction can inherit THROUGH another of its kind: the
    matmul's leading operands are the bookkeeping call's results, which
    lead back to wherever the group sizes were summed, and its scope is
    that of the rows and weights it contracts."""
    direct: Dict[str, str] = {}
    bare: Dict[str, list] = {}       # no metadata -> operand names
    own: Dict[str, list] = {}        # the compiler's own name -> operands
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op_name = _OP_NAME.search(rest)
        if op_name and "/" in op_name.group(1):
            path = scope_path(op_name.group(1))
            if path:
                direct[name] = path
        else:
            args = _OPERANDS.search(" " + rest)
            (own if op_name else bare)[name] = (
                _OPERAND.findall(args.group(1)) if args else [])
    def nearest(operands: list, known: Dict[str, str], hops: int = 8) -> str:
        for operand in operands:
            path = known.get(operand, "")
            if not path and operand in bare and hops > 1:
                path = nearest(bare[operand], known, hops - 1)
            if path:
                return path
        return ""

    def settled(instructions: Dict[str, list], known: Dict[str, str]):
        return {name: path for name, operands in instructions.items()
                if name not in known
                and (path := nearest(operands, known))}

    out = dict(direct)
    out.update(settled(bare, out))
    # the rewritten ones from what has a scope so far (none of them
    # has), then the bare ones THEY feed: the copy of a grouped
    # matmul's result belongs with the matmul
    out.update(settled(own, out))
    out.update(settled(bare, out))
    return out


def module_name(hlo_text: str) -> str:
    """``jit_serve_decode`` from the text's ``HloModule`` line: the name
    the program runs under on a trace's ``XLA Modules`` line."""
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            return m.group(1)
    raise ValueError("no HloModule line: not a compiled program's text")


def write_scope_maps(trace_dir: str, hlo_texts: Iterable[str]) -> str:
    """Write ``{module name: scope_map}`` of the compiled programs to
    ``<trace_dir>/qn_scopes.json``, beside the xplane of the run that
    traced them. Returns the path."""
    path = os.path.join(trace_dir, SCOPES_FILE)
    with open(path, "w") as f:
        json.dump({module_name(t): scope_map(t) for t in hlo_texts}, f)
    return path
