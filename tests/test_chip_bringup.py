"""What the TPU's compiler and the chip's entry script must keep doing,
checked without a chip (PR 21, the bring-up round).

1. The main-path KERNELS compile for a described ``v5e:2x2`` topology
   at GPT-2 124M's real widths — the on-chip-measurement guide's third
   rehearsal, kept as tests: ``pallas_flash_attention`` forward +
   backward at seq 4096 with and without ``segment_ids``, and
   ``paged_attention(interpret=False)`` at decode / verify / prefill
   shapes for 12 heads x Dh 64 x table width 64 (1024 positions),
   passthrough and int8-scaled; and (PR 27) the decode program of the
   recurrent family at granite-4.0-h-micro's published widths, whose
   plan must not hold a second copy of the per-slot state; and (PR 28)
   the decode and prefill programs at GPT-2 XL's widths, which must
   take the KV pool row-major and hold no copy of it or of a layer's
   slice — nor (PR 30) a decode program of a gathered view; and (PR
   31) the latent family's decode program at openPangu-Ultra-MoE's
   widths: one row-major latent pool, (PR 36) walked in place by one
   kernel call a layer loop with no gathered view, the experts as
   (PR 38) the grouped-matmul kernel that reads touched experts only.
   Both window families' decode programs walk each row's ring of the
   window store in place and slice none of it out.
   The parent
   commit's paged kernel was
   REFUSED at every one of these shapes (16 MiB default scoped-VMEM
   budget; a (1, Hkv) scale block; a lane-splitting reshape) —
   interpret mode cannot see any of that. Nothing runs: a compile that
   passes is not a chip run.
2. ``core/runtime.enable_compilation_cache`` is placed from outside.
3. ``chip_smoke.py`` refuses to print its success line off-TPU, and
   its train / serve phases run to their end at a tiny size on the CPU
   (the first rehearsal), called as functions — ``main`` is never
   reached, so no success line can appear for a non-TPU platform.
"""

import importlib
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# GPT-2 124M serving geometry: 12 heads x Dh 64, 16-token blocks, table
# width 64 = 1024 positions, 8 decode rows
H, D, BS, M, NB, ROWS = 12, 64, 16, 64, 512, 8


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e:2x2 to compile for, with the
    persistent compile cache off around the compiles — a topology
    compile is written to the cache but cannot be read back without a
    chip, so the next one would warn and compile again."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, no description
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One chip of it."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _kernels_compile_for_the_chip(request, monkeypatch):
    """A test that compiles for the described chip gets the KERNELS the
    chip would run: the session's interpreter switch (conftest.py turns
    it on for the CPU) is off for it, so a program that chooses its
    attention at lowering (nn/attention._paged_attend_walk) lowers the
    per-row walk's Mosaic kernel, not its emulation."""
    if {"chip", "topo"} & set(request.fixturenames):
        monkeypatch.setattr(
            importlib.import_module("quintnet_tpu.ops.paged_attention"),
            "INTERPRET", False)


def _assert_experts_run_the_kernel(hlo: str, *, bodies: int):
    """The dropless mixture's grouped matmuls in a program compiled for
    the chip (PR 38): ``bodies`` sparse-layer bodies (a scan's body
    once), each with the three calls of ops/grouped_matmul's kernel —
    gate, up, down — and no ``ragged-dot`` custom call left, whose
    padded ``layers * held`` groups read at 34-39% of the touched
    experts' bytes' roofline. The kernel's calls carry JAX's own name,
    so the scope map reads them ``blocks/moe/experts`` (obs/scopes.py;
    ``ragged-dot-none``'s own-name rule stays for programs that keep
    it)."""
    import re

    from quintnet_tpu.obs.scopes import scope_map

    assert "ragged-dot" not in hlo
    calls = re.findall(r"%(grouped_matmul[.\w]*) = ", hlo)
    assert len(calls) == 3 * bodies, calls
    scopes = scope_map(hlo)
    assert {scopes.get(c) for c in calls} == {"blocks/moe/experts"}, [
        (c, scopes.get(c)) for c in calls]


def _bf16_param_shapes(fam, init, cfg, chip):
    """The SHAPES of a family's parameters as a bf16 engine serves them
    (``jax.eval_shape`` of its initialiser through the weight policy:
    gigabytes of weights are never built), placed on the described
    chip."""
    from quintnet_tpu.serve.weight_quant import (make_weight_policy,
                                                 present_targets,
                                                 quantize_params)

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(lambda k: (lambda p: quantize_params(
            p, present_targets(p, fam.weight_targets),
            make_weight_policy("bf16")))(init(k, cfg)), jax.random.key(0)))


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# ---------------------------------------------------------------------
# 1-2: flash attention, forward + backward, seq 4096
# ---------------------------------------------------------------------
@pytest.mark.parametrize("segments", [False, True],
                         ids=["causal", "causal+segments"])
def test_flash_fwd_bwd_compiles_for_v5e(chip, segments):
    from quintnet_tpu.nn.attention import STREAMED_TILE
    from quintnet_tpu.ops import pallas_flash_attention

    S = 4096
    qkv = jax.ShapeDtypeStruct((1, H, S, D), jnp.bfloat16, sharding=chip)
    seg = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=chip)

    def loss(q, k, v, s=None):
        o = pallas_flash_attention(q, k, v, True, STREAMED_TILE,
                                   STREAMED_TILE, segment_ids=s)
        return jnp.sum(o.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    *((qkv, qkv, qkv, seg) if segments
                      else (qkv, qkv, qkv)))
    assert text.count("tpu_custom_call") >= 3     # fwd, dkv, dq


# ---------------------------------------------------------------------
# PR 32: the resident geometry at the training cells' local shapes, and
# the cells' own train steps with it inside
# ---------------------------------------------------------------------
@pytest.mark.parametrize("shape,segments", [
    ((32, 12, 1024, 64), False),      # gpt2-124m.train-packed-s1024
    ((8, 10, 1024, 64), False),       # gpt2-large.train-dp2tp2-s1024, local
    ((8, 10, 1024, 64), True),
    ((4, 12, 2048, 64), False),       # RESIDENT_MAX_SEQ
    ((4, 6, 1024, 128), False),       # the other head width the chooser admits
], ids=["124m", "large-local", "large-local+segments", "s2048", "dh128"])
def test_local_attention_is_the_resident_kernel_on_v5e(chip, shape, segments):
    """``local_attention`` itself, from a CPU process: the lowering for
    the described chip keeps the kernel branch (forward + one backward
    pass), a lowering for the CPU the ``sdpa`` branch."""
    from quintnet_tpu.nn.attention import local_attention

    b, _, s, _ = shape
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)
    seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=chip)

    def loss(q, k, v, ids=None):
        o = local_attention(q, k, v, causal=True, segment_ids=ids)
        return jnp.sum(o.astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))
    args = (qkv, qkv, qkv, seg) if segments else (qkv, qkv, qkv)
    text = _compile(grad, *args)
    assert text.count("tpu_custom_call") == 2     # fwd, bwd
    assert f"{s},{s}]" not in text                # no [.., S, S] buffer
    if shape[0] == 4:                             # a CPU lowering, small
        cpu = jax.jit(grad).lower(*(jnp.zeros(a.shape, a.dtype)
                                    for a in args)).as_text()
        assert "tpu_custom_call" not in cpu


@pytest.mark.parametrize("workload,calls", [
    ("gpt2-124m.train-packed-s1024", 3),
    ("gpt2-large.train-dp2tp2-s1024", 3),
])
def test_train_cells_plan_no_score_tensor_on_v5e(topo, workload, calls):
    """The cells' own jitted step (``benchmarks/drivers/train.build``,
    as ``benchmarks/tools/aot_sizes.py`` compiles it) for the described
    chip(s): a layer body holds the kernel three times (forward, and
    under remat forward again + backward), no ``[B, H, 1024, 1024]``
    scores or probabilities anywhere, and — on the mesh — the kernel
    traces inside ``shard_map`` with the tp all-reduces still there."""
    from jax.sharding import NamedSharding

    from quintnet_tpu.parallel.train_step import opt_state_specs

    from benchmarks.lib import harness

    bench = harness.Bench(REPO)
    cell = bench.cell(workload)
    t = cell.spec["trainer"]
    n_dev = math.prod(t["mesh_dim"])
    _, model, strategy, trainer = bench.driver("train").build(
        cell.spec, cell.config, 0, devices=topo.devices[:n_dev])
    mesh = strategy.mesh
    p_specs = strategy.param_specs(model)
    params = jax.eval_shape(
        lambda k: model.to_tp_layout(model.init(k), mesh.shape.get("tp", 1)),
        jax.random.key(0))
    opt = jax.eval_shape(trainer.optimizer.init, params)
    o_specs = opt_state_specs(trainer.optimizer, params, p_specs)
    ids = jax.ShapeDtypeStruct((int(t["batch"]), 1024), jnp.int32)

    def described(tree, specs):
        return jax.tree.map(
            lambda x, sp: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, sp)),
            tree, specs)

    step = trainer.step_fn.fn
    compiled = jax.jit(lambda p, o, b: step(p, o, b, 0),
                       donate_argnums=(0, 1)).lower(
        described(params, p_specs), described(opt, o_specs),
        described((ids, ids), strategy.batch_partition_specs(model))
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == calls
    assert "1024,1024]" not in text
    if n_dev > 1:
        assert "all-reduce" in text


# ---------------------------------------------------------------------
# 3-8: the paged kernel at GPT-2 124M widths, 1024 positions
# ---------------------------------------------------------------------
@pytest.mark.parametrize("scaled", [False, True],
                         ids=["bf16-passthrough", "int8-scaled"])
@pytest.mark.parametrize("rows,queries", [(ROWS, 1), (ROWS, 5), (1, 128)],
                         ids=["decode", "verify", "prefill"])
def test_paged_attention_compiles_for_v5e(chip, rows, queries, scaled):
    pa = importlib.import_module("quintnet_tpu.ops.paged_attention")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    q = sds((rows, H, queries, D), jnp.float32)
    tables, starts = sds((rows, M), jnp.int32), sds((rows,), jnp.int32)
    if scaled:
        pool = sds((NB * BS, H, D), jnp.int8)
        scale = sds((NB, H), jnp.float32)

        def fn(q, k, v, t, s, ks, vs, fk, fv):
            return pa.paged_attention(
                q, k, v, t, s, block_size=BS, kv_scales=(ks, vs),
                fresh_kv=(fk, fv), interpret=False)

        _compile(fn, q, pool, pool, tables, starts, scale, scale, q, q)
    else:
        pool = sds((NB * BS, H, D), jnp.bfloat16)

        def fn(q, k, v, t, s):
            return pa.paged_attention(q, k, v, t, s, block_size=BS,
                                      interpret=False)

        _compile(fn, q, pool, pool, tables, starts)
    # the limit handed to the compiler is the kernel's own estimate,
    # inside the cap
    need = pa.paged_attention_vmem_bytes(
        n_q_heads=H, n_kv_heads=H, n_queries=queries, head_dim=D,
        block_size=BS, table_width=M, pool_dtype=pool.dtype,
        scaled=scaled)
    assert 16 * 2 ** 20 < need <= pa.VMEM_CAP_BYTES


# ---------------------------------------------------------------------
# PR 34: the per-row walk at the serving cells' own decode shapes
# ---------------------------------------------------------------------
@pytest.mark.parametrize("rows,queries,lanes,layers,blocks,table,dh,kept", [
    (12, 32, 1664, 48, 384, 64, 64, None),      # gpt2-xl.serve-chat-sat
    (12, 128, 1664, 48, 384, 64, 64, None),     # a verify run of four drafts
    (64, 32, 512, 4, 2048, 64, 64, None),       # granite-4.0-h-micro
    (48, 48, 1024, 2, 22528, 1088, 128, None),  # laguna-xs.2.serve-code-sat
    # openpangu-ultra-moe-718b.serve-doc-sat (PR 36): ONE pool, 128
    # heads a token on a 576-of-640-lane latent row, 512 lanes kept
    (64, 128, 640, 5, 7680, 320, 576, 512),
    (64, 640, 640, 5, 7680, 320, 576, 512),     # four drafts: 5 x 128 rows
], ids=["xl-decode", "xl-verify", "hybrid-decode", "window-decode",
        "latent-decode", "latent-verify"])
def test_paged_walk_compiles_for_v5e(chip, rows, queries, lanes, layers,
                                     blocks, table, dh, kept):
    """``paged_walk_attention`` takes the WHOLE pool of each serving
    cell (1.5 GB a buffer in the window cell) as an HBM operand, its
    block table (52,224 entries there) as a scalar prefetch, and fits
    its double buffer of key blocks in VMEM, at the engine's key
    block. ``kept``: a latent family's call — no v pool, the values'
    product against the key block's first ``kept`` lanes."""
    from quintnet_tpu.nn.attention import WALK_KEY_BLOCK

    pa = importlib.import_module("quintnet_tpu.ops.paged_attention")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pool = sds((layers, blocks * BS, lanes), jnp.bfloat16)
    pools = (pool, pool) if kept is None else (pool,)

    def fn(qd, qpos, layer, tables, k, v=None):
        return pa.paged_walk_attention(
            qd, qpos, k, v, layer, tables, block_size=BS,
            key_block=WALK_KEY_BLOCK, head_dim=dh, kept_lanes=kept,
            scale=None if kept is None else dh ** -0.5, interpret=False)

    text = _compile(fn, sds((rows, queries, lanes), jnp.bfloat16),
                    sds((rows, queries), jnp.int32), sds((), jnp.int32),
                    sds((rows, table), jnp.int32), *pools)
    # the pool goes in as it is: no copy, slice or re-layout of it
    assert not [ln for ln in text.splitlines()
                if f"bf16[{layers},{blocks * BS},{lanes}]" in ln
                and (" copy(" in ln or " transpose(" in ln)]


@pytest.mark.parametrize("slots,rows,lanes,ring,window", [
    (128, 32, 512, 4112, 4096),     # smallthinker: 7 heads x 4 kv heads
    (48, 64, 1024, 528, 512),       # laguna-xs.2's sliding layers: 8 x 8
], ids=["wide-window-decode", "window-decode"])
def test_ring_walk_compiles_for_v5e(chip, slots, rows, lanes, ring, window):
    """``paged_walk_attention`` with a ``window``: each row walks
    its own ring of the cell's whole window store ``[3, (slots + 1) x
    ring, F]`` (1.1 GB a buffer in the wide-window cell), addressed
    through a table made from an iota on the device, the rows' last
    positions a fifth scalar prefetch and the ring mask in the kernel, at
    the key block the ring's width gives (:func:`_ring_key_block`: 528
    positions, eight trips over 4,112 rows and one over 528)."""
    from quintnet_tpu.nn.attention import _ring_key_block

    pa = importlib.import_module("quintnet_tpu.ops.paged_attention")
    pages = ring // BS

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    store = sds((3, (slots + 1) * ring, lanes), jnp.bfloat16)

    def fn(qd, qpos, layer, wk, wv):
        tables = (jnp.arange(slots)[:, None] * pages
                  + jnp.arange(pages)[None, :])
        return pa.paged_walk_attention(
            qd, qpos, wk, wv, layer, tables, block_size=BS,
            key_block=_ring_key_block(pages, BS), head_dim=128,
            window=window, interpret=False)

    text = _compile(fn, sds((slots, rows, lanes), jnp.bfloat16),
                    sds((slots, rows), jnp.int32), sds((), jnp.int32),
                    store, store)
    assert not [ln for ln in text.splitlines()
                if f"bf16[3,{(slots + 1) * ring},{lanes}]" in ln
                and (" copy(" in ln or " transpose(" in ln)]


# ---------------------------------------------------------------------
# PR 38: the grouped-matmul kernel at the three MoE cells' own shapes,
# and a prefill bucket of each family with it inside
# ---------------------------------------------------------------------
# cell -> (sparse layers, experts held, d, f, decode rows = slots x 8)
GMM_CELLS = {"ling": (4, 128, 2560, 768, 1536),
             "pangu": (4, 16, 7680, 2048, 512),
             "laguna": (4, 256, 2048, 512, 384)}


@pytest.mark.parametrize("side", ["up", "down"])
@pytest.mark.parametrize("program", ["decode", "b1024"])
@pytest.mark.parametrize("cell", sorted(GMM_CELLS))
def test_grouped_matmul_compiles_for_v5e(chip, cell, program, side):
    """ops/grouped_matmul at a sparse layer's call as the cell's decode
    program and a 1,024-token chunk make it — a stack of ``layers x
    held`` bf16 experts read at a traced layer, ``[d, f]`` (gate, up)
    and ``[f, d]`` (down), the tiles the module chooses from the shapes
    (openPangu's 31.5-MB expert in two column blocks) — compiled for
    the described chip with the metadata's arithmetic beside it, and
    planning no temporary at a weight's width."""
    from quintnet_tpu.ops import grouped_matmul as gm

    L, G, d, f, decode_rows = GMM_CELLS[cell]
    rows = decode_rows if program == "decode" else 8192
    K, N = (d, f) if side == "up" else (f, d)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def call(x, w, sizes, layer):
        visits = gm.group_visits(
            sizes, rows=rows, row_tile=gm.row_tile_for(rows, x.dtype))
        return gm.grouped_matmul(x, w, visits, layer=layer)

    compiled = jax.jit(call).lower(
        sds((rows, K), jnp.bfloat16), sds((L, G, K, N), jnp.bfloat16),
        sds((G,), jnp.int32), sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < K * N * 2 / 4


def _moe_prefill(name, chip):
    """(``prefill_from`` as a function of shapes only, its shapes,
    sparse-layer bodies) of one MoE family at its cell's published
    widths, for a 1,024-token chunk of a 2,048-position row."""

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def config(file):
        with open(os.path.join(REPO, "benchmarks", "configs", file)) as f:
            return json.load(f)

    slots, bs, width = 4, 16, 128
    if name == "pangu":
        from quintnet_tpu.models.pangu_moe import (PanguMoEConfig,
                                                   pangu_moe_init as init)
        from quintnet_tpu.serve import pangu_moe_family as family

        cfg = PanguMoEConfig.from_dict({
            **config("openpangu-ultra-moe-718b.json"),
            "num_hidden_layers": 2})
        bodies = 1
    elif name == "laguna":
        from quintnet_tpu.models.laguna import (LagunaConfig,
                                                laguna_init as init)
        from quintnet_tpu.serve import laguna_family as family

        cfg = LagunaConfig.from_dict(config("laguna-xs.2.json"))
        bodies = 2
    else:
        from quintnet_tpu.models.ling_hybrid import (
            LingHybridConfig, ling_hybrid_init as init)
        from quintnet_tpu.serve import ling_hybrid_family as family

        cfg = LingHybridConfig.from_dict(config("ling-3.0-flash.json"))
        bodies = 2
    fam = family(cfg)
    params = _bf16_param_shapes(fam, init, cfg, chip)
    lanes = 1024 if name == "laguna" else 640
    pool = sds((fam.n_layers, slots * width * bs, lanes), jnp.bfloat16)
    chunk = (sds((1, 1024), jnp.int32), sds((), jnp.int32),
             sds((), jnp.int32), sds((width,), jnp.int32))
    slot = sds((), jnp.int32)
    if name == "pangu":
        def prefill(params, k, ids, start, t0, row):
            return fam.prefill_from(params, k, None, ids, start, t0, row, bs)
        return prefill, (params, pool, *chunk), bodies
    if name == "laguna":
        store = sds((fam.window.n_layers, (slots + 1) * fam.window.ring,
                     1024), jnp.bfloat16)

        def prefill(params, k, v, wk, wv, ids, start, t0, row, slot):
            return fam.prefill_from(params, k, v, ids, start, t0, row, bs,
                                    window=(wk, wv), slot=slot)
        return prefill, (params, pool, pool, store, store, *chunk,
                         slot), bodies
    ssm = sds((fam.state.n_layers, slots + 1, *fam.state.ssm), jnp.float32)
    conv = sds((fam.state.n_layers, slots + 1, *fam.state.conv),
               jnp.bfloat16)

    def prefill(params, k, ssm, conv, ids, start, t0, row, slot):
        return fam.prefill_from(params, k, None, ids, start, t0, row, bs,
                                state=(ssm, conv), slot=slot)
    return prefill, (params, pool, ssm, conv, *chunk, slot), bodies


@pytest.mark.parametrize("family", ["laguna", "ling", "pangu"])
def test_moe_prefill_bucket_runs_the_kernel_where_it_lowers(chip, family):
    """The 1,024-token prefill bucket of each MoE family at its cell's
    published widths, traced ONCE in this CPU process: lowered for the
    described chip it holds the grouped-matmul kernel's calls — three a
    sparse-layer body, 8,192 sorted routings each — and no
    ``ragged_dot``; the same function lowered for the CPU this process
    has holds no TPU custom call at all (the choice is made where the
    program is lowered, nn/moe._expert_rows; the decode programs: the
    three ``*_on_v5e`` tests below, compiled)."""
    prefill, shapes, bodies = _moe_prefill(family, chip)
    for_chip = jax.jit(prefill).lower(*shapes).as_text()
    assert "ragged_dot" not in for_chip
    assert for_chip.count('kernel_name = "grouped_matmul"') == 3 * bodies
    here = jax.jit(prefill).lower(*jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), shapes)).as_text()
    assert "tpu_custom_call" not in here


# ---------------------------------------------------------------------
# the compile cache is placed from outside
# ---------------------------------------------------------------------
@pytest.fixture
def cache_config():
    """Put the three cache options back after a test moved them."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)


# ---------------------------------------------------------------------
# the recurrent family's decode step updates its state in place
# ---------------------------------------------------------------------
def test_hybrid_decode_holds_one_copy_of_the_state_on_v5e(chip):
    """``granite_hybrid_family(...).decode`` at the published widths
    (shapes only: ``jax.eval_shape``), the serving cell's 64 slots,
    pools and state donated, compiled for the described chip. Stacked
    as a scan's ys the state would be planned twice (4.6 GiB each
    here); carried and updated in place, the temporaries stay under a
    third of it. (64 slots, not fewer: a pool of a few tens of MB the
    compiler moves into VMEM whole around the layer's scatter and the
    per-row walk, PR 34 — a copy no deployment's pool can get.)"""
    import numpy as np

    from quintnet_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                    granite_hybrid_init)
    from quintnet_tpu.serve import granite_hybrid_family
    from quintnet_tpu.serve.kv_pool import feature_width
    from quintnet_tpu.serve.kv_quant import make_policy

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = GraniteHybridConfig.from_dict(json.load(f))
    fam = granite_hybrid_family(cfg)
    slots, bs, width = 64, 16, 64
    policy = make_policy("bf16")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = _bf16_param_shapes(fam, granite_hybrid_init, cfg, chip)
    pool = sds((fam.n_layers, 32 * slots * bs,
                feature_width(fam.n_kv_heads, fam.head_dim)), jnp.bfloat16)
    ssm = sds((fam.state.n_layers, slots + 1, *fam.state.ssm), jnp.float32)
    conv = sds((fam.state.n_layers, slots + 1, *fam.state.conv),
               jnp.bfloat16)
    rows = sds((slots,), jnp.int32)

    def decode(params, k, v, ssm, conv, tok, pos, tables):
        return fam.decode(params, k, v, tok, pos, tables, bs,
                          policy=policy, state=(ssm, conv))

    compiled = jax.jit(decode, donate_argnums=(1, 2, 3, 4)).lower(
        params, pool, pool, ssm, conv, rows, rows,
        sds((slots, width), jnp.int32)).compile()
    plan = compiled.memory_analysis()
    state_bytes = int(np.prod(ssm.shape)) * 4 + int(np.prod(conv.shape)) * 2
    assert plan.alias_size_in_bytes >= state_bytes     # in and out alias
    assert plan.temp_size_in_bytes < state_bytes / 3, (
        plan.temp_size_in_bytes, state_bytes)
    _assert_pool_row_major_and_uncopied(compiled.as_text(), pool,
                                        view=(slots, width, bs), walks=1)


def test_latent_decode_walks_the_one_pool_in_place_on_v5e(chip):
    """``pangu_moe_family(...).decode`` at openPangu-Ultra-MoE's
    published widths (shapes only: ``jax.eval_shape``), the cell's one
    dense and one of its four MoE layers, 8 slots of 5,120 positions,
    the latent pool donated, compiled for the described chip. The pool
    ``[L, slots, 640]`` (576 features padded to five lane rows) must
    enter row-major and alias out; each of the two layer loops holds
    ONE call of the per-row walk (PR 36: the absorbed form reads each
    row's live blocks of the one pool in place), fed by the pool itself
    — the loop's carry through the in-place ``kv_write`` scatter — so
    no copy of it, of a layer's slice or of a gathered view may be
    planned, and no gather at the table's width is left; and the
    experts must run as the grouped-matmul KERNEL (PR 38), the stack
    indexed in place, with no ``ragged-dot`` left."""
    import numpy as np

    from quintnet_tpu.models.pangu_moe import PanguMoEConfig, pangu_moe_init
    from quintnet_tpu.serve import pangu_moe_family
    from quintnet_tpu.serve.kv_pool import feature_width

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "openpangu-ultra-moe-718b.json")) as f:
        cfg = PanguMoEConfig.from_dict({**json.load(f),
                                        "num_hidden_layers": 2})
    fam = pangu_moe_family(cfg)
    slots, bs, width = 8, 16, 320

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = _bf16_param_shapes(fam, pangu_moe_init, cfg, chip)
    assert feature_width(1, fam.latent) == 640
    pool = sds((fam.n_layers, slots * width * bs, 640), jnp.bfloat16)
    rows = sds((slots,), jnp.int32)

    def decode(params, k, tok, pos, tables):
        return fam.decode(params, k, None, tok, pos, tables, bs)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, pool, rows, rows, sds((slots, width), jnp.int32)).compile()
    plan = compiled.memory_analysis()
    pool_bytes = int(np.prod(pool.shape)) * 2
    assert plan.alias_size_in_bytes >= pool_bytes      # in and out alias
    hlo = compiled.as_text()
    _assert_experts_run_the_kernel(hlo, bodies=1)
    spec = importlib.util.spec_from_file_location(
        "pool_layout_audit", os.path.join(REPO, "tools",
                                          "pool_layout_audit.py"))
    audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(audit)
    view_bytes = slots * width * bs * 640 * 2
    got = audit.read_hlo(hlo, {"k": pool.shape}, view_bytes)
    assert got["entry_layouts"] == {"k": ["{2,1,0}"]}, got
    assert len(got["row_walks"]) == 2, got["row_walks"]
    for call in got["row_walks"]:
        assert len(call["pool_operands"]) == 1, call
        assert call["copies_beside"] == [], call
    assert f"[{slots},{width},{bs}," not in hlo.replace(" ", "")
    assert f"[{slots},{width * bs}," not in hlo.replace(" ", "")
    for c in got["big_copies"]:
        dims = tuple(c["dims"])
        assert dims not in {tuple(pool.shape), tuple(pool.shape[1:]),
                            (1, *pool.shape[1:])}, c
        assert dims[:3] != (slots, width, bs), c
        assert dims[:2] != (slots, width * bs), c


def test_kda_latent_decode_holds_one_state_and_walks_the_pool_on_v5e(chip):
    """``ling_hybrid_family(...).decode`` at Ling-3.0-flash's published
    widths (shapes only: ``jax.eval_shape``), the benchmark's six
    layers, 64 slots of 12,288 positions, the latent pool and both
    state buffers donated, compiled for the described chip. The state
    ``[5, 65, 32, 128, 128]`` f32 (1.3 GiB) must alias in and out and be
    planned ONCE (sliced in and stacked out of a scan it would be there
    twice); the latent pool ``[1, slots, 640]`` must enter row-major,
    alias out and feed ONE call of the per-row walk through the
    in-place ``kv_write`` scatter, with no gather at the table's width;
    and the experts must run as the grouped-matmul kernel (PR 38: the
    three sparse KDA layers' scan body and the latent layer's)."""
    import numpy as np

    from quintnet_tpu.models.ling_hybrid import (LingHybridConfig,
                                                 ling_hybrid_init)
    from quintnet_tpu.serve import ling_hybrid_family
    from quintnet_tpu.serve.kv_pool import feature_width

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ling-3.0-flash.json")) as f:
        cfg = LingHybridConfig.from_dict(json.load(f))
    fam = ling_hybrid_family(cfg)
    slots, bs, width = 64, 16, 768

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = _bf16_param_shapes(fam, ling_hybrid_init, cfg, chip)
    assert feature_width(1, fam.latent) == 640
    pool = sds((fam.n_layers, slots * width * bs, 640), jnp.bfloat16)
    ssm = sds((fam.state.n_layers, slots + 1, *fam.state.ssm), jnp.float32)
    conv = sds((fam.state.n_layers, slots + 1, *fam.state.conv),
               jnp.bfloat16)
    rows = sds((slots,), jnp.int32)

    def decode(params, k, ssm, conv, tok, pos, tables):
        return fam.decode(params, k, None, tok, pos, tables, bs,
                          state=(ssm, conv))

    compiled = jax.jit(decode, donate_argnums=(1, 2, 3)).lower(
        params, pool, ssm, conv, rows, rows,
        sds((slots, width), jnp.int32)).compile()
    plan = compiled.memory_analysis()
    pool_bytes = int(np.prod(pool.shape)) * 2
    state_bytes = int(np.prod(ssm.shape)) * 4 + int(np.prod(conv.shape)) * 2
    assert plan.alias_size_in_bytes >= pool_bytes + state_bytes
    assert plan.temp_size_in_bytes < state_bytes / 3, (
        plan.temp_size_in_bytes, state_bytes)
    hlo = compiled.as_text()
    _assert_experts_run_the_kernel(hlo, bodies=2)
    spec = importlib.util.spec_from_file_location(
        "pool_layout_audit", os.path.join(REPO, "tools",
                                          "pool_layout_audit.py"))
    audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(audit)
    got = audit.read_hlo(hlo, {"k": pool.shape}, slots * width * bs * 640 * 2)
    assert got["entry_layouts"] == {"k": ["{2,1,0}"]}, got
    assert len(got["row_walks"]) == 1, got["row_walks"]
    for call in got["row_walks"]:
        assert len(call["pool_operands"]) == 1, call
        # a ONE-layer pool reaches the kernel through a bitcast of the
        # scatter's result (a re-reading of the same bytes): no copy
        assert all(c["op"] == "bitcast" for c in call["copies_beside"]), call
    assert f"[{slots},{width},{bs}," not in hlo.replace(" ", "")
    assert f"[{slots},{width * bs}," not in hlo.replace(" ", "")
    for c in got["big_copies"]:
        assert tuple(c["dims"]) not in {
            tuple(pool.shape), tuple(pool.shape[1:]), (1, *pool.shape[1:]),
            tuple(ssm.shape), tuple(ssm.shape[1:])}, c


def test_window_decode_reads_rings_and_contracts_as_stored_on_v5e(chip):
    """``laguna_family(...).decode`` at Laguna-XS.2's published widths
    (shapes only: ``jax.eval_shape``), the benchmark's five layers — a
    global dense one, three sliding sparse ones, a global sparse one —
    8 slots of 17,408 positions, all four cache buffers donated,
    compiled for the described chip. The block pool ``[2, slots, 1024]``
    and the window store ``[3, 9 x 528, 1024]`` must enter row-major
    and alias out; no copy of either, of a gathered view or of a ring
    cut into heads may be planned (48 and 64 query heads both contract
    the cached rows as stored); and the 256 experts of four layers must
    run as the grouped-matmul kernel (PR 38: the sliding run's scan
    body and the global sparse layer's). Nothing is sliced out of the
    window store either (a slice of every slot's k and v rings, which
    the compiler materialises as a fusion before the scores): each row
    walks its own ring in place, one walk in the sliding run's body
    beside the two global layers'."""
    import numpy as np

    from quintnet_tpu.models.laguna import LagunaConfig, laguna_init
    from quintnet_tpu.serve import laguna_family

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-xs.2.json")) as f:
        cfg = LagunaConfig.from_dict(json.load(f))
    fam = laguna_family(cfg)
    slots, bs, width, ring = 8, 16, 1088, fam.window.ring

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = _bf16_param_shapes(fam, laguna_init, cfg, chip)
    pool = sds((fam.n_layers, slots * width * bs, 1024), jnp.bfloat16)
    store = sds((fam.window.n_layers, (slots + 1) * ring, 1024),
                jnp.bfloat16)
    rows = sds((slots,), jnp.int32)

    def decode(params, k, v, wk, wv, tok, pos, tables):
        return fam.decode(params, k, v, tok, pos, tables, bs,
                          window=(wk, wv))

    compiled = jax.jit(decode, donate_argnums=(1, 2, 3, 4)).lower(
        params, pool, pool, store, store, rows, rows,
        sds((slots, width), jnp.int32)).compile()
    plan = compiled.memory_analysis()
    cache_bytes = 2 * 2 * (int(np.prod(pool.shape))
                           + int(np.prod(store.shape)))
    assert plan.alias_size_in_bytes >= cache_bytes     # in and out alias
    hlo = compiled.as_text()
    _assert_experts_run_the_kernel(hlo, bodies=2)
    _assert_pool_row_major_and_uncopied(hlo, pool, view=(slots, width, bs),
                                        walks=2, ring_walks=1)
    _assert_rings_walked_in_place(hlo, store, slots=slots, heads=8)


def test_wide_window_decode_reads_rings_and_runs_the_kernel_on_v5e(chip):
    """``smallthinker_family(...).decode`` at SmallThinker-21BA3B's
    published widths (shapes only), the cell's four layers — a NoPE
    global one, three sliding ones with rings of 4,112 rows — 8 slots of
    16,384 positions, all four cache buffers donated, compiled for the
    described chip: the caches alias out; no copy of the window store
    or of a ring cut into heads is planned (7 query heads a kv head
    contract the rows as stored); the global layer walks its pool (one
    walk); and the 64 ReGLU experts of both runs are the grouped-matmul
    kernel, read under each run's scope. Nothing is sliced out of the
    window store: ``window_gather``'s slice of every slot's k and v
    rings of a layer, idle slots' too, would be materialised before the
    scores — two ``constant_dynamic-slice_fusion`` copies of 539 MB a
    layer at the cell's 128 slots, about 10 of a 24.7-ms decode step on
    one v5e chip — where each row walks its own ring's live rows in
    place, one walk in the sliding run's body."""
    import re

    import numpy as np

    from quintnet_tpu.models.smallthinker import (SmallThinkerConfig,
                                                  smallthinker_init)
    from quintnet_tpu.obs.scopes import scope_map
    from quintnet_tpu.serve import smallthinker_family

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "smallthinker-21ba3b-instruct.json")) as f:
        cfg = SmallThinkerConfig.from_dict(json.load(f))
    fam = smallthinker_family(cfg)
    slots, bs, width, ring = 8, 16, 1024, fam.window.ring
    assert ring == 4112

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = _bf16_param_shapes(fam, smallthinker_init, cfg, chip)
    pool = sds((fam.n_layers, slots * width * bs, 512), jnp.bfloat16)
    store = sds((fam.window.n_layers, (slots + 1) * ring, 512),
                jnp.bfloat16)
    rows = sds((slots,), jnp.int32)

    def decode(params, k, v, wk, wv, tok, pos, tables):
        return fam.decode(params, k, v, tok, pos, tables, bs,
                          window=(wk, wv))

    compiled = jax.jit(decode, donate_argnums=(1, 2, 3, 4)).lower(
        params, pool, pool, store, store, rows, rows,
        sds((slots, width), jnp.int32)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * 2 * (
        int(np.prod(pool.shape)) + int(np.prod(store.shape)))
    hlo = compiled.as_text()
    assert "ragged-dot" not in hlo
    calls = re.findall(r"%(grouped_matmul[.\w]*) = ", hlo)
    assert len(calls) == 6, calls
    scopes = scope_map(hlo)
    assert {scopes.get(c) for c in calls} == {
        "blocks/global/moe/experts", "blocks/sliding/moe/experts"}
    _assert_pool_row_major_and_uncopied(hlo, pool, view=(slots, width, bs))
    # two walks: the global run's over the pool — a run of ONE layer is
    # a loop the compiler unrolls, so the pool reaches it through a free
    # bitcast where a longer run's carry is a get-tuple-element — and
    # the sliding run's over its rings
    walks = _audit().read_hlo(hlo, {"k": pool.shape, "v": pool.shape},
                              pool.shape[1] * 512 * 2)["row_walks"]
    assert len(walks) == 2, walks
    (walk,) = [w for w in walks if w["pool_operands"]]
    assert len(walk["pool_operands"]) == 2 and all(
        c["op"] == "bitcast" for c in walk["copies_beside"]), walk
    _assert_rings_walked_in_place(hlo, store, slots=slots, heads=4)


def _audit():
    spec = importlib.util.spec_from_file_location(
        "pool_layout_audit", os.path.join(REPO, "tools",
                                          "pool_layout_audit.py"))
    audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(audit)
    return audit


def _assert_rings_walked_in_place(hlo: str, store, *, slots: int,
                                  heads: int):
    """A window family's decode program: the window ``store``
    ``[L_w, (slots + 1) x ring, F]`` is neither copied (whole, a layer,
    or a ring cut into ``heads`` kv heads) nor sliced — no fusion
    materialises any part of it the size of every slot's ring of a
    layer — and ONE call of the walk reads it, k and v, fed by the
    store itself: the sliding run's carry or the in-place ``kv_write``
    scatter of the rows just written."""
    ring = store.shape[1] // (slots + 1)
    got = _audit().read_hlo(hlo, {"wk": store.shape, "wv": store.shape},
                            slots * ring * store.shape[2] * 2)
    for c in got["big_copies"]:
        dims = tuple(c["dims"])
        assert dims not in {tuple(store.shape), tuple(store.shape[1:]),
                            (1, *store.shape[1:])}, c
        assert sorted(dims) != sorted((slots, ring, heads, 128)), c
    assert got["big_slices"] == [], got["big_slices"]
    rings = [w for w in got["row_walks"] if any(
        c["dims"] == list(store.shape) for c in w["pool_operands"])]
    assert len(rings) == 1, got["row_walks"]
    (walk,) = rings
    stored = [c for c in walk["pool_operands"]
              if c["dims"] == list(store.shape)]
    assert len(stored) == 2 and walk["copies_beside"] == [], walk


def _assert_pool_row_major_and_uncopied(hlo: str, pool, view=None,
                                        walks=None, ring_walks=0):
    """The compiled program takes ``pool``-shaped parameters in the
    row-major layout and holds no ``copy`` / ``transpose`` of the whole
    pool or of one layer's slice of it (tools/pool_layout_audit.py is
    the same reading, for the cells' engines) — nor, for a decode
    program, of a gathered ``view`` (slots, table width, block size:
    the rows as gathered lead with these dims, and so did the view
    split into heads, which the compiler wrote out as a copy of it
    every layer until PR 30). ``walks``: the calls of the per-row walk
    the program must hold (PR 34: a decode program of a bf16 pool reads
    each row's live blocks out of the carried pool, one call a layer
    loop), each fed by the pool itself — the loop's carry through the
    in-place ``kv_write`` scatter — and by no copy. ``ring_walks``: the
    calls beside them that read another buffer (a window store's
    rings), whose operands this reading does not see."""
    spec = importlib.util.spec_from_file_location(
        "pool_layout_audit", os.path.join(REPO, "tools",
                                          "pool_layout_audit.py"))
    audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(audit)
    layer_bytes = pool.shape[1] * pool.shape[2] * pool.dtype.itemsize
    got = audit.read_hlo(hlo, {"k": pool.shape, "v": pool.shape},
                         layer_bytes)
    assert got["entry_layouts"] == {"k/v": ["{2,1,0}"] * 2}, got
    slices = {tuple(pool.shape), (1, *pool.shape[1:]), tuple(pool.shape[1:])}
    for c in got["big_copies"]:
        assert tuple(c["dims"]) not in slices, c
        if view is not None:
            assert tuple(c["dims"][:3]) != tuple(view), c
            assert tuple(c["dims"][:2]) != (view[0] * view[1], view[2]), c
    if walks is not None:
        fed = [c for c in got["row_walks"] if c["pool_operands"]]
        assert len(fed) == walks, got["row_walks"]
        assert len(got["row_walks"]) == walks + ring_walks, got["row_walks"]
        for call in fed:
            assert len(call["pool_operands"]) == 2, call
            assert call["copies_beside"] == [], call
        # and no gather at the table's width is left beside them
        if view is not None:
            assert f"[{view[0]},{view[1]},{view[2]}," not in hlo.replace(
                " ", "")


@pytest.mark.parametrize("width", ("decode", "prefill"))
def test_xl_programs_take_the_pool_row_major_and_copy_none_of_it(chip,
                                                                  width):
    """``gpt2_family(...).decode`` and ``.prefill_from`` at GPT-2 XL's
    published widths (25 heads x 64: 1600 features, padded to 1664), 4
    of its 48 layers, 12 slots and 384 blocks as the serving cell has
    them, compiled for the described chip (shapes only). With the
    trailing dims ``25, 64`` — or ``1600`` — the chip lays the pool out
    slot-minor and every program re-lays it (PERF.md, PR 28)."""
    from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
    from quintnet_tpu.serve import gpt2_family
    from quintnet_tpu.serve.kv_pool import feature_width
    from quintnet_tpu.serve.kv_quant import make_policy

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "gpt2-xl.json")) as f:
        cfg = GPT2Config.from_dict({**json.load(f), "n_layer": 4})
    fam = gpt2_family(cfg)
    slots, bs, blocks, table = 12, 16, 384, 64
    policy = make_policy("bf16")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: gpt2_init(k, cfg), jax.random.key(0)))
    width_f = feature_width(fam.n_kv_heads, fam.head_dim)
    assert width_f == 1664
    pool = sds((fam.n_layers, blocks * bs, width_f), jnp.bfloat16)
    scalar = sds((), jnp.int32)
    if width == "decode":
        rows = sds((slots,), jnp.int32)

        def program(params, k, v, tok, pos, tables):
            return fam.decode(params, k, v, tok, pos, tables, bs,
                              policy=policy)

        args = (rows, rows, sds((slots, table), jnp.int32))
    else:
        def program(params, k, v, ids, start, t0, row):
            return fam.prefill_from(params, k, v, ids, start, t0, row, bs,
                                    policy=policy)

        args = (sds((1, 64), jnp.int32), scalar, scalar,
                sds((table,), jnp.int32))
    compiled = jax.jit(program, donate_argnums=(1, 2)).lower(
        params, pool, pool, *args).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * (
        pool.shape[0] * pool.shape[1] * pool.shape[2] * 2)
    _assert_pool_row_major_and_uncopied(
        compiled.as_text(), pool,
        view=(slots, table, bs) if width == "decode" else None,
        walks=1 if width == "decode" else 0)


def test_cache_dir_from_env_is_left_alone(monkeypatch, tmp_path,
                                          cache_config):
    """With JAX_COMPILATION_CACHE_DIR set JAX reads the variable
    itself; the helper sets no directory in code, only the two
    thresholds."""
    from quintnet_tpu.core import runtime

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_cache_dir_default_is_fixed_inside_checkout(monkeypatch, tmp_path,
                                                    cache_config):
    """Unset: one fixed path inside the checkout — the same from two
    calls and two working directories, never a home, a temp name, a
    pid or a time."""
    from quintnet_tpu.core import runtime

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.enable_compilation_cache()
    monkeypatch.chdir(tmp_path)
    second = runtime.enable_compilation_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------
def test_chip_smoke_refuses_a_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_cpu_rehearsal(tmp_path, capsys, monkeypatch):
    """The first rehearsal: the train and serve phases (and the Pallas
    engine's half of the kernel phase, in the interpreter conftest
    turned on) run to their end at a tiny size on the CPU. ``main`` is
    never called, so the success line cannot appear."""
    import chip_smoke as cs
    from quintnet_tpu.models.gpt2 import GPT2Config

    tiny = cs.Size(
        cfg=GPT2Config.tiny(n_positions=128, n_layer=2), batch=4, seq=32,
        train_steps=5, slots=4, block_size=8, num_blocks=64,
        max_seq_len=96, prompt_lens=(5, 9, 14, 20, 27, 35, 44, 60),
        max_new=8, dense_check=(1, 6), kernel_check=(0, 2),
        pallas_prefill_len=16)
    # the whole-row kernel is pinned against the GATHERED view: the
    # xla engine's side of the comparison is traced with the kernels'
    # interpreter off, where its verify program (few rows a slot on a
    # bf16 pool) keeps ``_lane_diag_sdpa`` on the gathered view; with
    # it on, it would walk each row's live blocks (PR 34), another
    # rounding of the probabilities, bounded in test_paged_attention's
    # TestRowWalk
    pa = importlib.import_module("quintnet_tpu.ops.paged_attention")
    paged_logits = cs.paged_logits

    def logits_of(engine, prompts, width):
        monkeypatch.setattr(pa, "INTERPRET", engine.attn_kernel != "xla")
        return paged_logits(engine, prompts, width)

    with open(tmp_path / "phases.jsonl", "a") as sink:
        train = cs.run_phase(
            "train", lambda: cs.phase_train(tiny, str(tmp_path), 0), sink)
        served = {}

        def serve():
            rec, served["engine"], served["prompts"] = cs.phase_serve(
                tiny, 0)
            return rec

        serve_rec = cs.run_phase("serve", serve, sink)
        monkeypatch.setattr(cs, "paged_logits", logits_of)
        paged = cs.check_pallas_engine(tiny, served["engine"],
                                       served["prompts"], 0)

    assert len(train["losses"]) == 5
    assert train["losses"][-1] < train["losses"][0]
    assert train["checkpoint"]["restored_step"] == 5
    assert not os.path.exists(tmp_path / "ckpt")   # scratch, removed
    assert serve_rec["requests"] == 8 and serve_rec["new_tokens"] == 64
    assert serve_rec["paged_vs_dense_logits"]["max_abs_diff"] < 0.01
    # interpret mode: bit-parity with the gathered view, and (the
    # reason the kernel phase is chip-only) no custom call to find
    assert paged["pallas_vs_xla_logits"]["max_abs_diff"] == 0.0
    assert paged["tpu_custom_call"] is False

    lines = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(x)["phase"] for x in lines] == ["train", "serve"]
    assert all("wall_s" in json.loads(x) and "compile_s" in json.loads(x)
               for x in lines)
    # a phase's compile seconds and programs are the program's own
    # record's (obs/recorder.startup().totals), and still count
    assert not hasattr(cs, "CompileMeter")
    for rec in (train, serve_rec):
        assert rec["programs"] > 0 and 0 < rec["compile_s"] < rec["wall_s"]
    assert not any('"device"' in x for x in lines)  # no success line


def test_chip_smoke_kda_moe_phase_cpu_rehearsal():
    """The fourth phase at its own (tiny) size on the CPU: the linear-
    attention + latent family through ``ServeEngine`` with chunked
    prefill, every greedy token within the tolerance of the plain
    reference's best logit (on the CPU's exact f32: at it)."""
    import chip_smoke as cs

    rec = cs.phase_kda_moe(0)
    assert rec["requests"] == 5 and rec["new_tokens"] == 40
    assert rec["greedy_gap"] < 1e-4 < rec["ref_std"]
    assert rec["prefill_chunks"] >= 2 + 2 + 2       # prompts of 40, 60, 33
    assert rec["preempted"] == 0 and rec["state_bytes_per_slot"] > 0
    assert rec["programs_of_the_engine"][0] == "serve_decode"
