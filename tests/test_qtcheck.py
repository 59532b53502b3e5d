"""qtcheck golden tests: the static-analysis layer that pins QuintNet's
communication contracts (quintnet_tpu/analysis/).

- Collective-census goldens: the dp / tp / zero / 3D train steps and
  the serve prefill/decode programs must put EXACTLY the collectives
  the declarative specs (analysis/specs.py) derive from program
  structure on the wire — a single extra all-gather anywhere in
  parallel/ or serve/ fails these with a named per-axis diff.
- Recompile sentinel: the serve engine compiles exactly ONE prefill +
  ONE decode program across a mixed request trace (admissions,
  retirements, block growth, preemption), enforced at call time.
- Linter rules: each QT rule fires on a synthetic footgun snippet and
  respects pragmas.
- Baseline gate: the committed tools/qtcheck_baseline.json matches the
  tree EXACTLY (no new violations, no stale entries) — the same
  no-drift discipline tests/test_bench_stale.py applies to bench
  artifacts.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from quintnet_tpu.analysis.jaxpr_audit import (collective_census,
                                               donation_report,
                                               dtype_report)
from quintnet_tpu.analysis.lint import (compare_baseline, lint_paths,
                                        lint_source, load_baseline,
                                        violations_to_baseline)
from quintnet_tpu.analysis.recompile import (RecompileError,
                                             RecompileSentinel)
from quintnet_tpu.analysis import specs as census_specs
from quintnet_tpu.core import collectives as cc
from quintnet_tpu.core.config import Config
from quintnet_tpu.models.vit import ViTConfig, vit_init, vit_model_spec
from quintnet_tpu.parallel.strategy import get_strategy

REPO = os.path.join(os.path.dirname(__file__), "..")

VIT = ViTConfig(image_size=14, patch_size=7, in_channels=1, hidden_dim=16,
                depth=4, num_heads=2, num_classes=10)


def _train_setup(mesh_dim, mesh_name, optimizer="adamw", **training):
    cfg = Config.from_dict({
        "mesh_dim": list(mesh_dim), "mesh_name": list(mesh_name),
        "training": {"batch_size": 8, "optimizer": optimizer, **training},
    })
    strat = get_strategy("auto", cfg)
    model = vit_model_spec(VIT)
    opt = optax.adamw(1e-3)
    params = strat.shard_params(model, vit_init(jax.random.key(0), VIT))
    state = strat.init_opt_state(model, opt, params)
    x = jax.random.normal(jax.random.key(1), (8, 14, 14, 1))
    y = jax.random.randint(jax.random.key(2), (8,), 0, 10)
    batch = strat.shard_batch((x, y), model)
    step = strat.make_train_step(model, opt)
    return strat, model, step, params, state, batch


N_LEAVES = len(jax.tree.leaves(vit_init(jax.random.key(0), VIT)))


# ---------------------------------------------------------------------
# collective-census goldens (train steps)
# ---------------------------------------------------------------------

class TestTrainStepCensus:
    def test_dp_exact_counts(self):
        """dp train step: one all_reduce per gradient leaf + the loss
        pmean, nothing else, dp axis only."""
        _, _, step, params, state, batch = _train_setup([2], ["dp"])
        census = collective_census(step, params, state, batch, 0)
        expect = census_specs.expected_dp_train_step(N_LEAVES)
        assert census.diff(expect) == [], census.as_dict()
        assert census.dynamic == 0

    def test_dp_tp_2axis_exact_counts(self):
        """2-axis dp x tp mesh: each axis sees exactly its own pattern
        — the composition adds no cross terms. This census walks the
        row-parallel psums of every block (nn/attention, nn/layers),
        the replicated-grad syncs, and the clip-norm psums."""
        strat, model, step, params, state, batch = _train_setup(
            [2, 2], ["dp", "tp"])
        n, n_repl, n_shard = census_specs.spec_leaf_counts(
            strat.param_specs(model), "tp")
        census = collective_census(step, params, state, batch, 0)
        expect = census_specs.expected_dp_tp_train_step(
            n, VIT.depth, n_repl, n_shard)
        assert census.diff(expect) == [], census.as_dict()

    def test_zero1_exact_counts(self):
        """ZeRO-1 = the dp census + exactly ONE all_gather (flat param
        re-assembly). If optimizer-state sharding ever started
        gathering per leaf, this pins it."""
        _, _, step, params, state, batch = _train_setup(
            [2], ["dp"], optimizer="zero1_adamw")
        census = collective_census(step, params, state, batch, 0)
        expect = census_specs.expected_zero1_train_step(N_LEAVES)
        assert census.diff(expect) == [], census.as_dict()

    def test_zero2_exact_counts(self):
        """ZeRO-2 collapses the per-leaf grad pmeans into ONE
        reduce_scatter — the halved-traffic contract, verified
        structurally rather than by wire measurements."""
        _, _, step, params, state, batch = _train_setup(
            [2], ["dp"], optimizer="zero2_adamw")
        census = collective_census(step, params, state, batch, 0)
        expect = census_specs.expected_zero2_train_step()
        assert census.diff(expect) == [], census.as_dict()

    def test_3d_1f1b_exact_counts(self):
        """Full 3D (dp x tp x pp, 1F1B): per-microbatch tp psums (incl.
        the recompute forward), stage-boundary ppermutes, pp grad
        syncs, dp leaf pmeans — all pinned per axis."""
        strat, model, step, params, state, batch = _train_setup(
            [2, 2, 2], ["dp", "tp", "pp"],
            gradient_accumulation_steps=2, schedule="1f1b")
        pspecs = strat.param_specs(model)
        _, tp_repl, tp_shard = census_specs.spec_leaf_counts(pspecs, "tp")
        _, pp_repl, pp_shard = census_specs.spec_leaf_counts(pspecs, "pp")
        census = collective_census(step, params, state, batch, 0)
        expect = census_specs.expected_3d_train_step(
            N_LEAVES, VIT.depth, tp_repl, tp_shard, pp_repl, pp_shard,
            n_micro=2, pp_size=2)
        assert census.diff(expect) == [], census.as_dict()
        assert census.dynamic == 0  # no while_loops in any train step


class TestTpLayerCensus:
    """Pin parallel/tp.py's layer functions DIRECTLY: these counts are
    what an extra collective inserted into column_parallel_linear /
    row_parallel_linear changes first."""

    def _mesh(self):
        return Mesh(np.array(jax.devices()[:2]), ("tp",))

    def _params(self):
        k = jax.random.key(0)
        pc = {"w": jax.random.normal(k, (8, 16)),
              "b": jnp.zeros((16,))}
        pr = {"w": jax.random.normal(k, (16, 8)),
              "b": jnp.zeros((8,))}
        x = jax.random.normal(k, (4, 8))
        return pc, pr, x

    def _specs(self):
        from quintnet_tpu.parallel.tp import column_spec, row_spec

        return (column_spec(stacked=False), row_spec(stacked=False))

    def test_column_row_forward_exactly_one_psum(self):
        """Megatron block pattern (column no-gather -> row psum): ONE
        all_reduce per forward, zero gathers."""
        from quintnet_tpu.parallel import tp

        cs, rs = self._specs()

        def fwd(pc, pr, x):
            h = tp.column_parallel_linear(pc, x, axis="tp")
            y = tp.row_parallel_linear(pr, h, axis="tp")
            return jnp.sum(y)

        f = cc.shard_map_fn(fwd, self._mesh(),
                            in_specs=(cs, rs, P(None)), out_specs=P())
        census = collective_census(f, *self._params())
        assert census.as_dict() == {"tp": {"all_reduce": 1}}, \
            census.as_dict()

    def test_column_row_grad_adds_exactly_one_psum(self):
        """value_and_grad doubles it (the transpose re-syncs the
        replicated cotangent): 2 all_reduce, still zero gathers."""
        from quintnet_tpu.parallel import tp

        cs, rs = self._specs()

        def loss(pc, pr, x):
            h = tp.column_parallel_linear(pc, x, axis="tp")
            y = tp.row_parallel_linear(pr, h, axis="tp")
            return jnp.sum(y)

        def vg(pc, pr, x):
            return jax.value_and_grad(loss, argnums=(0, 1))(pc, pr, x)

        f = cc.shard_map_fn(vg, self._mesh(),
                            in_specs=(cs, rs, P(None)),
                            out_specs=(P(), self._specs()))
        census = collective_census(f, *self._params())
        assert census.as_dict() == {"tp": {"all_reduce": 2}}, \
            census.as_dict()

    def test_gather_output_costs_one_all_gather_and_its_transpose(self):
        """column gather_output=True: +1 all_gather forward, and its
        autodiff transpose is a reduce_scatter in the backward — the
        exact comm signature of the gathered variant."""
        from quintnet_tpu.parallel import tp

        cs, _ = self._specs()

        def loss(pc, x):
            return jnp.sum(tp.column_parallel_linear(
                pc, x, axis="tp", gather_output=True))

        def vg(pc, x):
            return jax.value_and_grad(loss)(pc, x)

        f = cc.shard_map_fn(vg, self._mesh(),
                            in_specs=(cs, P(None)),
                            out_specs=(P(), cs))
        pc, _, x = self._params()
        census = collective_census(f, pc, x)
        assert census.as_dict() == {
            "tp": {"all_gather": 1, "reduce_scatter": 1}}, census.as_dict()


# ---------------------------------------------------------------------
# serve programs: census + the one-compiled-program invariant
# ---------------------------------------------------------------------

class TestServe:
    @pytest.fixture(scope="class")
    def gpt2(self):
        from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init

        cfg = GPT2Config.tiny(n_layer=2)
        return cfg, gpt2_init(jax.random.key(0), cfg)

    def _engine(self, cfg, params, mesh=None, **kw):
        from quintnet_tpu.serve import ServeEngine, gpt2_family

        kw.setdefault("max_slots", 3)
        kw.setdefault("block_size", 4)
        kw.setdefault("num_blocks", 24)
        kw.setdefault("max_seq_len", 32)
        return ServeEngine(gpt2_family(cfg), params, mesh=mesh, **kw)

    def _prefill_args(self, eng, params, bucket):
        # one bucket program's args: tail ids padded to the bucket
        # width, dynamic (start, t0) split, COW scalars
        ids = np.zeros((1, bucket), np.int32)
        row = np.zeros((eng.table_width,), np.int32)
        kp, vp = eng.pool.caches()
        return (params, kp, vp, jnp.asarray(ids), jnp.int32(1),
                jnp.int32(3), jnp.asarray(row), jnp.int32(0),
                jnp.int32(0), jnp.asarray(eng._key_data[0]))

    def _decode_args(self, eng, params):
        kp, vp = eng.pool.caches()
        return (params, kp, vp, jnp.asarray(eng._tok),
                jnp.asarray(eng._pos), jnp.asarray(eng._tables),
                jnp.asarray(eng._key_data))

    def test_single_device_census_is_collective_free(self, gpt2):
        cfg, params = gpt2
        eng = self._engine(cfg, params)
        cases = [(eng._prefills[b].fn,
                  self._prefill_args(eng, params, b),
                  census_specs.expected_serve_prefill(cfg.n_layer))
                 for b in eng.prefill_buckets]
        cases.append((eng._decode.fn, self._decode_args(eng, params),
                      census_specs.expected_serve_decode(cfg.n_layer)))
        for fn, args, spec in cases:
            census = collective_census(fn, *args)
            assert census.diff(spec) == [], census.as_dict()
            assert census.total() == 0

    def test_tp_census_two_psums_per_layer_every_bucket(self, gpt2):
        """Head-sharded serving: exactly 2 row-parallel psums per block
        per program (attention out-proj + MLP down-proj), nothing else
        — the engine's batching/paging/prefix-cache COW adds NO
        collectives, and EVERY prefill bucket width carries the same
        census (the bucket only changes a batch-like dim)."""
        cfg, params = gpt2
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        eng = self._engine(cfg, params, mesh=mesh)
        assert len(eng.prefill_buckets) >= 2  # actually bucketed
        cases = [(eng._prefills[b].fn,
                  self._prefill_args(eng, params, b),
                  census_specs.expected_serve_prefill(cfg.n_layer,
                                                      tp_axis="tp"))
                 for b in eng.prefill_buckets]
        cases.append((eng._decode.fn, self._decode_args(eng, params),
                      census_specs.expected_serve_decode(cfg.n_layer,
                                                         tp_axis="tp")))
        for fn, args, spec in cases:
            census = collective_census(fn, *args)
            assert census.diff(spec) == [], census.as_dict()

    def test_pallas_census_matches_xla_per_backend(self, gpt2):
        """The attention-backend ladder (analysis/specs.attn_kernels)
        must not move a single collective: under tp the pallas decode
        program carries EXACTLY the xla decode census (2 row-parallel
        psums per layer — the kernel sits strictly inside the per-layer
        attention; a pallas_call has no collectives), for the
        passthrough f32 pool AND the scaled int8 one. A kernel that
        snuck a gather/psum into the wire would fail with a named
        diff."""
        from quintnet_tpu.analysis.specs import attn_kernels

        cfg, params = gpt2
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        spec = census_specs.expected_serve_decode(cfg.n_layer,
                                                  tp_axis="tp")
        for kv_dtype in ("f32", "int8"):
            per_backend = {}
            for kernel in attn_kernels():
                eng = self._engine(cfg, params, mesh=mesh,
                                   kv_dtype=kv_dtype,
                                   attn_kernel=kernel)
                caches = eng.pool.caches()
                args = (params, *caches, jnp.asarray(eng._tok),
                        jnp.asarray(eng._pos), jnp.asarray(eng._tables),
                        jnp.asarray(eng._key_data))
                census = collective_census(eng._decode.fn, *args)
                assert census.diff(spec) == [], (kernel, kv_dtype,
                                                 census.as_dict())
                per_backend[kernel] = census.as_dict()
            assert per_backend["pallas"] == per_backend["xla"]

    def test_one_prefill_one_decode_across_mixed_trace(self, gpt2):
        """The PR 1 serving promise as a sentinel-enforced invariant:
        staggered arrivals, varying prompt lengths, retirements, block
        growth and a forced preemption all hit the SAME two compiled
        programs. A second lowering would raise RecompileError at the
        call that caused it."""
        cfg, params = gpt2
        # pool sized to force growth + preemption mid-trace
        eng = self._engine(cfg, params, max_slots=3, block_size=2,
                           num_blocks=12, max_seq_len=16)
        rng = np.random.default_rng(0)
        prompts = [np.asarray(rng.integers(0, cfg.vocab_size, (n,)),
                              np.int32) for n in (3, 5, 4, 6, 3)]
        arrivals = [0, 1, 2, 5, 8]
        submitted, step = 0, 0
        while submitted < len(prompts) or eng.has_work:
            while (submitted < len(prompts)
                   and arrivals[submitted] <= step):
                eng.submit(prompts[submitted], 5)
                submitted += 1
            eng.step()
            step += 1
            assert step < 500
        assert eng.metrics.finished == len(prompts)
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
        eng.assert_compile_count()  # raises with a diff on violation

    def test_donation_no_aliasable_misses(self, gpt2):
        """Every aliasable buffer of the serve programs is donated
        (pool caches, token rows, key state) in every prefill bucket:
        peak memory is paid once."""
        cfg, params = gpt2
        eng = self._engine(cfg, params)
        cases = [(eng._prefills[b].fn, self._prefill_args(eng, params, b))
                 for b in eng.prefill_buckets]
        cases.append((eng._decode.fn, self._decode_args(eng, params)))
        for fn, args in cases:
            rep = donation_report(fn, *args)
            assert rep.undonated_aliasable == [], rep.summary()
            assert rep.donated_bytes > 0

    def _spec_engine(self, cfg, params, mesh=None, **kw):
        from quintnet_tpu.serve import SpecConfig

        return self._engine(cfg, params, mesh=mesh, spec=SpecConfig(),
                            **kw)

    def _verify_args(self, eng, params, k):
        # one verify bucket's args: [S, k+1] token runs, per-row
        # (start, tail_len), full tables, per-row key state
        S = eng.max_slots
        kp, vp = eng.pool.caches()
        return (params, kp, vp,
                jnp.asarray(np.zeros((S, k + 1), np.int32)),
                jnp.asarray(np.zeros((S,), np.int32)),
                jnp.asarray(np.ones((S,), np.int32)),
                jnp.asarray(eng._tables), jnp.asarray(eng._key_data))

    def test_verify_census_matches_decode_every_bucket(self, gpt2):
        """The speculative verify programs (serve/spec.py) are the
        decode step widened to k+1 tokens per row: single-device they
        must be collective-free, under tp exactly the decode census —
        2 row-parallel psums per layer, nothing else, identical for
        EVERY draft-length bucket (the bucket only changes a
        batch-like dim; the draft scatter/gather adds no
        collectives)."""
        cfg, params = gpt2
        eng = self._engine(cfg, params)
        assert eng.compile_stats() == {"prefill": 0, "decode": 0}
        seng = self._spec_engine(cfg, params)
        assert tuple(seng._verifies) == seng.spec.buckets
        for k in seng.spec.buckets:
            census = collective_census(
                seng._verifies[k].fn, *self._verify_args(seng, params, k))
            spec = census_specs.expected_serve_verify(cfg.n_layer)
            assert census.diff(spec) == [], census.as_dict()
            assert census.total() == 0

        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        teng = self._spec_engine(cfg, params, mesh=mesh)
        for k in teng.spec.buckets:
            census = collective_census(
                teng._verifies[k].fn, *self._verify_args(teng, params, k))
            spec = census_specs.expected_serve_verify(cfg.n_layer,
                                                      tp_axis="tp")
            assert census.diff(spec) == [], census.as_dict()

    def test_verify_donation_no_aliasable_misses(self, gpt2):
        """Every verify bucket donates its aliasable buffers: the pool
        caches update in place and the [S, P] ids row aliases the
        candidate-token output (key_data does NOT alias — the chain
        output is [S, P, keysize], a different shape)."""
        cfg, params = gpt2
        eng = self._spec_engine(cfg, params)
        for k in eng.spec.buckets:
            rep = donation_report(eng._verifies[k].fn,
                                  *self._verify_args(eng, params, k))
            assert rep.undonated_aliasable == [], rep.summary()
            assert rep.donated_bytes > 0

    @pytest.mark.parametrize("sp", [2, 4])
    def test_sp_prefill_census_ppermutes_are_f_of_sp(self, gpt2, sp):
        """The ring sp-prefill programs (long-context serving,
        serve/longctx.py): per layer, the stacked chunk K/V pair and
        its position vector rotate sp scan steps (2*sp ppermutes) plus
        one all_gather reassembling the chunk for the pool scatter,
        plus ONE program-wide psum extracting the last position's
        hidden row — analysis/specs.expected_serve_sp_prefill, a pure
        function of (n_layers, sp), identical for EVERY bucket width
        (sp shards the bucket, it never changes the wire). An extra
        collective from a refactor fails here with a named diff. The
        decode program on the same mesh stays collective-FREE (it runs
        replicated)."""
        from quintnet_tpu.serve import ServeEngine, gpt2_family

        cfg, params = gpt2
        mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
        eng = ServeEngine(gpt2_family(cfg), params, mesh=mesh,
                          sp_axis="sp", max_slots=3, block_size=4,
                          num_blocks=24, max_seq_len=32)
        assert eng.sp_axis == "sp"
        spec = census_specs.expected_serve_sp_prefill(cfg.n_layer, sp)
        for b in eng.prefill_buckets:
            census = collective_census(
                eng._prefills[b].fn, *self._prefill_args(eng, params, b))
            assert census.diff(spec) == [], census.as_dict()
            assert census.total() == 2 * sp * cfg.n_layer \
                + cfg.n_layer + 1
        dec = collective_census(eng._decode.fn,
                                *self._decode_args(eng, params))
        assert dec.total() == 0


# ---------------------------------------------------------------------
# MoE serving: expert-parallel collective census
# ---------------------------------------------------------------------

class TestServeMoE:
    """Census goldens for expert-parallel serving
    (analysis/specs.expected_serve_moe): under ep>1 every program kind
    — every prefill bucket, the decode step, every verify bucket —
    carries EXACTLY 2 all_to_alls per MoE layer (the nn/moe.py
    dispatch + combine) and nothing else on the ep axis; the
    capacity-bounded scatter/gather is local and the router
    replicated. ep=1 (and no mesh) is the dense-replicated program:
    ZERO collectives — the census face of the ep=1 == dense
    bit-identity contract. The dense families' own censuses are
    pinned by TestServe above; these goldens prove MoE adds all_to_all
    and ONLY all_to_all, and only on the ep axis."""

    _engine = TestServe._engine
    _spec_engine = TestServe._spec_engine
    _prefill_args = TestServe._prefill_args
    _decode_args = TestServe._decode_args
    _verify_args = TestServe._verify_args

    @pytest.fixture(scope="class")
    def gpt2(self):
        from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init

        cfg = GPT2Config.tiny(n_layer=2, n_experts=4, expert_top_k=2)
        return cfg, gpt2_init(jax.random.key(0), cfg)

    def test_ep_census_two_all_to_alls_per_moe_layer(self, gpt2):
        cfg, params = gpt2
        mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
        eng = self._spec_engine(cfg, params, mesh=mesh, ep_axis="ep")
        assert eng.ep_axis == "ep"
        spec = census_specs.expected_serve_moe(cfg.n_layer,
                                               ep_axis="ep")
        cases = [(eng._prefills[b].fn,
                  self._prefill_args(eng, params, b))
                 for b in eng.prefill_buckets]
        cases.append((eng._decode.fn, self._decode_args(eng, params)))
        cases.extend((eng._verifies[k].fn,
                      self._verify_args(eng, params, k))
                     for k in eng.spec.buckets)
        for fn, args in cases:
            census = collective_census(fn, *args)
            assert census.diff(spec) == [], census.as_dict()
            assert census.total() == 2 * cfg.n_layer

    def test_ep_times_tp_census_composes(self, gpt2):
        """ep x tp: the dense tp census (2 row-parallel psums per
        layer — the expert FFN's down-proj psum folds into the same
        count) PLUS the 2 per-layer ep all_to_alls, each axis
        accounted separately."""
        cfg, params = gpt2
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("ep", "tp"))
        eng = self._engine(cfg, params, mesh=mesh, ep_axis="ep")
        spec = census_specs.expected_serve_moe(cfg.n_layer,
                                               ep_axis="ep",
                                               tp_axis="tp")
        cases = [(eng._prefills[b].fn,
                  self._prefill_args(eng, params, b))
                 for b in eng.prefill_buckets]
        cases.append((eng._decode.fn, self._decode_args(eng, params)))
        for fn, args in cases:
            census = collective_census(fn, *args)
            assert census.diff(spec) == [], census.as_dict()

    def test_ep1_census_is_collective_free(self, gpt2):
        """A size-1 ep mesh nulls ep_axis at construction — the
        programs are the dense-replicated MoE math, zero collectives
        (expected_serve_moe with ep_axis=None)."""
        cfg, params = gpt2
        mesh = Mesh(np.array(jax.devices()[:1]), ("ep",))
        eng = self._engine(cfg, params, mesh=mesh, ep_axis="ep")
        assert eng.ep_axis is None
        assert census_specs.expected_serve_moe(cfg.n_layer) == {}
        for b in eng.prefill_buckets:
            census = collective_census(
                eng._prefills[b].fn, *self._prefill_args(eng, params, b))
            assert census.total() == 0

    def test_ep_donation_no_aliasable_misses(self, gpt2):
        """The widened MoE return (the trailing routing-stats dict)
        must not cost a donation: every aliasable buffer of every ep
        program is still donated."""
        cfg, params = gpt2
        mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
        eng = self._engine(cfg, params, mesh=mesh, ep_axis="ep")
        cases = [(eng._prefills[b].fn,
                  self._prefill_args(eng, params, b))
                 for b in eng.prefill_buckets]
        cases.append((eng._decode.fn, self._decode_args(eng, params)))
        for fn, args in cases:
            rep = donation_report(fn, *args)
            assert rep.undonated_aliasable == [], rep.summary()
            assert rep.donated_bytes > 0


# ---------------------------------------------------------------------
# serve programs: dtype-promotion census per KV layout policy
# ---------------------------------------------------------------------

class TestServeDtypeCensus:
    """The dtype_report goldens for the serving programs, pinned PER
    KV-POOL LAYOUT POLICY (serve/kv_quant.py): the f32/bf16 passthrough
    programs carry no silent f64 upcasts and no 16-bit accumulation
    (softmax and scores stay f32 — the engine's mixed-precision
    contract), and the scaled int8 / fake_quant programs — whose
    kernels now dequantize inside the gathered view and quantize on
    scatter — introduce NONE either: quant math accumulates in f32,
    int8 is storage only. A half-accum dot or accidental x64 in any
    policy's prefill/decode/verify fails here with the primitive
    named. The collective census is policy-invariant too (the scaled
    paths are local gather/scatter arithmetic)."""

    @pytest.fixture(scope="class")
    def gpt2(self):
        from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init

        cfg = GPT2Config.tiny(n_layer=2)
        return cfg, gpt2_init(jax.random.key(0), cfg)

    def _engine(self, cfg, params, kv_dtype, mesh=None, **kw):
        from quintnet_tpu.serve import ServeEngine, SpecConfig, gpt2_family

        kw.setdefault("max_slots", 3)
        kw.setdefault("block_size", 4)
        kw.setdefault("num_blocks", 24)
        kw.setdefault("max_seq_len", 32)
        kw.setdefault("spec", SpecConfig())
        return ServeEngine(gpt2_family(cfg), params, mesh=mesh,
                           kv_dtype=kv_dtype, **kw)

    def _cases(self, eng, params):
        """(fn, args) for one bucket of each program family."""
        b = eng.prefill_buckets[0]
        k = eng.spec.buckets[0]
        S = eng.max_slots
        pools = eng.pool.caches()
        prefill = (params, *pools, jnp.zeros((1, b), jnp.int32),
                   jnp.int32(1), jnp.int32(3),
                   jnp.zeros((eng.table_width,), jnp.int32),
                   jnp.int32(0), jnp.int32(0),
                   jnp.asarray(eng._key_data[0]))
        decode = (params, *pools, jnp.asarray(eng._tok),
                  jnp.asarray(eng._pos), jnp.asarray(eng._tables),
                  jnp.asarray(eng._key_data))
        verify = (params, *pools,
                  jnp.zeros((S, k + 1), jnp.int32),
                  jnp.zeros((S,), jnp.int32), jnp.ones((S,), jnp.int32),
                  jnp.asarray(eng._tables), jnp.asarray(eng._key_data))
        return [(eng._prefills[b].fn, prefill),
                (next(iter(eng._decodes.values())).fn, decode),
                (eng._verifies[k].fn, verify)]

    @pytest.mark.parametrize("kv_dtype",
                             ["f32", "bf16", "int8", "fake_quant"])
    def test_dtype_census_clean_every_policy(self, gpt2, kv_dtype):
        cfg, params = gpt2
        eng = self._engine(cfg, params, kv_dtype)
        assert eng.kv_policy.name == kv_dtype
        for fn, args in self._cases(eng, params):
            issues = dtype_report(fn, *args)
            assert issues == [], (kv_dtype, [i.detail for i in issues])

    def test_int8_tp_collective_census_unchanged(self, gpt2):
        """Quantization adds NO collectives: the int8 programs under
        tp=2 carry exactly the f32 census — 2 row-parallel psums per
        block, nothing for the scales (they shard with the heads and
        dequant/requant is rank-local)."""
        cfg, params = gpt2
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        eng = self._engine(cfg, params, "int8", mesh=mesh)
        specs = [census_specs.expected_serve_prefill(cfg.n_layer,
                                                     tp_axis="tp"),
                 census_specs.expected_serve_decode(cfg.n_layer,
                                                    tp_axis="tp"),
                 census_specs.expected_serve_verify(cfg.n_layer,
                                                    tp_axis="tp")]
        for (fn, args), spec in zip(self._cases(eng, params), specs):
            census = collective_census(fn, *args)
            assert census.diff(spec) == [], census.as_dict()

    def test_int8_single_device_collective_free(self, gpt2):
        cfg, params = gpt2
        eng = self._engine(cfg, params, "int8")
        for fn, args in self._cases(eng, params):
            assert collective_census(fn, *args).total() == 0

    def test_scaled_programs_donate_scales(self, gpt2):
        """The scale arrays update in place every step — they must be
        donated like the pools (no aliasable misses in any scaled
        program)."""
        cfg, params = gpt2
        eng = self._engine(cfg, params, "int8")
        for fn, args in self._cases(eng, params):
            rep = donation_report(fn, *args)
            assert rep.undonated_aliasable == [], rep.summary()


# ---------------------------------------------------------------------
# serve programs: dtype-promotion census per WEIGHT layout policy
# ---------------------------------------------------------------------

class TestWeightDtypeCensus:
    """The same census ladder for the packed-weight policies
    (serve/weight_quant.py): the int8/fp8 programs dequantize inside
    the serving matmuls (nn/layers.quantized_matmul upcasts the packed
    operand, dots in f32, applies the per-channel scale after), so no
    policy may introduce a half-accum dot or a silent x64 — the int8
    storage is NOT an accumulation dtype. The collective census is
    weight-policy-invariant too: under tp the w_scale leaves shard
    with their columns (augment_weight_specs) and the per-column
    multiply is rank-local, so the scaled programs carry exactly the
    f32 census and the single-device programs stay collective-free."""

    @pytest.fixture(scope="class")
    def gpt2(self):
        from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init

        cfg = GPT2Config.tiny(n_layer=2)
        return cfg, gpt2_init(jax.random.key(0), cfg)

    def _engine(self, cfg, params, weights_dtype, mesh=None, **kw):
        from quintnet_tpu.serve import ServeEngine, SpecConfig, gpt2_family

        kw.setdefault("max_slots", 3)
        kw.setdefault("block_size", 4)
        kw.setdefault("num_blocks", 24)
        kw.setdefault("max_seq_len", 32)
        kw.setdefault("spec", SpecConfig())
        return ServeEngine(gpt2_family(cfg), params, mesh=mesh,
                           weights_dtype=weights_dtype, **kw)

    # same program surface as the KV census — but invoked with the
    # engine's own (policy-packed) param tree
    _cases = TestServeDtypeCensus._cases

    @pytest.mark.parametrize("weights_dtype", [
        "f32", "bf16", "int8",
        pytest.param("fp8", marks=pytest.mark.skipif(
            not hasattr(jnp, "float8_e4m3fn"),
            reason="no float8_e4m3fn in this jax")),
        "fake_quant"])
    def test_dtype_census_clean_every_policy(self, gpt2, weights_dtype):
        cfg, params = gpt2
        eng = self._engine(cfg, params, weights_dtype)
        assert eng.weight_policy.name == weights_dtype
        for fn, args in self._cases(eng, eng.params):
            issues = dtype_report(fn, *args)
            assert issues == [], (weights_dtype,
                                  [i.detail for i in issues])

    def test_int8_tp_collective_census_unchanged(self, gpt2):
        """Packed weights add NO collectives under tp=2: the programs
        carry exactly the f32 census (row-parallel psums per block,
        nothing for the w_scale leaves)."""
        cfg, params = gpt2
        from quintnet_tpu.models.gpt2 import gpt2_to_tp_layout

        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        tp_params = gpt2_to_tp_layout(params, cfg, 2)
        eng = self._engine(cfg, tp_params, "int8", mesh=mesh)
        specs = [census_specs.expected_serve_prefill(cfg.n_layer,
                                                     tp_axis="tp"),
                 census_specs.expected_serve_decode(cfg.n_layer,
                                                    tp_axis="tp"),
                 census_specs.expected_serve_verify(cfg.n_layer,
                                                    tp_axis="tp")]
        for (fn, args), spec in zip(self._cases(eng, eng.params),
                                    specs):
            census = collective_census(fn, *args)
            assert census.diff(spec) == [], census.as_dict()

    def test_int8_single_device_collective_free(self, gpt2):
        cfg, params = gpt2
        eng = self._engine(cfg, params, "int8")
        for fn, args in self._cases(eng, eng.params):
            assert collective_census(fn, *args).total() == 0

    def test_packed_programs_keep_pool_donation(self, gpt2):
        """Packing the weights must not disturb the donation story:
        the KV pools still alias in place, and the packed w/w_scale
        leaves (read-only params) are correctly NOT aliasable."""
        cfg, params = gpt2
        eng = self._engine(cfg, params, "int8")
        for fn, args in self._cases(eng, eng.params):
            rep = donation_report(fn, *args)
            assert rep.undonated_aliasable == [], rep.summary()


# ---------------------------------------------------------------------
# recompile sentinel unit behaviour
# ---------------------------------------------------------------------

class TestRecompileSentinel:
    def test_counts_distinct_abstract_signatures(self):
        s = RecompileSentinel("t", jax.jit(lambda x: x + 1))
        s(jnp.zeros((2,)))
        s(jnp.ones((2,)))              # same signature
        assert s.compile_count == 1
        s(jnp.zeros((3,)))             # new shape
        assert s.compile_count == 2
        s(jnp.zeros((2,), jnp.int32))  # new dtype
        assert s.compile_count == 3

    def test_max_compiles_raises_before_dispatch_with_diff(self):
        calls = []
        s = RecompileSentinel("t", lambda x: calls.append(1),
                              max_compiles=1)
        s(jnp.zeros((2,)))
        with pytest.raises(RecompileError, match=r"float32\[2\]"):
            s(jnp.zeros((4,)))
        assert len(calls) == 1  # the violating call never dispatched

    def test_assert_compile_count(self):
        s = RecompileSentinel("t", lambda x: x)
        s(jnp.zeros((2,)))
        s.assert_compile_count(1)
        with pytest.raises(RecompileError, match="expected 2"):
            s.assert_compile_count(2)

    def test_trainer_step_is_wrapped(self):
        """Trainer wires its step through the sentinel: one lowering for
        a constant-shape loop, count visible for assertion."""
        from quintnet_tpu.train.trainer import Trainer

        cfg = Config.from_dict({
            "mesh_dim": [2], "mesh_name": ["dp"],
            "training": {"batch_size": 8, "epochs": 1}})
        trainer = Trainer(cfg, vit_model_spec(VIT))
        params, state = trainer.init_state()
        x = np.zeros((8, 14, 14, 1), np.float32)
        y = np.zeros((8,), np.int64)
        hist = trainer.fit(lambda ep: [(x, y)] * 2)
        assert len(hist.train_loss) == 1
        trainer.assert_compile_count(steps=1)


# ---------------------------------------------------------------------
# dtype report
# ---------------------------------------------------------------------

class TestDtypeReport:
    def test_flags_f64_upcast(self):
        def f(x):
            return jnp.sum(x.astype(jnp.float64))

        with jax.enable_x64(True):
            issues = dtype_report(f, jnp.zeros((4,), jnp.float32))
        assert any(i.kind == "f64-upcast" for i in issues), issues

    def test_flags_half_precision_accumulation(self):
        def f(a, b):
            return jnp.dot(a, b)  # bf16 x bf16 -> accumulates in bf16

        issues = dtype_report(f, jnp.zeros((4, 4), jnp.bfloat16),
                              jnp.zeros((4, 4), jnp.bfloat16))
        assert any(i.kind == "half-accum"
                   and i.primitive == "dot_general" for i in issues)

    def test_clean_with_f32_accumulation(self):
        """The mixed-precision recipe — bf16 operands, f32 accumulate —
        passes (and jnp.sum upcasts 16-bit reductions by itself)."""
        def f(a, b):
            return (jnp.dot(a, b, preferred_element_type=jnp.float32),
                    jnp.sum(a, axis=0))

        assert dtype_report(f, jnp.zeros((4, 4), jnp.bfloat16),
                            jnp.zeros((4, 4), jnp.bfloat16)) == []

    def test_train_step_is_clean(self):
        """The shipped dp train step neither upcasts to f64 nor
        accumulates in 16-bit."""
        _, _, step, params, state, batch = _train_setup([2], ["dp"])
        assert dtype_report(step, params, state, batch, 0) == []


# ---------------------------------------------------------------------
# donation report
# ---------------------------------------------------------------------

class TestDonationReport:
    def test_flags_undonated_train_state(self):
        opt = optax.sgd(1e-2)

        def step(p, s, g):
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s

        p = {"w": jnp.zeros((32, 32))}
        s = opt.init(p)
        rep = donation_report(jax.jit(step), p, s, p)
        assert rep.undonated_aliasable, rep.summary()

        rep2 = donation_report(jax.jit(step, donate_argnums=(0, 1)),
                               p, s, p)
        # donated params claim the only (32, 32) output slot; the grads
        # arg has nowhere left to alias -> nothing is flagged
        assert rep2.undonated_aliasable == [], rep2.summary()

    def test_parallel_train_step_donates_params_and_opt(self):
        """The inner jit of make_parallel_train_step donates params and
        opt_state — the auditor confirms no aliasable leaf outside the
        batch is left undonated."""
        _, _, step, params, state, batch = _train_setup([2], ["dp"])
        step(params, state, batch, 0)  # materialise compiled["fn"]
        # params/opt were donated by that call; rebuild fresh ones
        _, _, _, params, state, batch = _train_setup([2], ["dp"])


# ---------------------------------------------------------------------
# linter rules (synthetic snippets)
# ---------------------------------------------------------------------

SNIPPET_JIT_NP = """
import jax, numpy as np

@jax.jit
def f(x):
    y = np.random.normal(size=3)
    z = np.asarray(x)
    return x + y.sum() + z
"""

SNIPPET_SHARD_MAP = """
import numpy as np
from quintnet_tpu.core import collectives as cc

def local_step(p, b):
    noise = np.random.normal(size=3)
    return p + noise.sum()

step = cc.shard_map_fn(local_step, None, in_specs=(), out_specs=())
"""

SNIPPET_TRACER_BRANCH = """
import jax, jax.numpy as jnp

@jax.jit
def f(x):
    if jnp.any(x > 0):
        return x
    return -x
"""

SNIPPET_HOST_SYNC = """
def run(step_fn, params, batches):
    losses = []
    for b in batches:
        params, loss = step_fn(params, b)
        losses.append(float(loss))
    return losses
"""

SNIPPET_MUTABLE_DEFAULT = """
import numpy as np

def f(x, acc=[], table=np.zeros(4)):
    acc.append(x)
    return table
"""

SNIPPET_TIMING = """
import time

def bench(step, params, b):
    t0 = time.perf_counter()
    for _ in range(10):
        out = step(params, b)
    return time.perf_counter() - t0
"""

SNIPPET_TIMING_OK = """
import time, jax

def bench(step, params, b):
    t0 = time.perf_counter()
    for _ in range(10):
        out = step(params, b)
    jax.block_until_ready(out)
    return time.perf_counter() - t0
"""


class TestLintRules:
    def _rules(self, src):
        return {v.rule for v in lint_source(src, "x.py")}

    def test_np_and_rng_in_jit(self):
        rules = self._rules(SNIPPET_JIT_NP)
        assert "QT102" in rules  # np.random.normal
        assert "QT101" in rules  # np.asarray

    def test_function_passed_to_shard_map_is_traced(self):
        assert "QT102" in self._rules(SNIPPET_SHARD_MAP)

    def test_tracer_branch(self):
        assert "QT103" in self._rules(SNIPPET_TRACER_BRANCH)

    def test_host_sync_in_step_loop(self):
        assert "QT104" in self._rules(SNIPPET_HOST_SYNC)

    def test_float_outside_step_loop_not_flagged(self):
        src = "def f(x):\n    return float(x)\n"
        assert self._rules(src) == set()

    def test_mutable_and_array_defaults(self):
        vs = [v for v in lint_source(SNIPPET_MUTABLE_DEFAULT, "x.py")
              if v.rule == "QT105"]
        assert len(vs) == 2  # the list AND the np.zeros default

    def test_timing_without_sync_flagged_with_sync_clean(self):
        assert "QT106" in self._rules(SNIPPET_TIMING)
        assert "QT106" not in self._rules(SNIPPET_TIMING_OK)

    def test_pragma_suppresses_specific_rule(self):
        src = SNIPPET_HOST_SYNC.replace(
            "losses.append(float(loss))",
            "losses.append(float(loss))  # qtcheck: ok[QT104]")
        assert "QT104" not in self._rules(src)
        # a pragma for a DIFFERENT rule does not suppress
        src2 = SNIPPET_HOST_SYNC.replace(
            "losses.append(float(loss))",
            "losses.append(float(loss))  # qtcheck: ok[QT106]")
        assert "QT104" in self._rules(src2)

    def test_host_math_float_not_flagged(self):
        src = ("import numpy as np\n"
               "def run(step_fn, xs):\n"
               "    for x in xs:\n"
               "        step_fn(x)\n"
               "        y = float(np.exp(1.0))\n")
        assert self._rules(src) == set()


# ---------------------------------------------------------------------
# baseline gate (tier-1 CI): committed baseline == tree, exactly
# ---------------------------------------------------------------------

class TestBaselineGate:
    BASELINE = os.path.join(REPO, "tools", "qtcheck_baseline.json")

    def test_lint_baseline_gate(self):
        """THE gate: zero new violations, zero stale entries — the
        committed file cannot drift from the tree in either
        direction."""
        violations = lint_paths(["quintnet_tpu", "tools"], root=REPO)
        baseline = load_baseline(self.BASELINE)
        new, stale = compare_baseline(violations, baseline)
        assert new == [], "\n".join(new)
        assert stale == [], "\n".join(stale)

    def test_baseline_entries_all_carry_notes(self):
        """Every grandfathered violation must say WHY it is allowed —
        a baseline without justifications is just a mute button."""
        baseline = load_baseline(self.BASELINE)
        missing = [e for e in baseline["violations"] if not e.get("note")]
        assert missing == [], missing

    def test_cli_gate_passes(self):
        """The exact command CI documents:
        python -m quintnet_tpu.tools.qtcheck --baseline
        tools/qtcheck_baseline.json."""
        from quintnet_tpu.tools.qtcheck import main

        rc = main(["--baseline", self.BASELINE, "--root", REPO])
        assert rc == 0

    def test_cli_detects_new_violation(self, tmp_path, capsys):
        """A fresh footgun in a linted file fails the gate (exit 1) and
        is reported as NEW."""
        from quintnet_tpu.tools.qtcheck import main

        bad = tmp_path / "pkg"
        bad.mkdir()
        (bad / "mod.py").write_text(SNIPPET_JIT_NP)
        rc = main([str(bad), "--root", str(tmp_path),
                   "--baseline", self.BASELINE])
        assert rc == 1
        assert "NEW" in capsys.readouterr().out

    def test_stale_baseline_fails(self, tmp_path):
        """Fixing a legacy violation without regenerating the baseline
        fails the gate — the staleness half of the discipline."""
        import json

        stale_base = violations_to_baseline([])
        stale_base["violations"] = [{
            "rule": "QT106", "path": "nonexistent.py",
            "symbol": "gone", "count": 1, "line": 1}]
        p = tmp_path / "base.json"
        p.write_text(json.dumps(stale_base))
        clean = tmp_path / "pkg"
        clean.mkdir()
        (clean / "ok.py").write_text("x = 1\n")
        from quintnet_tpu.tools.qtcheck import main

        rc = main([str(clean), "--root", str(tmp_path),
                   "--baseline", str(p)])
        assert rc == 1
