"""Start-up on the record (quintnet_tpu/obs/spans.py SetupPhases + the
compile listener, obs/recorder.py StartupRecord): the ``qn.setup.*``
spans from import to the first step, what JAX traced, lowered and
compiled or loaded charged where the work happened — to a span, to the
engine step that recompiled, else ``unattributed`` — and all of it
INERT: tokens, the compile census and the blocking reads a step are the
parent tree's.
"""

import inspect
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
from quintnet_tpu.obs import recorder
from quintnet_tpu.obs import spans
from quintnet_tpu.obs.recorder import StartupRecord
from quintnet_tpu.serve import ServeEngine, gpt2_family

CFG = GPT2Config.tiny(n_layer=2)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILED = ("trace_s", "lower_s", "compile_or_load_s")

# sampled tokens (temperature 0.8, top-k 5, key 100 + i) of the golden
# prompts from the parent tree (commit fc43095), and the blocking reads
# of each of its steps: the record changes neither
GOLDEN_SAMPLED = [
    [14, 59, 95, 64, 95, 88, 64, 95], [15, 15, 64, 64, 77, 15, 95, 64],
    [117, 64, 15, 77, 77, 95, 59, 15], [88, 59, 59, 119, 64, 14, 103, 14],
    [15, 95, 115, 64, 95, 117, 95, 117]]
GOLDEN_HOST_SYNCS = [6, 2, 2, 2, 2, 2, 2, 6, 2, 2, 2, 2, 2, 2, 4, 2, 2, 2,
                     2, 2, 2]


@pytest.fixture(scope="module")
def params():
    return gpt2_init(jax.random.key(0), CFG)


@pytest.fixture
def record(monkeypatch):
    """A start-up record of this test's own in the process's place."""
    rec = StartupRecord()
    monkeypatch.setattr(recorder, "_STARTUP", rec)
    return rec


@pytest.fixture
def compile_cache(tmp_path):
    """The persistent compile cache on, in an empty directory, taking
    every program however small: each compile is then a hit or a
    miss."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1,
            "jax_enable_compilation_cache": True}
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _engine(params, **kw):
    kwargs = dict(max_slots=2, block_size=4, num_blocks=32, max_seq_len=48)
    kwargs.update(kw)
    return ServeEngine(gpt2_family(CFG), params, **kwargs)


def _golden_prompts():
    g = np.random.default_rng(25)
    return [np.asarray(g.integers(0, CFG.vocab_size, (t,)), np.int32)
            for t in (5, 9, 3, 7, 12)]


def _named(snapshot, name):
    return [s for s in snapshot["spans"] if s["name"] == name]


class _TickClock:
    """Moves by one tick at every reading."""

    def __init__(self, tick=0.5):
        self.t, self.tick = 0.0, tick

    def __call__(self):
        self.t += self.tick
        return self.t


# ---------------------------------------------------------------------
# the mechanism and the record
# ---------------------------------------------------------------------

def test_spans_nest_and_exclusive_times_sum_to_the_span(record):
    """A parent's exclusive time plus its children's ``t1 - t0`` is its
    own ``t1 - t0`` — exactly, on a clock that ticks at every reading —
    and every span names the one that caused it."""
    phases = spans.SetupPhases(clock=_TickClock())
    with phases.phase("build", who="trainer") as outer:
        with phases.phase("build"):
            pass
        with phases.phase("warmup"):
            with phases.phase("warmup/jit_local_step"):
                pass
    got = record.snapshot()["spans"]
    assert [s["name"] for s in got] == [
        "qn.setup.build", "qn.setup.build", "qn.setup.warmup",
        "qn.setup.warmup/jit_local_step"]
    assert [s["parent"] for s in got] == [None, 0, 0, 2]
    assert got[0]["attrs"] == {"who": "trainer"} and outer["id"] == 0
    by_id = {s["id"]: s for s in got}
    for s in got:
        kids = [k for k in got if k["parent"] == s["id"]]
        assert s["t1"] - s["t0"] == pytest.approx(
            s["exclusive_s"] + sum(k["t1"] - k["t0"] for k in kids))
        if s["parent"] is not None:
            p = by_id[s["parent"]]
            assert p["t0"] <= s["t0"] and s["t1"] <= p["t1"]
    # one tick between two readings: the innermost was open one
    # stretch, the outermost three (before, between and after its
    # children)
    assert got[3]["exclusive_s"] == 0.5 and got[0]["exclusive_s"] == 1.5


def test_the_two_vocabularies_share_one_mechanism():
    assert issubclass(spans.StepPhases, spans.Phases)
    assert issubclass(spans.SetupPhases, spans.Phases)
    assert spans.StepPhases.phase is spans.SetupPhases.phase
    assert spans.StepPhases.prefix == "qn.serve."
    assert spans.SetupPhases.prefix == "qn.setup."
    assert spans.program_name("serve_decode") == "jit_serve_decode"
    assert spans.program_name("jit(serve_prefill_b16)") \
        == "jit_serve_prefill_b16"


def test_a_span_is_on_the_record_while_it_is_open(record):
    with spans.setup_phase("build"):
        (open_,) = record.snapshot()["spans"]
        assert open_["t1"] is None and open_["name"] == "qn.setup.build"
    (closed,) = record.snapshot()["spans"]
    assert closed["t1"] >= closed["t0"]


def test_the_cap_counts_what_fell_off(monkeypatch):
    rec = StartupRecord(capacity=3)
    monkeypatch.setattr(recorder, "_STARTUP", rec)
    for i in range(5):
        with spans.setup_phase("build", i=i):
            pass
    snap = rec.snapshot()
    assert rec.dropped == snap["dropped"] == 2 and len(rec) == 3
    assert [s["attrs"]["i"] for s in snap["spans"]] == [2, 3, 4]
    assert [s["id"] for s in snap["spans"]] == [2, 3, 4]   # oldest first
    with pytest.raises(ValueError):
        StartupRecord(capacity=0)


def test_the_package_stamps_its_import_on_the_process_record():
    """``qn.setup.import`` is the first span of the process's record,
    closed, with JAX's import inside it where this package was imported
    first."""
    first = recorder.startup().snapshot()["spans"][0]
    if recorder.startup().dropped == 0:
        assert first["name"] == "qn.setup.import" and first["id"] == 0
        assert first["t1"] > first["t0"] and first["parent"] is None
    out = subprocess.run(
        [sys.executable, "-c",
         "import time; t0 = time.perf_counter(); import quintnet_tpu; "
         "t1 = time.perf_counter(); import json; "
         "from quintnet_tpu.obs.recorder import startup; "
         "print(json.dumps([t0, t1, startup().snapshot()['spans']]))"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    t0, t1, got = json.loads(out.stdout.strip().splitlines()[-1])
    (imp,) = got
    assert imp["name"] == "qn.setup.import"
    assert t0 <= imp["t0"] < imp["t1"] <= t1
    # all but the interpreter's own share of the statement
    assert imp["t1"] - imp["t0"] > 0.9 * (t1 - t0) - 0.05


def test_the_record_is_jax_free():
    """obs/recorder.py loaded alone by its path imports no jax: a
    reader that holds no engine finds the record without the
    backend."""
    path = os.path.join(REPO, "quintnet_tpu", "obs", "recorder.py")
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('rec', {path!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['rec'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "r = mod.startup(); r.open('qn.setup.import', 1.0, t1=3.5)\n"
        "assert r.snapshot()['spans'][0]['exclusive_s'] == 2.5\n"
        "assert not any(m == 'jax' or m.startswith('jax.')"
        " for m in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True)
    assert out.returncode == 0, out.stderr


# ---------------------------------------------------------------------
# an engine: build, warm-up by program, the step that recompiled
# ---------------------------------------------------------------------

def test_warmup_yields_one_child_a_program_with_its_compile_split(
        params, record, compile_cache):
    eng = _engine(params, prefill_bucket_sizes=(16, 48))
    snap = record.snapshot()
    (build,) = _named(snap, "qn.setup.build")
    assert build["parent"] is None and build["t1"] is not None
    eng.warmup()
    snap = record.snapshot()
    (warm,) = _named(snap, "qn.setup.warmup")
    kids = [s for s in snap["spans"] if s["parent"] == warm["id"]]
    assert [k["name"] for k in kids] == [
        "qn.setup.warmup/jit_serve_prefill_b16",
        "qn.setup.warmup/jit_serve_prefill_b48",
        "qn.setup.warmup/jit_serve_decode"]
    assert sorted(k["name"].split("/")[1] for k in kids) == [
        "jit_" + n for n in eng.recorder.static["programs"]]
    for k in kids:
        a = k["attrs"]
        assert all(a[key] > 0 for key in COMPILED), a
        assert a["programs"] == 1
        assert a.get("cache_hits", 0) + a.get("cache_misses", 0) == 1
        # what JAX did for the program is no more than its child span;
        # the rest of the span is the dispatch of the first run
        assert sum(a[key] for key in COMPILED) <= k["t1"] - k["t0"]
        assert warm["t0"] <= k["t0"] and k["t1"] <= warm["t1"]
    assert warm["t1"] - warm["t0"] == pytest.approx(
        warm["exclusive_s"] + sum(k["t1"] - k["t0"] for k in kids))
    # an empty cache: this process compiled, and says so
    assert sum(k["attrs"].get("cache_misses", 0) for k in kids) == 3


def test_a_second_warmup_of_the_same_engine_compiles_nothing(params, record):
    eng = _engine(params, prefill_bucket_sizes=(48,))
    eng.warmup()
    eng.warmup()
    first, second = _named(record.snapshot(), "qn.setup.warmup")
    spans_ = record.snapshot()["spans"]
    for warm, compiled in ((first, True), (second, False)):
        kids = [s for s in spans_ if s["parent"] == warm["id"]]
        assert [k["name"] for k in kids] == [
            "qn.setup.warmup/jit_serve_prefill_b48",
            "qn.setup.warmup/jit_serve_decode"]
        for k in kids:
            assert bool(k["attrs"]) is compiled, k
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}


def test_a_second_engine_appends_to_the_record(params, record):
    one = _engine(params, prefill_bucket_sizes=(48,))
    one.warmup()
    n = len(record)
    two = _engine(params, max_slots=3, prefill_bucket_sizes=(48,))
    two.warmup()
    names = [s["name"] for s in record.snapshot()["spans"]]
    assert names[:n] == names[n:] and n == 4
    assert names.count("qn.setup.build") == 2
    assert record.dropped == 0
    del one, two


def test_the_pack_update_program_is_a_child_of_warmup_too(params, record):
    eng = _engine(params, adapters=True)
    eng.warmup()
    snap = record.snapshot()
    (warm,) = _named(snap, "qn.setup.warmup")
    kids = [s["name"] for s in snap["spans"] if s["parent"] == warm["id"]]
    assert kids[0] == "qn.setup.warmup/jit_serve_pack_update"
    assert all(k.startswith("qn.setup.warmup/jit_serve_") for k in kids)
    assert len(kids) == 1 + len(eng.recorder.static["programs"])


def test_a_step_that_compiles_says_so_and_the_next_does_not(params, record):
    """A prefill bucket's first call outside ``warmup()`` compiles
    inside a served step: that step's record names the program, the
    next carries nothing, and nothing of it is ``unattributed``."""
    eng = _engine(params, prefill_bucket_sizes=(16, 48))
    for sentinel, args in eng._warmup_calls():      # all but bucket 48
        if sentinel.fn.__name__ != "serve_prefill_b48":
            *pools, _t, _k = sentinel(*args)
            eng.pool.update(*eng._pop_moe(pools, note=False))
    eng.submit(_golden_prompts()[4][:5], 4)         # bucket 16: warm
    eng.step()
    assert "compiled" not in eng.recorder.last()["attrs"]
    long = np.arange(20, dtype=np.int32) % CFG.vocab_size
    eng.submit(long, 4)                             # bucket 48: cold
    before = record.snapshot()
    eng.step()
    after = record.snapshot()
    rec = eng.recorder.last()
    assert rec["admitted"] == 1
    assert "jit_serve_prefill_b48" in rec["attrs"]["compiled"]
    # charged to the step, so not to ``unattributed``; in ``totals``
    assert after["unattributed"] == before["unattributed"]
    assert (after["totals"]["programs"] - before["totals"]["programs"]
            == len(rec["attrs"]["compiled"]))
    assert after["totals"]["compile_or_load_s"] \
        > before["totals"]["compile_or_load_s"]
    assert len(after["spans"]) == len(before["spans"])
    # the blocking reads of the step are what they were: two an
    # admission, two for the decode
    assert rec["host_syncs"] == 2 * 1 + 2
    eng.step()
    assert "compiled" not in eng.recorder.last()["attrs"]
    eng.run()
    assert not any("compiled" in r["attrs"]
                   for r in eng.recorder.snapshot()[2:])


def test_trace_view_shows_compiled_in_a_steps_args(record):
    from tools.trace_view import chrome_trace, validate_chrome_trace

    ring = [{"step": 1, "t0": 1.0, "t1": 1.5, "attrs": {}},
            {"step": 2, "t0": 2.0, "t1": 9.0,
             "attrs": {"compiled": ["jit_serve_prefill_b48"]}}]
    with spans.setup_phase("warmup"), \
            spans.warmup_program("serve_decode") as child:
        child["attrs"]["compile_or_load_s"] = 0.25
    with spans.setup_phase("build"):
        trace = chrome_trace(ring, startup=record.snapshot())
    assert validate_chrome_trace(trace) > 0
    steps = [e for e in trace["traceEvents"]
             if e["ph"] == "X" and e.get("cat") == "engine"]
    assert "compiled" not in steps[0]["args"]
    assert steps[1]["args"]["compiled"] == ["jit_serve_prefill_b48"]
    assert steps[1]["name"] == "step 2 (compiled jit_serve_prefill_b48)"
    setup = {e["name"]: e for e in trace["traceEvents"]
             if e.get("cat") == "setup"}
    assert set(setup) == {"qn.setup.warmup",
                          "qn.setup.warmup/jit_serve_decode"}   # closed
    assert setup["qn.setup.warmup/jit_serve_decode"]["args"][
        "compile_or_load_s"] == 0.25


# ---------------------------------------------------------------------
# the listener
# ---------------------------------------------------------------------

def test_a_compile_outside_every_span_and_step_is_unattributed(record):
    def startup_probe_unattributed(x):
        return jnp.sin(x) @ x

    jax.jit(startup_probe_unattributed)(jnp.ones((4, 4))).block_until_ready()
    snap = record.snapshot()
    assert snap["spans"] == []
    assert snap["unattributed"]["programs"] >= 1
    assert all(snap["unattributed"][k] > 0 for k in COMPILED)
    assert snap["totals"] == snap["unattributed"]


def test_a_trace_inside_a_trace_is_counted_once(record):
    """``jnp.sin`` is itself jitted: its trace reports a duration
    before the outer function's does. The span is charged the
    outermost trace alone, so what it holds is never more than the
    span."""
    def startup_probe_nested(x):
        for _ in range(20):
            x = jnp.sin(jnp.linalg.norm(x) + x)
        return x

    with spans.setup_phase("warmup"), \
            spans.warmup_program("startup_probe_nested") as child:
        jax.jit(startup_probe_nested)(jnp.ones((4, 4)))
    a = child["attrs"]
    assert child["name"] == "qn.setup.warmup/jit_startup_probe_nested"
    assert a["programs"] == 1
    assert 0 < sum(a[k] for k in COMPILED) <= child["t1"] - child["t0"]


def test_a_span_is_charged_its_own_threads_compiles_alone(record):
    def startup_probe_other_thread(x):
        return jnp.cos(x) * 3

    def work():
        jax.jit(startup_probe_other_thread)(jnp.ones((5,)))

    with spans.setup_phase("build") as span:
        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert span["attrs"] == {}
    assert record.snapshot()["unattributed"]["programs"] >= 1


# ---------------------------------------------------------------------
# a trainer
# ---------------------------------------------------------------------

def _trainer():
    from quintnet_tpu.core.config import Config
    from quintnet_tpu.models.vit import ViTConfig, vit_model_spec
    from quintnet_tpu.train.trainer import Trainer

    cfg = Config.from_dict({
        "mesh_dim": [2], "mesh_name": ["dp"],
        "training": {"batch_size": 8, "epochs": 1, "optimizer": "adam",
                     "log_every": 0},
    })
    model = vit_model_spec(ViTConfig(
        image_size=28, patch_size=7, in_channels=1, hidden_dim=16, depth=2,
        num_heads=2, num_classes=10))
    return cfg, model, Trainer(cfg, model, task_type="classification",
                               log_fn=lambda s: None)


def test_the_trainers_first_step_is_its_warmup_and_its_second_is_not(
        record):
    from quintnet_tpu.data import load_mnist

    cfg, model, trainer = _trainer()
    names = [s["name"] for s in record.snapshot()["spans"]]
    # Trainer.__init__ with get_strategy inside it
    assert names == ["qn.setup.build", "qn.setup.build"]
    assert record.snapshot()["spans"][1]["parent"] == 0
    strategy = trainer.strategy
    params = strategy.shard_params(model, model.init(jax.random.key(0)))
    opt_state = strategy.init_opt_state(model, trainer.optimizer, params)
    assert [s["name"] for s in record.snapshot()["spans"]][2:] == [
        "qn.setup.build"]
    x, y = load_mnist(split="train", synthetic_size=16)

    def step(params, opt_state, i):
        b = strategy.shard_batch((jnp.asarray(x[:8]), jnp.asarray(y[:8])),
                                 model)
        return trainer.step_fn(params, opt_state, b, i)

    params, opt_state, _ = step(params, opt_state, 0)
    snap = record.snapshot()
    warm, child = snap["spans"][3:]
    assert warm["name"] == "qn.setup.warmup" and warm["parent"] is None
    assert child["name"] == "qn.setup.warmup/jit_local_step"
    assert child["parent"] == warm["id"]
    assert all(child["attrs"][k] > 0 for k in COMPILED)
    assert child["attrs"]["programs"] == 1
    assert sum(child["attrs"][k] for k in COMPILED) \
        <= child["t1"] - child["t0"]
    params, opt_state, loss = step(params, opt_state, 1)
    assert np.isfinite(float(loss))
    assert len(record.snapshot()["spans"]) == 5     # no span: it is warm
    trainer.assert_compile_count(steps=1)


def test_the_eval_steps_first_call_is_a_warmup_too(record):
    from quintnet_tpu.data import load_mnist

    cfg, model, trainer = _trainer()
    params = trainer.strategy.shard_params(
        model, model.init(jax.random.key(0)))
    x, y = load_mnist(split="train", synthetic_size=16)
    n = len(record)
    out = trainer.evaluate(params, [(x[:8], y[:8]), (x[8:], y[8:])])
    assert np.isfinite(out["loss"])
    new = record.snapshot()["spans"][n:]
    assert [s["name"] for s in new] == [
        "qn.setup.warmup", "qn.setup.warmup/jit_eval_step"]
    assert new[1]["attrs"]["programs"] == 1
    trainer.evaluate(params, [(x[:8], y[:8])])
    assert len(record) == n + 2


# ---------------------------------------------------------------------
# inertness, as PR 25 held it
# ---------------------------------------------------------------------

def test_sampled_tokens_and_blocking_reads_are_the_parents(params):
    eng = _engine(params, temperature=0.8, top_k=5)
    prompts = _golden_prompts()
    rids = [eng.submit(p, 8, key=jax.random.key(100 + i))
            for i, p in enumerate(prompts)]
    eng.run()
    got = [[int(t) for t in eng.result(r)[len(p):]]
           for r, p in zip(rids, prompts)]
    assert got == GOLDEN_SAMPLED
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    ring = eng.recorder.snapshot()
    assert [r["host_syncs"] for r in ring] == GOLDEN_HOST_SYNCS
    # cold programs here: the two steps that met them say so
    compiled = [r["attrs"].get("compiled", []) for r in ring]
    assert sorted(sum(compiled, [])) == ["jit_serve_decode",
                                         "jit_serve_prefill_b16"]
    assert all(not c for c in compiled[1:])


def test_the_record_adds_no_program_to_a_warm_engine(params):
    """The compile census: after ``warmup()`` a served trace compiles
    or loads NOTHING — by the engine's sentinels and by the process's
    own count of backend compiles."""
    eng = _engine(params)
    eng.warmup()
    jnp.zeros((3,)).block_until_ready()
    programs = recorder.startup().totals.get("programs", 0)
    assert programs > 0
    for p in _golden_prompts():
        eng.submit(p, 6)
    eng.run()
    assert recorder.startup().totals.get("programs", 0) == programs
    assert eng.compile_stats() == {"prefill": 3, "decode": 1}  # 16, 32, 48
    assert not any("compiled" in r["attrs"]
                   for r in eng.recorder.snapshot())


def test_setup_span_keeps_the_signature_it_wraps():
    sig = inspect.signature(ServeEngine.__init__)
    assert list(sig.parameters)[:3] == ["self", "family", "params"]
    assert "prefill_bucket_sizes" in sig.parameters
    assert ServeEngine.warmup.__doc__.startswith("Compile EVERY")
