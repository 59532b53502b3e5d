"""The six per-layer readers of ``setup_s`` (PR 35) on a hand-made
start-up record, values by hand; a program with no record; the
manifest's eight new entries. CPU only: nothing here is a device
number."""

import os

import pytest

from benchmarks.lib import harness, startup

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ("setup_import_s", "setup_build_s.serve", "setup_build_s.train",
       "setup_warmup_s.serve", "setup_warmup_s.train",
       "setup_trace_lower_s", "setup_compile_or_load_s",
       "setup_cache_misses")


class _Record:
    """What a reader needs of obs.recorder.StartupRecord."""

    def __init__(self, spans, unattributed):
        self._spans, self._unattributed = spans, unattributed

    def snapshot(self):
        return {"spans": [dict(s) for s in self._spans], "dropped": 0,
                "unattributed": dict(self._unattributed), "totals": {}}


def _span(i, name, t0, t1, exclusive, parent=None, **attrs):
    return {"id": i, "name": "qn.setup." + name, "t0": t0, "t1": t1,
            "parent": parent, "exclusive_s": exclusive, "attrs": attrs}


# import 2.5 s; a strategy built inside a trainer's construction (1.0 s
# of which 0.25 the strategy's), an engine built in 0.75; a warm-up of
# 6.0 s with two programs (2.0 and 3.5 s), a later one still open
SPANS = [
    _span(0, "import", 1.0, 3.5, 2.5),
    _span(1, "build", 10.0, 11.0, 0.75, compile_or_load_s=0.125,
          programs=2, cache_hits=2),
    _span(2, "build", 10.25, 10.5, 0.25, parent=1, trace_s=0.0625),
    _span(3, "build", 12.0, 12.75, 0.75, trace_s=0.125, lower_s=0.25),
    _span(4, "warmup", 13.0, 19.0, 0.5, trace_s=0.0625),
    _span(5, "warmup/jit_serve_prefill_b16", 13.25, 15.25, 2.0, parent=4,
          trace_s=0.5, lower_s=0.25, compile_or_load_s=1.0, programs=1,
          cache_misses=1),
    _span(6, "warmup/jit_serve_decode", 15.5, 19.0, 3.5, parent=4,
          trace_s=1.0, lower_s=0.5, compile_or_load_s=1.5, programs=1,
          cache_hits=1),
    _span(7, "warmup", 20.0, None, 0.0),
]
UNATTRIBUTED = {"trace_s": 0.25, "lower_s": 0.5, "compile_or_load_s": 2.0,
                "programs": 9, "cache_hits": 7, "cache_misses": 2}
BY_HAND = {
    "setup_import_s": 2.5,
    "setup_build_s.serve": 0.75 + 0.25 + 0.75,
    "setup_build_s.train": 0.75 + 0.25 + 0.75,
    "setup_warmup_s.serve": 0.5 + 2.0 + 3.5,
    "setup_warmup_s.train": 0.5 + 2.0 + 3.5,
    # trace: .0625 + .125 + .0625 + .5 + 1. + .25; lower: .25 + .25 + .5 + .5
    "setup_trace_lower_s": 2.0 + 1.5,
    "setup_compile_or_load_s": 0.125 + 1.0 + 1.5 + 2.0,
    "setup_cache_misses": 1 + 2,
}


@pytest.fixture
def bench():
    return harness.Bench(ROOT)


@pytest.fixture
def record(monkeypatch):
    from quintnet_tpu.obs import recorder

    rec = _Record(SPANS, UNATTRIBUTED)
    monkeypatch.setattr(recorder, "startup", lambda: rec, raising=False)
    return rec


@pytest.mark.parametrize("name", NEW)
def test_a_reader_on_a_hand_made_record(bench, record, name):
    assert bench.reader(name)({}) == pytest.approx(BY_HAND[name], abs=1e-12)


@pytest.mark.parametrize("name", NEW)
def test_a_program_with_no_record_reads_none(bench, monkeypatch, name):
    """The parent of PR 35: ``obs.recorder`` has no ``startup``."""
    from quintnet_tpu.obs import recorder

    monkeypatch.delattr(recorder, "startup", raising=False)
    assert startup.find_record() is None
    assert bench.reader(name)({}) is None


def test_a_record_with_no_such_span_reads_none(bench, monkeypatch):
    """A span metric with nothing to read is left out of the line (a
    counter over an empty record is a true zero)."""
    from quintnet_tpu.obs import recorder

    rec = _Record([s for s in SPANS if s["name"].endswith(".import")], {})
    monkeypatch.setattr(recorder, "startup", lambda: rec, raising=False)
    assert bench.reader("setup_import_s")({}) == 2.5
    assert bench.reader("setup_build_s.serve")({}) is None
    assert bench.reader("setup_warmup_s.train")({}) is None
    assert bench.reader("setup_cache_misses")({}) == 0


def test_an_open_span_is_not_read(record):
    """The warm-up still open at reading time (``t1`` None) is not a
    span yet."""
    spans = startup.closed_spans(record.snapshot(), "warmup", True)
    assert [s["id"] for s in spans] == [4, 5, 6]


def test_the_manifest_appends_the_eight_and_they_resolve(bench):
    entries = bench.manifest["per_layer"]
    assert tuple(m["name"] for m in entries[-len(NEW):]) == NEW
    cells = {w["name"] for w in bench.manifest["workloads"]}
    for m in entries[-len(NEW):]:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] in ("program_span", "program_counter")
        assert set(m["workloads"]) <= cells and m["workloads"]
        reader = m["name"].split(".", 1)[0]
        assert os.path.isfile(bench.path("layer_metrics", reader + ".py"))
        assert callable(bench.reader(m["name"]))
        kind = m["name"].partition(".")[2]
        if kind:
            assert all(f".{kind}-" in c for c in m["workloads"]), m
            assert {c for c in cells if f".{kind}-" in c} == set(
                m["workloads"])
        else:
            assert set(m["workloads"]) == cells
    # every cell reports six of them
    for cell in cells:
        got = [m["name"] for m in bench.cell(cell).per_layer
               if m["moves"] == "setup_s"]
        assert len(got) == 6, (cell, got)


def test_the_readers_on_a_real_engines_record():
    """The program's own record through the readers: a tiny engine
    built and warmed up here."""
    import jax

    from quintnet_tpu.models.gpt2 import GPT2Config, gpt2_init
    from quintnet_tpu.obs.recorder import startup as program_record
    from quintnet_tpu.serve import ServeEngine, gpt2_family

    cfg = GPT2Config.tiny(n_layer=2)
    before = program_record().snapshot()
    eng = ServeEngine(gpt2_family(cfg), gpt2_init(jax.random.key(0), cfg),
                      max_slots=2, block_size=4, num_blocks=32,
                      max_seq_len=48)
    eng.warmup()
    bench = harness.Bench(ROOT)
    after = program_record().snapshot()
    new = [s for s in after["spans"] if s["id"] >= len(before["spans"])
           + before["dropped"]]
    assert [s["name"] for s in new][:2] == ["qn.setup.build",
                                            "qn.setup.warmup"]
    assert bench.reader("setup_import_s")({}) > 0
    assert bench.reader("setup_build_s.serve")({}) >= new[0]["exclusive_s"]
    warm = bench.reader("setup_warmup_s.serve")({})
    assert warm >= new[1]["t1"] - new[1]["t0"] > 0
    parts = (bench.reader("setup_trace_lower_s")({})
             + bench.reader("setup_compile_or_load_s")({}))
    assert parts > 0
