"""bench.last_known_result: a plain scanner of committed artifacts.

No run path of bench.py calls it any more — a benchmark that finds no
chip fails instead of printing an old number (PR 21). It stays, with
these tests, only while the serve/fleet/ft bench tests use it to find
their committed records (ROADMAP D7/D8). The repo's own committed
artifacts are the fixture.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import bench  # noqa: E402


@pytest.mark.fast
def test_last_known_from_committed_artifacts():
    """artifacts/loss_chunk_r04.json holds a real headline number; the
    scanner must surface it with provenance."""
    last = bench.last_known_result()
    assert last is not None
    assert last["stale"] is True
    assert last["metric"] == bench.HEADLINE_METRIC
    assert last["value"] > 0
    assert last["source"].startswith("artifacts")
    assert last["as_of"]  # commit date or mtime, never empty


@pytest.mark.fast
def test_last_known_prefers_default_config_record(tmp_path):
    """Among same-age records, the one measured under the committed
    baseline config (extras.baseline set) wins, not the fastest."""
    recs = [
        {"metric": bench.HEADLINE_METRIC, "value": 250.0, "rc": 0,
         "unit": "samples/s/chip", "vs_baseline": 1.0,
         "extras": {"baseline": None, "batch_per_chip": 32}},
        {"metric": bench.HEADLINE_METRIC, "value": 188.0, "rc": 0,
         "unit": "samples/s/chip", "vs_baseline": 1.037,
         "extras": {"baseline": 181.3, "batch_per_chip": 8, "mfu": 0.36}},
    ]
    (tmp_path / "sweep.json").write_text(json.dumps(recs))
    last = bench.last_known_result(art_dir=str(tmp_path))
    assert last["value"] == 188.0
    assert last["mfu"] == 0.36


@pytest.mark.fast
def test_last_known_skips_failed_records(tmp_path):
    recs = [
        {"metric": "backend_unavailable", "value": 0.0, "rc": 0},
        {"metric": bench.HEADLINE_METRIC, "value": 100.0, "rc": 1},
    ]
    (tmp_path / "bad.json").write_text(json.dumps(recs))
    (tmp_path / "junk.json").write_text("not json{")
    assert bench.last_known_result(art_dir=str(tmp_path)) is None
