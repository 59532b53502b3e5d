"""tools/ft_run.py must never rot unexecuted: the fast suite runs the
supervisor end-to-end (CPU, tiny run, one injected kill + relaunch) and
checks the JSON goodput contract.
"""

import json
import os
import subprocess
import sys

REPO = os.path.join(os.path.dirname(__file__), "..")

FT_METRIC = "ft_goodput"


def test_ft_run_smoke_survives_injected_kill(tmp_path):
    """One SIGTERM kill mid-run: the supervisor relaunches, the child
    resumes from the emergency snapshot, the run completes, and the
    one-line JSON record carries the acceptance fields."""
    out_file = str(tmp_path / "ft.json")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ft_run.py"),
         "--run-dir", str(tmp_path / "run"),
         "--epochs", "2", "--samples", "32", "--batch-size", "16",
         "--save-every", "1", "--kill-at", "3", "--kill-mode", "sigterm",
         "--out", out_file],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == FT_METRIC
    assert rec["rc"] == 0
    assert rec["unit"] == "fraction"
    ex = rec["extras"]
    assert ex["completed"] is True
    assert ex["restarts"] == 1
    assert ex["faults_survived"] == 1
    # 2 epochs x 2 steps: the graceful kill at step 3 checkpoints step 3,
    # so the relaunch replays only step 4 — no useful work lost
    assert ex["useful_steps"] == 4
    assert ex["lost_steps"] == 0
    assert ex["attempts"] == 2
    assert 0 < rec["value"] <= 1
    # --out appends to an artifacts-style JSON list
    assert json.load(open(out_file)) == [rec]
