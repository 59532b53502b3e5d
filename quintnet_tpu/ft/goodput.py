"""Goodput accounting: how much of the wall clock bought training.

Terms (Google's goodput papers use the same decomposition):

- **useful step time** — time spent computing steps that SURVIVE into
  the final model. With step-granular resume the surviving steps are
  exactly ``0..final_step``; steps executed after the last checkpoint
  before a kill are re-run by the next attempt and count as lost.
- **checkpoint overhead** — host-blocking time inside save calls (the
  async write itself overlaps compute; only the snapshot/dispatch and
  the final barrier block).
- **restore overhead** — time restoring state at (re)start.

One meter lives per PROCESS (attempt); the supervisor in
tools/ft_run.py merges the per-attempt reports into the run-level
goodput record it prints (schema: docs/fault_tolerance.md). Step timing is wall-clock around the loop —
under JAX async dispatch an individual step's host time is not its
device time, but the SUM over a window is honest (the loop cannot run
ahead of the device by more than ``training.sync_every`` steps).
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Optional


class GoodputMeter:
    def __init__(self, *, emit_markers: bool = False):
        # emit_markers: print a one-line JSON marker at resume so a
        # supervisor can account work lost by HARD kills (the attempt
        # never lives to emit its report; the supervisor reconstructs
        # steps_run = kill_step - resumed_at from the markers)
        self.emit_markers = emit_markers
        self.t_start = time.time()
        self.resumed_at: Optional[int] = None  # global step we continued from
        self.reached: int = 0                  # last completed global step
        self.steps_run: int = 0
        self.save_s: float = 0.0               # host-blocking save time
        self.restore_s: float = 0.0
        self.fallback_steps: int = 0           # corrupt ckpts skipped on resume
        self._last_result = None               # device value of the last step

    # -- hooks called by Trainer.fit -----------------------------------
    def on_resume(self, global_step: int, restore_s: float,
                  fallback_steps: int = 0) -> None:
        self.resumed_at = global_step
        self.reached = max(self.reached, global_step)
        self.restore_s += restore_s
        self.fallback_steps += fallback_steps
        if self.emit_markers:
            print(json.dumps({"ft_start": {"resumed_at": global_step}}),
                  flush=True)

    def on_step(self, global_step: int, result=None) -> None:
        """``result``: any device value produced by the step (the loss).
        Kept (not synced!) so :meth:`report` can block on the final
        step's device work before reading the wall clock — without it,
        under async dispatch the meter would close its window while the
        last ``training.sync_every`` steps are still executing and
        report dispatch time as step time."""
        self.steps_run += 1
        self.reached = global_step
        if result is not None:
            self._last_result = result

    def on_save(self, blocking_s: float) -> None:
        self.save_s += blocking_s

    # -- reporting -----------------------------------------------------
    def report(self, *, completed: bool) -> Dict[str, Any]:
        if self._last_result is not None:
            # drain in-flight device work so wall_s covers what the
            # device DID, not what the host dispatched
            import jax

            jax.block_until_ready(self._last_result)
            self._last_result = None
        wall = time.time() - self.t_start
        return {
            "resumed_at": self.resumed_at or 0,
            "reached": self.reached,
            "steps_run": self.steps_run,
            "wall_s": round(wall, 4),
            "save_blocking_s": round(self.save_s, 4),
            "restore_s": round(self.restore_s, 4),
            "fallback_steps": self.fallback_steps,
            "completed": bool(completed),
        }

    def emit(self, *, completed: bool) -> None:
        """One marker line on stdout for the supervisor to collect."""
        print(json.dumps({"ft_attempt": self.report(completed=completed)}),
              flush=True)


def aggregate(attempts, *, wall_s: float,
              final_step: Optional[int] = None) -> Dict[str, Any]:
    """Merge per-attempt reports into the run-level goodput record.

    ``attempts`` is the chronological list of ``ft_attempt`` dicts the
    supervisor collected. Hard-killed attempts emit none themselves —
    the supervisor synthesizes a record from the ``ft_start``/
    ``ft_kill`` markers and tags it ``synthetic`` (its wall clock is
    unknown, so it contributes lost steps but not step timing).
    ``wall_s`` is the SUPERVISOR's wall clock, which includes process
    startup and the restart gaps the child meters cannot see.

    ``final_step``: for a run that never completed, the last step known
    to be CHECKPOINTED (the supervisor tracks it from the markers). A
    killed attempt may have "reached" further, but steps past the last
    checkpoint survive into no model — they are lost, not useful.
    """
    steps_run = sum(a["steps_run"] for a in attempts)
    # useful steps = where the SURVIVING trajectory ended
    final = max((a["reached"] for a in attempts
                 if a.get("completed")), default=0) \
        or int(final_step or 0)
    lost = max(steps_run - final, 0)
    timed = [a for a in attempts if not a.get("synthetic")]
    save_s = sum(a["save_blocking_s"] for a in timed)
    restore_s = sum(a["restore_s"] for a in timed)
    child_wall = sum(a["wall_s"] for a in timed)
    timed_steps = sum(a["steps_run"] for a in timed)
    step_s = ((child_wall - save_s - restore_s) / timed_steps
              if timed_steps else 0.0)
    useful_s = final * step_s
    return {
        "goodput": round(useful_s / wall_s, 4) if wall_s > 0 else 0.0,
        "useful_steps": final,
        "steps_run": steps_run,
        "lost_steps": lost,
        "step_time_s": round(step_s, 4),
        "checkpoint_overhead_s": round(save_s, 4),
        "restore_overhead_s": round(restore_s, 4),
        "wall_s": round(wall_s, 4),
        "attempts": len(attempts),
    }
