"""Profiling: step timing + XLA trace capture.

The reference's profiling module is a pass-body stub
(utils/profiling.py:11-27). Real tooling here:

- :func:`profile_time` / :class:`StepTimer`: wall-clock timing that
  ends in ``jax.block_until_ready`` (JAX returns before the device
  finishes, so a timing without the barrier measures the enqueue);
- :func:`trace`: context manager around ``jax.profiler`` writing a
  TensorBoard-loadable XLA trace;
- :func:`device_memory_stats`: per-device live-bytes snapshot
  (the reference's utils/memory.py get_memory_usage equivalent).
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np


def profile_time(fn: Callable) -> Callable:
    """Decorator: prints wall time of each call (synced on the output)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        print(f"[profile] {fn.__name__}: {time.perf_counter() - t0:.4f}s")
        return out

    return wrapped


class StepTimer:
    """Collects per-step durations; reports mean/p50/p99."""

    def __init__(self):
        self.times: List[float] = []
        self._t0: Optional[float] = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, out: Any = None):
        jax.block_until_ready(out)
        assert self._t0 is not None
        self.times.append(time.perf_counter() - self._t0)
        self._t0 = None

    def summary(self) -> Dict[str, float]:
        """Zero recorded steps is a legal state (a run that died before
        its first stop(), an idle serving replica): report a zeroed
        summary with ``steps: 0`` instead of NaN means + a NumPy
        RuntimeWarning from an empty reduction."""
        if not self.times:
            return {"steps": 0, "mean_s": 0.0, "p50_s": 0.0, "p99_s": 0.0}
        a = np.asarray(self.times[1:] or self.times)  # drop compile step
        return {
            "steps": len(self.times),
            "mean_s": float(a.mean()),
            "p50_s": float(np.percentile(a, 50)),
            "p99_s": float(np.percentile(a, 99)),
        }


@contextlib.contextmanager
def trace(logdir: str):
    """Capture an XLA profiler trace viewable in TensorBoard/Perfetto."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """Live/peak bytes per device where the backend exposes them."""
    out = {}
    for d in jax.devices():
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:
            pass
        out[str(d)] = {
            "bytes_in_use": int(stats.get("bytes_in_use", -1)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", -1)),
            "bytes_limit": int(stats.get("bytes_limit", -1)),
        }
    return out
