"""The harness: finds everything by the names in ``BENCHMARK.json``.

::

    BENCHMARK.json                       manifest (metrics, bounds, cells)
    <bench>/configs/<config>.json        a configuration's sizes
    <bench>/workloads/<cell>.json        a cell: driver, options, sizing
    <bench>/traffic/<traffic>.json       a traffic mix's parameters
    <bench>/drivers/<driver>.py          one per kind of job
    <bench>/layer_metrics/<reader>.py    one per per-layer reader

``<bench>`` is the first entry of the manifest's ``paths``. A per-layer
metric ``engine_step_ms.sat`` is read by ``layer_metrics/
engine_step_ms.py``: the part of a metric's name before the first dot
names its reader, the rest tells apart the entries that one reader
serves (one per end-to-end metric it ``moves``). A later PR adds files
and appends entries; no file that is there needs an edit. A name with
no file is an error that says which file is missing.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Callable, Dict, List, Optional


class MissingFile(FileNotFoundError):
    pass


def _load_json(path: str, what: str) -> Dict:
    if not os.path.isfile(path):
        raise MissingFile(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, what: str):
    if not os.path.isfile(path):
        raise MissingFile(f"{what}: no file {path}")
    name = "_bench_" + os.path.splitext(os.path.basename(path))[0] \
        + "_" + os.path.basename(os.path.dirname(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: Dict            # workloads/<cell>.json
    config: Dict          # configs/<config>.json
    traffic: Dict         # traffic/<traffic>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]


class Bench:
    """One checkout's benchmark: the manifest and the files it names."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.manifest = _load_json(
            os.path.join(self.root, "BENCHMARK.json"), "the manifest")
        self.dir = os.path.join(self.root, self.manifest["paths"][0])

    def path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def metrics_for(self, cell: str, group: str) -> List[Dict]:
        """Entries of ``end_to_end`` or ``per_layer`` that ``cell``
        reports: those with no ``workloads`` key, or that list it."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or cell in m["workloads"]]

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            known = [w["name"] for w in self.manifest["workloads"]]
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {known})")
        cfg_entry = next((c for c in self.manifest["configs"]
                          if c["name"] == entry["config"]), None)
        if cfg_entry is None:
            raise KeyError(f"workload {name!r} names configuration "
                           f"{entry['config']!r}, which BENCHMARK.json "
                           f"does not list")
        return Cell(
            name=name, chips=int(entry["chips"]),
            spec=_load_json(self.path("workloads", name + ".json"),
                            f"cell {name!r}"),
            config=_load_json(os.path.join(self.root, cfg_entry["file"]),
                              f"configuration {entry['config']!r}"),
            traffic=_load_json(
                self.path("traffic", entry["traffic"] + ".json"),
                f"traffic mix {entry['traffic']!r}"),
            end_to_end=self.metrics_for(name, "end_to_end"),
            per_layer=self.metrics_for(name, "per_layer"))

    def driver(self, name: str):
        return _load_module(self.path("drivers", name + ".py"),
                            f"driver {name!r}")

    def reader(self, metric: str) -> Callable[[Dict], Optional[float]]:
        reader = metric.split(".", 1)[0]
        mod = _load_module(self.path("layer_metrics", reader + ".py"),
                           f"per-layer metric {metric!r}")
        return mod.read


def per_layer_values(bench: Bench, cell: Cell, ctx: Dict) -> Dict:
    """Every per-layer metric of the cell through its reader. A reader
    that finds nothing to read returns None and the metric is left out
    of the line."""
    out = {}
    for m in cell.per_layer:
        value = bench.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


@dataclasses.dataclass
class RunContext:
    """What a driver is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    meter: Any                      # lib.device.CompileMeter
    t_process_start: float          # time.perf_counter() at start
    scratch: str                    # a directory inside the checkout
    info: Callable[[Dict], None]    # prints one JSON line, early

    @property
    def trace_seconds(self) -> float:
        """How much of a ``--trace 1`` run's window the profiler
        covers: the LAST stretch; the host-clock per-layer numbers come
        from the stretch before it, with the profiler off."""
        want = float(self.cell.spec.get("trace_seconds", 2.0))
        return min(want, self.seconds / 2)


class DeviceTrace:
    """``jax.profiler`` around a stretch of a run, reduced to busy
    time, window and breakdown (lib/trace_reduce.py), then deleted: a
    trace is tens of megabytes and the checkout stays small."""

    def __init__(self, ctx: RunContext):
        self.dir = os.path.join(ctx.scratch, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)

    def start(self) -> None:
        import jax

        os.makedirs(self.dir, exist_ok=True)
        # the Python call tracer hooks every call of the host loop and
        # slows what it measures; TraceAnnotation spans need only the
        # host tracer
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> Optional[Dict]:
        import jax

        from benchmarks.lib import trace_reduce

        jax.profiler.stop_trace()
        try:
            trace = trace_reduce.read_trace(
                trace_reduce.find_xplane(self.dir))
            return trace_reduce.reduce_trace(trace)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def annotate(name: str):
    """A host span on the profiler's clock, named ``bench.<name>``:
    what the host was doing, for the idle gaps of the device."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)
