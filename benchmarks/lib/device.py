"""What the benchmark asks of the device it runs on: that it is a TPU
with enough chips, what it is called, how much of its memory was used,
and how long JAX spent compiling (and when)."""

from __future__ import annotations

from typing import Dict, List, Tuple


class NoAccelerator(SystemExit):
    """Raised (exit code 3) where JAX found no TPU or too few chips. No
    result line is printed for another platform."""

    def __init__(self, msg: str):
        super().__init__(3)
        self.msg = msg


def require_tpu(chips: int) -> List:
    """The first ``chips`` devices, or :class:`NoAccelerator`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"the benchmark measures a TPU; JAX found "
            f"{devices[0].platform!r} ({devices[0].device_kind}). "
            f"Nothing was measured.")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peak_bytes(stats: Dict) -> int:
    """Peak bytes of one device from its ``memory_stats()``: the peak of
    the BUFFERS the allocator handed out (weights, optimizer state,
    pool, inputs, outputs: ``peak_bytes_in_use``) plus the peak of what
    the runtime RESERVED beside them for running programs' temporaries
    (``peak_bytes_reserved``). On this runtime the first alone leaves
    the temporaries out: a GPT-2 124M step whose compiler plan has 9.89
    GB of them read 1.53 GB in use and 9.89 GB reserved (my chip run,
    PR 24). The two peaks need not fall in the same instant, so the sum
    is an upper bound; in a steady loop of one program they do."""
    return int(stats["peak_bytes_in_use"]
               + stats.get("peak_bytes_reserved", 0))


def device_record(devices) -> Dict:
    """Platform, kind and count as JAX reports them, and the peak bytes
    (:func:`peak_bytes`) on the fullest chip."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": max(peak_bytes(d.memory_stats())
                                     for d in devices)}


class CompileMeter:
    """Seconds JAX spent in backend compile-or-load and the persistent
    cache's hits and misses, from ``jax.monitoring`` (copied from
    chip_smoke.CompileMeter). ``compiles`` counts backend compilations
    OR loads: inside a measured window it has to stay where it was."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self) -> Tuple[float, int, int, int]:
        return self.compile_s, self.compiles, self.hits, self.misses
