"""Multi-host (pod-scale) runtime: process bootstrap + per-host data.

The reference's multi-process story is torchrun env rendezvous +
``dist.init_process_group`` + per-rank ``DistributedSampler`` feeding
(reference: core/mesh.py:196-251, examples/full_3d.py:129-155). The JAX
equivalent is one ``jax.distributed.initialize`` call per process, after
which ``jax.devices()`` is the GLOBAL device list and every jitted
computation is a single SPMD program across all hosts — the v5e-64
north-star topology (16 hosts x 4 chips) runs the exact same Strategy/
Trainer code as one chip.

Per-host data feeding (the DistributedSampler analogue) has two modes:

- host-global: every process holds the full global batch; only this
  process's shards are transferred to its devices
  (:func:`global_array_from_host_data` via ``make_array_from_callback``).
- process-local: every process holds ONLY its slice
  (:func:`global_array_from_process_data` via
  ``jax.make_array_from_process_local_data``);
  :func:`host_local_slice` computes which rows those are.

On TPU pods ``initialize()`` auto-detects everything. For multi-process
CPU testing (no pod available), pass coordinator/process counts
explicitly — tests/test_multihost.py runs a real 2-process dp x tp
training to single-process parity this way.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    *,
    local_device_count: Optional[int] = None,
    platform: Optional[str] = None,
):
    """Bring this process into the global runtime.

    TPU pod: call with no arguments BEFORE any other jax use — slice
    topology is discovered from the TPU metadata (the reference needs
    MASTER_ADDR/RANK env plumbing per process; torchrun provides it).

    CPU multi-process (testing/dev): pass ``coordinator_address``
    ("host:port"), ``num_processes``, ``process_id``, and optionally
    ``local_device_count`` virtual devices per process and
    ``platform='cpu'``; collectives ride gloo.
    """
    import jax

    if platform is not None:
        jax.config.update("jax_platforms", platform)
    if local_device_count is not None:
        jax.config.update("jax_num_cpu_devices", int(local_device_count))
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax


# The one place the persistent compile cache lives when the environment
# names none: inside the checkout (.gitignore lists it). The path is
# part of every cache key's provenance story — a directory that moves
# (a home, a temp name, a pid, a time) never hits on the next run.
COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Persist compiled XLA executables across processes.

    First TPU compile of a big training step costs 20-40s+; with the
    cache, relaunching the same program (same jaxpr + compile options +
    topology) loads in well under a second. Call BEFORE the first jit
    execution — every entry point does (chip_smoke.py,
    benchmarks/run.py, examples/common.setup_platform,
    fleet/proc.replica_main).

    The directory is placed from OUTSIDE: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    function sets no directory in code; where it is not, the cache goes
    to the fixed :data:`COMPILATION_CACHE_DIR` inside the checkout.
    Either way every jit-compiled program is cached, however small or
    quick. Returns the directory in use.

    The reference has no analogue (torch eager pays no compile, and its
    NCCL init cost is unavoidable per launch).
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILATION_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def is_main_process() -> bool:
    """Gate for host-side logging/IO (reference: ``is_main_process``,
    core/distributed.py:43-59, rank-0 tqdm guards)."""
    return process_index() == 0


def is_multiprocess() -> bool:
    return process_count() > 1


def global_array_from_host_data(sharding, host_array):
    """Build a global jax.Array from HOST-GLOBAL data: only this
    process's shards are materialised on its devices. Works in single-
    and multi-process alike (multi-process ``jax.device_put`` of a
    host-global array onto non-addressable devices does not)."""
    import jax

    host_array = np.asarray(host_array)
    return jax.make_array_from_callback(
        host_array.shape, sharding, lambda idx: host_array[idx])


def global_array_from_process_data(sharding, local_array,
                                   global_shape=None):
    """Build a global jax.Array from this process's LOCAL slice — true
    per-host feeding (each host loads only its rows; the reference's
    DistributedSampler role, examples/full_3d.py:129-155)."""
    import jax

    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local_array), global_shape)


def host_local_slice(sharding, global_shape: Sequence[int]) -> tuple:
    """Index (tuple of slices) of the rows of a host-global array this
    process must provide under ``sharding`` — feed
    ``global_batch[host_local_slice(...)]`` to
    :func:`global_array_from_process_data`.

    Assumes this process's addressable shards tile a contiguous block
    per dimension (true for batch sharding over process-major mesh
    axes)."""
    idx_map = sharding.addressable_devices_indices_map(tuple(global_shape))
    ndim = len(global_shape)
    starts = [None] * ndim
    stops = [None] * ndim
    for idx in idx_map.values():
        for d in range(ndim):
            sl = idx[d] if d < len(idx) else slice(None)
            lo = 0 if sl.start is None else sl.start
            hi = global_shape[d] if sl.stop is None else sl.stop
            starts[d] = lo if starts[d] is None else min(starts[d], lo)
            stops[d] = hi if stops[d] is None else max(stops[d], hi)
    return tuple(slice(lo, hi) for lo, hi in zip(starts, stops))
