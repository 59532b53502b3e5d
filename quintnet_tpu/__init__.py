"""QuintNet-TPU: a TPU-native 5D-parallel training framework.

A from-scratch JAX/XLA re-design of the capabilities of the reference
QuintNet library (pure-Python PyTorch + NCCL 3D parallelism; see
/root/reference). Instead of process groups, autograd-wrapped NCCL
collectives, and in-place ``nn.Linear`` rewriting, this framework uses:

- one ``jax.sharding.Mesh`` with named axes (``dp``, ``tp``, ``pp``, ``sp``)
  instead of ``MeshGenerator`` + ``ProcessGroupManager``
  (reference: core/mesh.py:124, core/process_groups.py:42);
- ``jax.lax`` collectives under ``shard_map`` — differentiable by
  construction — instead of hand-written autograd Functions
  (reference: core/communication.py:46-600);
- sharding rules on parameter pytrees instead of module surgery
  (reference: parallelism/tensor_parallel/model_wrapper.py:37);
- ``lax.scan`` + ``ppermute`` pipeline schedules instead of batched
  isend/irecv P2P (reference: parallelism/pipeline_parallel/schedule.py);
- a single grad ``psum`` over the ``dp`` axis instead of DDP gradient
  bucketing (reference: parallelism/data_parallel/ddp.py:49).

Capabilities beyond the reference: sequence parallelism / ring attention
for long context, ZeRO-1/2 optimizer sharding (reference stubs:
optimizers/zero.py), Pallas TPU kernels, profiling, and a simulated
multi-device test story that needs no real multi-host hardware.
"""

import time as _time

_T_IMPORT = _time.perf_counter()        # qn.setup.import opens here

__version__ = "0.2.0"

from quintnet_tpu.core.config import Config, load_config  # noqa: E402
from quintnet_tpu.core.mesh import MeshSpec, build_mesh  # noqa: E402

__all__ = [
    "Config",
    "load_config",
    "MeshSpec",
    "build_mesh",
    "__version__",
]

# ... and closes here: the package's import, JAX's included, on the
# start-up record (obs/recorder.startup()). Importing obs/spans also
# registers the program's one compile listener, so what a caller
# compiles before it builds an engine or a trainer is on the record too
# (``unattributed``).
from quintnet_tpu.obs.spans import stamp_import as _stamp  # noqa: E402

_stamp(_T_IMPORT)
