"""Pytest setup: run every test on a simulated 8-device CPU mesh.

The reference's test story needs real GPUs + torchrun per rank and skips
on world-size mismatch (reference: tests/conftest.py:48-135). JAX gives
multi-device simulation for free: 8 virtual CPU devices in one process,
so the full DPxTPxPP matrix runs in CI with no hardware.

XLA_FLAGS must be set before first backend use; the platform is pinned
to the CPU here as well, so a bare ``pytest`` never reaches for a chip.

The paged-attention Pallas kernel compiles for real by default and
never picks interpret mode for itself (ops/paged_attention.py); this
session has no TPU, so it turns the interpreter on ONCE, here, for
every in-process test that builds a Pallas engine.
"""

import importlib
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

import numpy as np
import pytest

# (import_module: ``quintnet_tpu.ops`` re-exports the FUNCTION under the
# submodule's name, so attribute access would find the function)
importlib.import_module(
    "quintnet_tpu.ops.paged_attention").INTERPRET = True

# Whole files whose tests are multi-minute on one CPU core (subprocess
# meshes, full-matrix parity, long schedules). Everything else is
# auto-marked ``fast`` — `pytest -m fast` stays green in <5 min
# single-core; `-m slow` (or no -m) runs the rest. Individual tests can
# still carry an explicit @pytest.mark.slow inside fast files.
SLOW_FILES = {
    "test_5d.py",         # 32-device 5D subprocess run (~9 min budget)
    "test_multihost.py",  # real 2-process jax.distributed rendezvous
    "test_launcher.py",   # spawns multi-process demos
    "test_sp.py",         # ring/zigzag/ulysses golden matrix (~4 min)
    "test_vp.py",         # vocab-parallel loss/embedding matrix (~2 min)
    "test_train.py",      # multi-epoch trainer runs + resume
    "test_generate.py",   # KV-cache + tp decode goldens (~4 min)
    "test_moe.py",        # MoE routing/dispatch matrix (~4 min)
    "test_dropout.py",    # seed-discipline matrix across strategies (~5 min)
    "test_gpt2.py",       # 3D training goldens + HF import (~2 min)
    "test_dp.py",         # replica-identity/grad-accum goldens (~1.5 min)
    "test_strategy.py",   # full strategy x schedule matrix (~2 min)
    "test_flash.py",      # pallas interpret-mode kernels (~1.5 min)
    "test_llama.py",      # HF goldens + strategy matrix (~3 min; the
                          # HF-logits golden is promoted fast)
    "test_lora.py",       # adapter goldens (~1.5 min; identity +
                          # save/load promoted fast)
    "test_beam.py",       # beam-search goldens (~1 min)
    "test_remat_knobs.py",  # remat policy matrix (~1.5 min; plain
                            # policy goldens promoted fast)
    "test_segments.py",   # packed-segment matrix incl. sp modes (~3 min;
                          # sdpa/host-helper goldens promoted fast)
    "test_fsdp.py",       # ZeRO-3 golden matrix (~4 min; spec-transform
                          # + guard tests promoted fast)
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        fname = os.path.basename(str(item.fspath))
        explicit_slow = item.get_closest_marker("slow") is not None
        # an explicit @pytest.mark.fast inside a slow FILE promotes that
        # test into the smoke subset
        explicit_fast = item.get_closest_marker("fast") is not None
        if explicit_slow or (fname in SLOW_FILES and not explicit_fast):
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.fast)


@pytest.fixture(scope="session", autouse=True)
def _devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)
