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

# Whole files kept out of tier-1 (`-m 'not slow'`, six workers, one file
# a worker). The seconds are one run of each file alone on one worker
# of this machine (8 cores, CPU, `-m slow`, measured 2026-10-01, PR 29);
# a file comes off this list when it runs under 120 s that way and no
# test in it takes over 30 s (ROADMAP D15). Everything else is
# auto-marked ``fast``; a test in a fast file can still carry an
# explicit @pytest.mark.slow, and an explicit @pytest.mark.fast inside
# a file listed here promotes that test into tier-1.
SLOW_FILES = {
    "test_sp.py",         # 23 tests, 196 s: ring/zigzag/ulysses goldens
                          # (zigzag grads alone 42 s)
    "test_moe.py",        # 20 tests, 173 s: routing/dispatch x pp matrix
    "test_llama.py",      # 25 tests, 121 s: HF goldens + strategy matrix
                          # (the HF-logits golden is promoted fast)
    "test_dropout.py",    # 13 tests, 100 s: seed discipline x strategies
    "test_fsdp.py",       # 12 tests, 92 s: ZeRO-3 golden matrix (spec-
                          # transform + guard tests promoted fast)
    "test_lora.py",       # 8 tests, 36 s: adapter goldens (identity +
                          # save/load promoted fast)
    "test_generate.py",   # 11 tests, 29 s: KV-cache + tp decode goldens
    "test_multihost.py",  # 1 test, 29 s: a real 2-process
                          # jax.distributed rendezvous
    "test_5d.py",         # 1 test, 26 s: 32-device 5D subprocess run
    "test_remat_knobs.py",  # 3 tests, 21 s: remat policy matrix (plain
                            # policy goldens promoted fast)
    "test_launcher.py",   # 2 tests, 5 s: spawns multi-process demos
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
