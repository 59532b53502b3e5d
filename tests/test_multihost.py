"""Multi-host runtime test: a REAL 2-process jax.distributed run
(localhost coordinator, 4 virtual CPU devices per process, gloo
collectives) training dp4 x tp2 ViT with per-process data feeding, to
parity with the single-process result.

Reference analogue: torchrun rendezvous + DistributedSampler
(core/mesh.py:196-251, examples/full_3d.py:129-155) — which the
reference can only exercise on real multi-GPU hosts; here it runs in CI.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import optax
import pytest

from quintnet_tpu.models.vit import (
    ViTConfig,
    cross_entropy_loss,
    vit_apply,
    vit_init,
)

CFG = ViTConfig(image_size=14, patch_size=7, in_channels=1, hidden_dim=16,
                depth=4, num_heads=2, num_classes=10)
PORT = "12397"


def _single_process_reference():
    x = jax.random.normal(jax.random.key(1), (16, 14, 14, 1))
    y = jax.random.randint(jax.random.key(2), (16,), 0, 10)
    params = vit_init(jax.random.key(0), CFG)
    opt = optax.sgd(0.05)
    state = opt.init(params)

    def loss_fn(p):
        return cross_entropy_loss(vit_apply(p, x, CFG), y)

    losses = []
    for _ in range(2):
        loss, g = jax.value_and_grad(loss_fn)(params)
        upd, state = opt.update(g, state, params)
        params = optax.apply_updates(params, upd)
        losses.append(float(loss))
    sqsum = float(sum(np.sum(np.square(np.asarray(l)))
                      for l in jax.tree.leaves(params)))
    return losses, sqsum


def test_two_process_dp_tp_matches_single_process(tmp_path):
    ref_losses, ref_sqsum = _single_process_reference()

    env = dict(os.environ)
    # workers pick their own device count/platform; the conftest's
    # 8-device XLA flag and platform pin must not leak in
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = os.getcwd()

    worker = os.path.join(os.path.dirname(__file__), "_mp_worker.py")
    outs = [str(tmp_path / f"w{i}.json") for i in range(2)]
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), "2", PORT, outs[i]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i in range(2)
    ]
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process workers timed out")
        logs.append(out.decode(errors="replace"))
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i} failed:\n{logs[i][-4000:]}"

    for i in range(2):
        with open(outs[i]) as f:
            res = json.load(f)
        for mode in ("global", "local", "fsdp"):
            np.testing.assert_allclose(
                res[mode]["losses"], ref_losses, rtol=1e-5,
                err_msg=f"worker {i} mode {mode} losses")
            np.testing.assert_allclose(
                res[mode]["param_sqsum"], ref_sqsum, rtol=1e-5,
                err_msg=f"worker {i} mode {mode} params")
