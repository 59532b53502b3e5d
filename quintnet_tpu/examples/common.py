"""Shared example plumbing: platform selection, config load, ViT runner.

Every example accepts the reference's YAML schema (examples/config.yaml)
and a ``--simulate N`` flag that swaps the real TPU for N virtual CPU
devices (the capability the reference lacks — it needs torchrun + GPUs
for every smoke test)."""

from __future__ import annotations

import argparse
import os


def parse_args(default_config: str):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=default_config)
    ap.add_argument("--simulate", type=int, default=0,
                    help="run on N virtual CPU devices instead of TPU")
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--limit", type=int, default=None,
                    help="cap train/val samples per epoch (smoke runs)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--data-dir", default=None)
    add_multihost_args(ap)
    return ap.parse_args()


def add_multihost_args(ap):
    """Pod-scale launch flags (reference: torchrun env rendezvous,
    README.md:93-97). One process per host; on TPU pods --multihost
    alone auto-detects the slice topology."""
    ap.add_argument("--multihost", action="store_true",
                    help="jax.distributed.initialize() (TPU pod)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="explicit coordinator (CPU/dev multi-process)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    return ap


def setup_platform(simulate: int, args=None):
    """Must run before first jax backend use."""
    multihost = args is not None and (args.multihost or args.coordinator)
    if simulate and not multihost:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={simulate}")
    import jax

    from quintnet_tpu.core import runtime

    runtime.enable_compilation_cache()
    if multihost:
        runtime.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
            local_device_count=simulate or None,
            platform="cpu" if simulate else None,
        )
        print(f"process {jax.process_index()}/{jax.process_count()}: "
              f"{jax.local_device_count()} local / "
              f"{jax.device_count()} global devices")
    elif simulate:
        jax.config.update("jax_platforms", "cpu")
    return jax


def run_vit(args, strategy_name: str):
    setup_platform(args.simulate, args)

    from quintnet_tpu.core.config import load_config
    from quintnet_tpu.data import ArrayDataset, load_mnist, make_batches
    from quintnet_tpu.models.vit import ViTConfig, vit_model_spec
    from quintnet_tpu.parallel.strategy import get_strategy
    from quintnet_tpu.train.trainer import Trainer

    cfg = load_config(args.config)
    if args.epochs:
        cfg.training.epochs = args.epochs

    vcfg = ViTConfig.from_model_config(cfg.model)
    model = vit_model_spec(vcfg, remat=cfg.training.remat_mode)
    strategy = get_strategy(strategy_name, cfg)
    print(f"strategy={strategy.name} mesh={dict(strategy.mesh.shape)}")

    xtr, ytr = load_mnist(args.data_dir, split="train")
    xte, yte = load_mnist(args.data_dir, split="test")
    limit = getattr(args, "limit", None)
    if limit:
        xtr, ytr = xtr[:limit], ytr[:limit]
        xte, yte = xte[:limit], yte[:limit]
    train = ArrayDataset(xtr, ytr)
    test = ArrayDataset(xte, yte)
    bs = cfg.training.batch_size

    trainer = Trainer(cfg, model, strategy=strategy,
                      task_type="classification",
                      checkpoint_dir=args.checkpoint_dir)
    hist = trainer.fit(
        lambda ep: make_batches(train, bs, seed=ep),
        val_batches_fn=lambda ep: make_batches(test, bs, shuffle=False),
    )
    msg = (f"done in {hist.wall_time_s:.1f}s; "
           f"final train_loss {hist.train_loss[-1]:.4f}")
    if hist.val_metric:
        # reference headline metric (README.md:214: 93.24% val acc)
        msg += f"; final val_accuracy {hist.val_metric[-1]:.4f}"
    print(msg)
    return hist
