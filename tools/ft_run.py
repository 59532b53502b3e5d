"""Fault-tolerance supervisor: relaunch training until it completes,
inject deterministic kills, and report goodput as ONE JSON line:

  {"metric": "ft_goodput", "value": 0.87, "unit": "fraction", "rc": 0,
   "extras": {"faults_survived": 2, "restarts": 2, "useful_steps": 18,
   "lost_steps": 1, "checkpoint_overhead_s": .., ...}}

The restart loop is what ``pod_run train`` lacked before
``--max-restarts``: a child exiting with a fault-tolerance sentinel
code (75 = graceful preemption snapshot saved, 113 = hard chaos kill)
is RELAUNCHED, and the step-granular cursor in the checkpoint
(quintnet_tpu/ft/) makes the relaunched process continue mid-epoch
with bit-identical results (tests/test_ft.py proves the bit-identity;
this tool proves the operational loop end-to-end and prices it).

Faults are armed per attempt through the ``QT_CHAOS`` env var: each
launch gets the next un-consumed kill from ``--kill-at`` (GLOBAL step
numbers — the relaunched run resumes, passes its old death point, and
dies at the next armed step, the repeated-preemption pod scenario).

Modes:
  python tools/ft_run.py                         # 2 hard kills, CPU-ok
  python tools/ft_run.py --kill-at 5,11 --kill-mode sigterm
  python tools/ft_run.py --epochs 2 --samples 48 --kill-at 2  # smoke
      (CI runs this — tests/test_ft_run.py — so the CLI can never rot)
  python tools/ft_run.py --child ...             # internal: one attempt

``--out FILE`` appends the record to a JSON list in FILE. Report
schema: docs/fault_tolerance.md.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# child: one training attempt (resumes from whatever the checkpoint holds)


def run_child(args) -> int:
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from quintnet_tpu.core.config import Config
    from quintnet_tpu.data import ArrayDataset, make_batches
    from quintnet_tpu.data.datasets import synthetic_mnist
    from quintnet_tpu.ft import (ChaosMonkey, FTContext, GoodputMeter,
                                 PREEMPTED_EXIT_CODE, PreemptionHandler,
                                 TrainingPreempted)
    from quintnet_tpu.models.vit import ViTConfig, vit_model_spec
    from quintnet_tpu.train.trainer import Trainer

    cfg = Config.from_dict({
        "mesh_dim": [1], "mesh_name": ["dp"],
        "training": {"batch_size": args.batch_size, "epochs": args.epochs,
                     "optimizer": "adam", "learning_rate": 1e-3,
                     "log_every": 0, "seed": args.seed,
                     "save_every_steps": args.save_every},
    })
    vcfg = ViTConfig(image_size=28, patch_size=7, in_channels=1,
                     hidden_dim=16, depth=2, num_heads=2, num_classes=10)
    x, y = synthetic_mnist(args.samples, seed=args.seed)
    ds = ArrayDataset(x, y)

    trainer = Trainer(cfg, vit_model_spec(vcfg),
                      checkpoint_dir=os.path.join(args.run_dir,
                                                  "checkpoints"))
    meter = GoodputMeter(emit_markers=True)
    ft = FTContext(preemption=None, chaos=ChaosMonkey.from_env(),
                   goodput=meter)
    with PreemptionHandler() as handler:
        ft.preemption = handler
        try:
            hist = trainer.fit(
                lambda ep, start=0: make_batches(
                    ds, args.batch_size, seed=ep, start_batch=start),
                ft=ft)
        except TrainingPreempted:
            meter.emit(completed=False)
            return PREEMPTED_EXIT_CODE
    hist.to_jsonl(os.path.join(args.run_dir, "history.jsonl"))
    meter.emit(completed=True)
    return 0


# ---------------------------------------------------------------------------
# supervisor: restart loop + goodput aggregation


def supervise(args) -> dict:
    from quintnet_tpu.ft.chaos import CHAOS_ENV, CHAOS_KILL_EXIT_CODE
    from quintnet_tpu.ft.goodput import aggregate
    from quintnet_tpu.ft.preempt import PREEMPTED_EXIT_CODE

    os.makedirs(args.run_dir, exist_ok=True)
    kills = [int(k) for k in args.kill_at.split(",") if k] \
        if args.kill_at else []
    child_cmd = [sys.executable, os.path.abspath(__file__), "--child",
                 "--run-dir", args.run_dir,
                 "--epochs", str(args.epochs),
                 "--samples", str(args.samples),
                 "--batch-size", str(args.batch_size),
                 "--save-every", str(args.save_every),
                 "--seed", str(args.seed),
                 "--platform", args.platform or ""]

    attempts, faults, restarts = [], [], 0
    last_ckpt = 0  # newest checkpointed global step we know of
    t0 = time.time()
    rc = None
    while True:
        env = dict(os.environ)
        env.pop(CHAOS_ENV, None)
        armed = kills[len(faults)] if len(faults) < len(kills) else None
        if armed is not None:
            env[CHAOS_ENV] = json.dumps(
                {"kill_at_step": armed, "mode": args.kill_mode})
        print(f"[ft_run] attempt {restarts + 1}"
              + (f" (armed: kill at step {armed}, {args.kill_mode})"
                 if armed is not None else ""), flush=True)
        resumed_at, killed_at = last_ckpt, None
        p = subprocess.Popen(child_cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        for line in p.stdout:
            s = line.decode(errors="replace")
            sys.stdout.write("  " + s)
            try:
                rec = json.loads(s)
            except json.JSONDecodeError:
                continue
            if not isinstance(rec, dict):
                continue
            if "ft_attempt" in rec:
                attempts.append(rec["ft_attempt"])
                # graceful exits checkpoint at their last reached step
                # (emergency snapshot / end-of-run save)
                last_ckpt = max(last_ckpt, rec["ft_attempt"]["reached"])
            elif "ft_start" in rec:
                resumed_at = last_ckpt = rec["ft_start"]["resumed_at"]
            elif "ft_kill" in rec:
                killed_at = rec["ft_kill"]["global_step"]
                faults.append({"kind": "hard_kill", **rec["ft_kill"]})
        rc = p.wait()
        print(f"[ft_run] attempt {restarts + 1} exited rc={rc}", flush=True)
        if rc == 0:
            break
        if killed_at is not None:
            # hard kill: the attempt never emitted its report — account
            # its executed-but-possibly-lost steps from the markers
            attempts.append({
                "resumed_at": resumed_at, "reached": killed_at,
                "steps_run": max(killed_at - resumed_at, 0),
                "wall_s": 0.0, "save_blocking_s": 0.0, "restore_s": 0.0,
                "fallback_steps": 0, "completed": False,
                "synthetic": True})
        if rc == PREEMPTED_EXIT_CODE and armed is not None:
            # sigterm-mode kill: graceful snapshot, no ft_kill marker
            faults.append({"kind": "preemption", "global_step": armed})
        if restarts >= args.max_restarts:
            print(f"[ft_run] giving up after {restarts} restarts "
                  f"(last rc={rc})", file=sys.stderr)
            break
        if rc not in (PREEMPTED_EXIT_CODE, CHAOS_KILL_EXIT_CODE):
            print(f"[ft_run] rc={rc} is not a fault-tolerance sentinel "
                  "(75/113) — restarting anyway, a preemption can kill "
                  "harder than SIGTERM", file=sys.stderr)
        restarts += 1

    g = aggregate(attempts, wall_s=time.time() - t0, final_step=last_ckpt)
    return {
        "metric": "ft_goodput",
        "value": g["goodput"],
        "unit": "fraction",
        "vs_baseline": 1.0,
        "rc": 0 if rc == 0 else 1,
        "extras": {
            **{k: v for k, v in g.items() if k != "goodput"},
            "faults_injected": len(kills),
            "faults_survived": len(faults),
            "restarts": restarts,
            "kill_mode": args.kill_mode,
            "kill_at": kills,
            "save_every_steps": args.save_every,
            "epochs": args.epochs,
            "samples": args.samples,
            "batch_size": args.batch_size,
            "completed": rc == 0,
        },
    }


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true",
                    help="internal: run ONE training attempt")
    ap.add_argument("--run-dir", default="runs/ft")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--samples", type=int, default=96,
                    help="synthetic dataset size (steps/epoch = "
                         "samples // batch_size)")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--save-every", type=int, default=2,
                    help="checkpoint cadence in steps "
                         "(training.save_every_steps)")
    ap.add_argument("--kill-at", default="5,11",
                    help="comma-separated GLOBAL steps to kill at, "
                         "consumed one per attempt ('' = no faults)")
    ap.add_argument("--kill-mode", default="hard",
                    choices=("hard", "sigterm"))
    ap.add_argument("--max-restarts", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--platform", default="cpu",
                    help="'cpu' (default: runs anywhere) or 'tpu'")
    ap.add_argument("--out", default=None,
                    help="append the record to this artifacts JSON file")
    args = ap.parse_args()

    if args.child:
        sys.exit(run_child(args))

    out = supervise(args)
    line = json.dumps(out)
    print(line)
    if args.out:
        records = []
        if os.path.exists(args.out):
            try:
                with open(args.out) as f:
                    prev = json.load(f)
                records = prev if isinstance(prev, list) else [prev]
            except (OSError, json.JSONDecodeError):
                records = []
        records.append(out)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    sys.exit(out["rc"])


if __name__ == "__main__":
    main()
