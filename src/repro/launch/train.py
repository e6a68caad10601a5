"""Training launcher.

Two modes:

1. Single-job training (``--arch``): builds the mesh (or single-device),
   shards TrainState per the arch's parallelism plan, runs optimizer steps
   with periodic checkpoints and optional simulated failure/elastic-resume.

       PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
           --steps 50 --seq-len 128 --batch 8 --ckpt-dir /tmp/ckpt

2. OEF-scheduled multi-tenant mode (``--scheduler``): the paper's control
   plane drives several training jobs; each round the fair-share evaluator
   (cooperative or non-cooperative OEF) re-allocates the heterogeneous fleet
   and every tenant advances proportionally to its granted device-throughput
   (see examples/cluster_scheduler_e2e.py for the annotated version).

       PYTHONPATH=src python -m repro.launch.train --scheduler oef-coop \
           --tenants qwen2-1.5b,gemma3-4b,xlstm-350m --rounds 3
"""
from __future__ import annotations

import argparse
import tempfile

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step, then auto-recover")
    ap.add_argument("--mesh", type=str, default=None,
                    help="e.g. 2x4 (needs forced host devices)")
    # scheduler mode
    ap.add_argument("--scheduler", type=str, default=None,
                    choices=["oef-coop", "oef-noncoop"])
    ap.add_argument("--tenants", type=str, default="qwen2-1.5b,gemma3-4b,xlstm-350m")
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    if args.scheduler:
        _run_scheduled(args)
        return
    if not args.arch:
        ap.error("--arch or --scheduler required")
    _run_single(args)


def _run_single(args) -> None:
    from repro.configs import get_config, get_smoke
    from repro.runtime import Trainer, TrainerConfig
    from repro.runtime.trainer import SimulatedFailure

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_test_mesh

        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_test_mesh(shape, ("data", "model")[: len(shape)])
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix=f"oef-train-{cfg.name}-")
    t = Trainer(cfg, TrainerConfig(seq_len=args.seq_len, global_batch=args.batch,
                                   peak_lr=args.lr, total_steps=args.steps,
                                   ckpt_dir=ckpt, ckpt_every=args.ckpt_every),
                mesh=mesh)
    print(f"training {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps, ckpt -> {ckpt}")
    try:
        out = t.run(args.steps, fail_at=args.fail_at)
    except SimulatedFailure as e:
        print(f"!! {e} — recovering from checkpoint")
        step = t.restore_latest()
        print(f"   restored step {step}; resuming")
        out = t.run(args.steps - step)
    print(f"done: step {out['final_step']}, "
          f"loss {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f}, "
          f"{out['steps'] / max(out['seconds'], 1e-9):.2f} steps/s")


def _run_scheduled(args) -> None:
    from repro.configs import get_smoke
    from repro.core import ClusterSpec, ProfilingAgent, Tenant, WorkloadCost
    from repro.core import oef
    from repro.core.placement import RoundingPlacer
    from repro.models.config import ShapeCell
    from repro.models.costs import model_flops, param_bytes
    from repro.runtime import Trainer, TrainerConfig

    cluster = ClusterSpec(types=("tpu-v5e", "tpu-v4", "tpu-v5p", "tpu-v6e"),
                          m=(8, 8, 4, 4))
    agent = ProfilingAgent()
    names = [n.strip() for n in args.tenants.split(",")]
    cell = ShapeCell("sched", "train", args.seq_len, args.batch)
    tenants, trainers = [], {}
    for name in names:
        cfg = get_smoke(name)
        cost = WorkloadCost(name=name, flops=model_flops(cfg, cell) / args.batch,
                            hbm_bytes=float(param_bytes(cfg)) * 3)
        profile = agent.profile(cost)
        tenants.append(Tenant(name=name, job_types=(profile,)))
        trainers[name] = Trainer(cfg, TrainerConfig(
            seq_len=args.seq_len, global_batch=args.batch, peak_lr=args.lr,
            total_steps=10_000,
            ckpt_dir=tempfile.mkdtemp(prefix=f"oef-{name}-"), ckpt_every=20))
        print(f"tenant {name}: speedups {np.round(np.asarray(profile.speedup), 3)}")
    placer = RoundingPlacer(len(tenants), cluster.m)
    mode = "cooperative" if args.scheduler == "oef-coop" else "noncooperative"
    for rnd in range(args.rounds):
        ta = oef.evaluate_tenants(tenants, cluster, mode=mode)
        real = placer.round_shares(ta.X)
        print(f"\nround {rnd}: grants\n{real}")
        for ti, tenant in enumerate(tenants):
            units = float(np.dot(np.asarray(tenant.job_types[0].speedup), real[ti]))
            steps = max(1, int(units))
            out = trainers[tenant.name].run(steps)
            print(f"  {tenant.name}: {steps} steps, "
                  f"loss -> {out['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
