"""Training launcher: ``PYTHONPATH=src python -m repro.launch.train
--arch <id> [--smoke] [--steps N] [--mesh dxm] ...``

On real hardware the same entry point runs under multi-host jax.distributed
(one process per host; jax.make_mesh spans hosts transparently). In this
container it runs CPU-scale smoke configs end-to-end with the full
substrate: FSDP+TP sharding, EP MoE, fault tolerance, checkpointing.
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--devices", type=int, default=0,
                    help="force host device count (spawns CPU devices)")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x4 -> mesh (data=2, model=4) with EP MoE")
    ap.add_argument("--moe-impl", default="ep_dedup",
                    help="local | ep_flat | ep_dedup (EP dispatch protocol"
                         " used by the meshed train step)")
    ap.add_argument("--wire", default="fp8",
                    help="EP dispatch wire precision: fp8 | bf16 | fp32")
    ap.add_argument("--microbatches", type=int, default=2, choices=(1, 2),
                    help="2 = dual anti-phase microbatch overlap (paper"
                         " §2.3.1); 1 = single-batch step")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax

    from repro.configs.base import get_config, smoke_config
    from repro.launch import compile_cache
    from repro.launch.mesh import make_mesh
    from repro.parallel import context as pctx_mod
    from repro.train.trainer import Trainer, TrainConfig

    compile_cache.enable()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)

    ctx = pctx_mod.ParallelCtx()
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data", "model")[:len(shape)]
                         if len(shape) == 2 else ("pod", "data", "model"))
        dp = (("data",) if len(shape) == 2 else ("pod", "data"))
        ctx = pctx_mod.ParallelCtx(
            mesh=mesh, dp_axes=dp,
            moe_impl=args.moe_impl if cfg.moe else "local",
            wire=args.wire, microbatches=args.microbatches)
    tc = TrainConfig(peak_lr=args.lr, warmup=max(args.steps // 10, 1),
                     total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                     ckpt_every=max(args.steps // 4, 1))
    # ctx is threaded explicitly: the step function is built from it
    # (EP impl + wire + microbatch overlap), not from ambient globals
    tr = Trainer(cfg, tc, global_batch=args.batch, seq_len=args.seq,
                 ctx=ctx)
    out = tr.run(args.steps)
    h = out["history"]
    print(f"[train] {args.arch}: step {out['final_step']}, "
          f"loss {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f}, "
          f"restarts {out['restarts']}")
    if args.mesh:
        print(f"[train] mesh {out['mesh_shape']} moe_impl={args.moe_impl} "
              f"wire={args.wire} microbatches={args.microbatches} "
              f"straggler_events={len(out['straggler_events'])}")


if __name__ == "__main__":
    main()
