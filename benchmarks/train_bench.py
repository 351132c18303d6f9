"""Distributed training benchmark: tokens/s, step time, and HLO
all-to-all wire bytes for ep_flat vs ep_dedup (ISSUE 3 acceptance
metric), on a forced-8-device host mesh.

    PYTHONPATH=src python benchmarks/train_bench.py --out BENCH_train.json

Measures the meshed dual-microbatch train step (sharded params/opt, EP
MoE under shard_map, FP8 dispatch wire) end-to-end through ``Trainer``
on a (2, 4) = data x model mesh, with a DeepSeekMoE-style config whose
``top_k=4 > group_limit=2`` makes the paper's §4.3 dedup reduction
visible: ep_dedup must move strictly fewer all-to-all bytes than
ep_flat (the M·t vs k·t wire accounting, read off the step's lowering
via ``parallel.overlap.collective_bytes`` — intra-group ppermute hops,
the fast-fabric NVLink analogue, intentionally don't count).

Device count is locked at first backend init, so ``benchmarks/run.py``
invokes this file as a subprocess (the parent's 1-device jax stays
untouched); run directly it forces 8 host devices itself.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MESH_SHAPE = (2, 4)


def bench_config():
    from repro.configs.base import ModelConfig, MoEConfig
    return ModelConfig(
        name="train-bench-moe", family="moe", num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=512, head_dim=32,
        attention="gqa",
        moe=MoEConfig(num_experts=8, top_k=4, expert_ff=64, num_shared=1,
                      shared_ff=64, num_groups=4, group_limit=2, group_top=2,
                      capacity_factor=2.0, router_bias=True,
                      score_fn="sigmoid", layout="all"),
        dtype="float32", param_dtype="float32")


def bench_impl(impl: str, *, batch: int = 8, seq: int = 32, steps: int = 4,
               wire: str = "fp8") -> dict:
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.parallel import context as pctx_mod
    from repro.parallel import overlap
    from repro.train.trainer import Trainer, TrainConfig

    cfg = bench_config()
    mesh = make_mesh(MESH_SHAPE, ("data", "model"))
    ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                               moe_impl=impl, wire=wire)
    tc = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=steps + 1)
    tr = Trainer(cfg, tc, global_batch=batch, seq_len=seq, ctx=ctx)

    # wire bytes straight off the full train step's lowering (fwd + bwd,
    # per scan iteration — identical loop structure for both impls)
    batch0 = {k: jnp.asarray(v) for k, v in tr.data.batch_at(0).items()}
    batch0 = jax.device_put(batch0, tr._batch_sharding(batch0))
    txt = tr._jit_step.lower(tr.params, tr.opt_state, batch0,
                             jnp.asarray(0)).as_text()
    a2a_bytes = overlap.collective_bytes(txt, "all_to_all")
    a2a_ops = txt.count("stablehlo.all_to_all")

    tr.run(1)                      # compile + first step
    t0 = time.perf_counter()
    out = tr.run(steps)
    wall = time.perf_counter() - t0
    step_s = wall / steps
    return {
        "impl": impl,
        "wire": wire,
        "mesh": list(MESH_SHAPE),
        "batch": batch,
        "seq": seq,
        "steps": steps,
        "tokens_per_s": batch * seq / step_s,
        "step_ms": step_s * 1e3,
        "alltoall_bytes": a2a_bytes,
        "alltoall_ops": a2a_ops,
        "loss_first": out["history"][0]["loss"],
        "loss_last": out["history"][-1]["loss"],
        "backend": jax.default_backend(),
    }


def run(out: str | None = None, steps: int = 4) -> list:
    rows = [bench_impl(impl, steps=steps)
            for impl in ("ep_flat", "ep_dedup")]
    by = {r["impl"]: r for r in rows}
    summary = {
        "suite": "train_bench",
        "rows": rows,
        "dedup_bytes_reduction": (by["ep_flat"]["alltoall_bytes"]
                                  / max(by["ep_dedup"]["alltoall_bytes"], 1)),
    }
    if out:
        with open(out, "w") as f:
            json.dump(summary, f, indent=2)
    return rows


def suite():
    """benchmarks/run.py hook: runs in a subprocess so the forced
    8-device host platform never leaks into the parent's jax."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the child measures on a virtual CPU mesh; it must never reach for
    # an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    outf = "BENCH_train.json"
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--out", outf],
        capture_output=True, text=True, env=env, timeout=1200)
    if r.returncode != 0:
        yield ("train_bench_FAILED", 0.0, r.stderr[-200:].replace(",", ";"))
        return
    with open(outf) as f:
        data = json.load(f)
    for row in data["rows"]:
        yield (f"train_step_{row['impl']}", row["step_ms"] * 1e3,
               f"tok/s={row['tokens_per_s']:.1f} "
               f"a2a_bytes={row['alltoall_bytes']}")
    yield ("train_ep_dedup_reduction", 0.0,
           f"{data['dedup_bytes_reduction']:.2f}x fewer a2a bytes")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_train.json")
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    rows = run(out=args.out, steps=args.steps)
    for r in rows:
        print(f"[train_bench] {r['impl']}: {r['tokens_per_s']:.1f} tok/s, "
              f"{r['step_ms']:.1f} ms/step, "
              f"a2a {r['alltoall_bytes']} B/scan-iter ({r['alltoall_ops']} ops), "
              f"loss {r['loss_first']:.3f} -> {r['loss_last']:.3f}")
    print(f"[train_bench] wrote {args.out}")


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    main()
