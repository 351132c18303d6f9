"""Serving launcher: fused-chunk batched decode with optional MTP
speculative drafting and prefill/decode disaggregation.

``PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v3-671b
--smoke --requests 8 [--disagg] [--mtp] [--chunk 8] [--temperature 0.7]``

Requests are queued with ``submit()``; ``step()``/``run()`` admit them into
slots (bucketed jitted prefill + jitted cache splice) and drive fused
k-step decode chunks — the steady-state dispatch count is printed so the
one-dispatch-per-chunk property is visible from the CLI.

``--paged [--page-size 8] [--pool-pages N] [--page-storage fp8|bf16]``
swaps in the paged block-pool cache (docs/serving.md §4): page-granular
admission plus FP8 page storage; the pool occupancy and bytes/token are
printed alongside the dispatch stats.

``--gateway N`` serves the request stream through N in-process engine
replicas (one shared parameter set) behind the fault-tolerant gateway
(docs/serving.md §6): health-checked least-loaded routing, idempotent
retry, load shedding. ``--chaos "6=crash:0,9=slow:1"`` injects faults on
the gateway's tick clock (kinds: crash, hang, slow, flaky-admit — the
``tick=kind[:replica]`` grammar is ``repro/faultspec.py``'s, shared with
the training launcher's ``--chaos``); the run prints goodput, retries,
and per-replica health so recovery is visible from the CLI.

``--mesh D,M`` runs the whole hot path sharded over a ``(data, model)``
mesh (docs/serving.md §5): params per the serving inference rules,
batch/slots over ``data``, heads + experts over ``model``, with
``--moe-impl ep_flat|ep_dedup`` routing MoE through the EP shard_map at
``--wire fp8|bf16|fp32`` dispatch precision; the decode all-to-all
bytes/step are printed from the compiled lowering. With ``--disagg``,
``--prefill-mesh D,M`` puts the prefill pool on its own (differently
sized) mesh — the cross-mesh handoff stages through host memory.
Requires enough devices (e.g. ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` for a CPU dry run).
"""
from __future__ import annotations

import argparse

import numpy as np


def _make_ctx(spec, moe_impl: str, wire: str):
    """'D,M' (or launch/train.py's 'DxM') -> ParallelCtx over a
    (data, model) mesh; None passes through (the zero-config
    single-device default)."""
    if not spec:
        return None
    from repro.launch.mesh import make_mesh
    from repro.parallel import context as pctx_mod
    try:
        shape = tuple(int(s) for s in spec.replace("x", ",").split(","))
    except ValueError:
        raise SystemExit(f"--mesh expects 'D,M' or 'DxM' (got {spec!r})")
    if len(shape) != 2:
        raise SystemExit(f"--mesh expects 'D,M' or 'DxM' (got {spec!r})")
    mesh = make_mesh(shape, ("data", "model"))
    return pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                                moe_impl=moe_impl, wire=wire)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--mtp", action="store_true")
    ap.add_argument("--disagg", action="store_true")
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pool-pages", type=int, default=None)
    ap.add_argument("--page-storage", default="fp8",
                    choices=("fp8", "bf16"))
    ap.add_argument("--host-tier-pages", type=int, default=None,
                    metavar="N",
                    help="paged only: host-memory KV tier of N pages "
                         "behind the device pool (docs/serving.md §8) — "
                         "suspended requests and cold prefix pages spill "
                         "over the staged PCIe hop and prefetch back")
    ap.add_argument("--gateway", type=int, default=0, metavar="N",
                    help="serve through N engine replicas behind the "
                         "fault-tolerant gateway (docs/serving.md §6)")
    ap.add_argument("--chaos", default=None, metavar="T=KIND[:R],..",
                    help="gateway only: inject faults on the tick clock, "
                         "e.g. '6=crash:0,9=slow:1' (kinds: crash, hang, "
                         "slow, flaky-admit, pcie_slow, pcie_drop, "
                         "tier_full — the tier kinds need "
                         "--host-tier-pages)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="gateway only: re-dispatch budget per request")
    ap.add_argument("--mesh", default=None, metavar="D,M",
                    help="shard serving over a (data, model) mesh, e.g. "
                         "'2,4' (default: single-device)")
    ap.add_argument("--prefill-mesh", default=None, metavar="D,M",
                    help="disagg only: separate mesh for the prefill "
                         "pool (cross-mesh handoff via host)")
    ap.add_argument("--moe-impl", default="ep_flat",
                    choices=("local", "ep_flat", "ep_dedup"),
                    help="MoE dispatch when meshed (ignored unmeshed)")
    ap.add_argument("--wire", default="fp8",
                    choices=("fp8", "bf16", "fp32"),
                    help="EP dispatch wire precision when meshed")
    args = ap.parse_args()
    paged_kw = dict(paged=args.paged, page_size=args.page_size,
                    pool_pages=args.pool_pages,
                    page_storage=args.page_storage)
    tier_kw = {}
    if args.host_tier_pages is not None:
        if not args.paged:
            raise SystemExit("--host-tier-pages requires --paged")
        if args.disagg:
            raise SystemExit("--host-tier-pages does not apply to the "
                             "--disagg decode pool yet")
        tier_kw["host_tier_pages"] = args.host_tier_pages
    from repro.launch import compile_cache
    compile_cache.enable()
    ctx = _make_ctx(args.mesh, args.moe_impl, args.wire)
    if args.prefill_mesh and not args.disagg:
        raise SystemExit("--prefill-mesh only applies with --disagg")

    from repro.configs.base import get_config, smoke_config
    from repro.serve.disagg import Disaggregator
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.speculative import measured
    from repro.serve.tier import TieredServeEngine

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)

    if args.chaos and not args.gateway:
        raise SystemExit("--chaos only applies with --gateway")
    if args.gateway:
        if args.disagg or args.mesh:
            raise SystemExit("--gateway replicas are single-device "
                             "engines (no --disagg/--mesh)")
        from repro import faultspec
        from repro.serve.fault import ServeFaultInjector
        from repro.serve.gateway import Gateway

        injector = None
        if args.chaos:
            schedule = faultspec.parse_schedule(args.chaos,
                                                faultspec.SERVE_KINDS)
            injector = ServeFaultInjector(schedule)
        gw = Gateway(cfg, replicas=args.gateway, slots=args.slots,
                     max_len=args.max_len, chunk=args.chunk,
                     temperature=args.temperature, top_k=args.top_k,
                     max_retries=args.max_retries, injector=injector,
                     **paged_kw, **tier_kw)
        grs = [gw.submit((np.arange(5 + i * 2) * (i + 3)) % cfg.vocab_size,
                         max_new=args.max_new)
               for i in range(args.requests)]
        gw.run_until_done()
        s = gw.stats
        print(f"[serve] gateway x{args.gateway}: "
              f"{s['completed']}/{s['submitted']} done in {s['ticks']} "
              f"ticks, retries {s['retries']}, deaths "
              f"{s['replica_deaths']}, shed {s['shed']}, timed_out "
              f"{s['timed_out']}, affinity {s['affinity_hits']}")
        print(f"[serve] replica health: {gw.registry.states()}")
        if injector is not None and injector.events:
            print(f"[serve] chaos fired: {injector.events}")
        if args.host_tier_pages is not None:
            for rep in gw.registry.replicas.values():
                ts = rep.engine.tier_stats()
                print(f"[serve] replica {rep.rid} tier: suspensions "
                      f"{ts['suspensions']}, resumes {ts['resumes']}, "
                      f"stalls {ts['prefetch_stalls']}, degraded "
                      f"{ts['degraded']}, retries {ts['retries']}, "
                      f"host occupancy {ts['host_occupancy']:.2f}")
        for g in grs[:3]:
            print(f"  req {g.gid}: prompt {list(g.prompt[:6])}... -> "
                  f"{g.delivered[:args.max_new]} [{g.state}]")
        return

    reqs = [Request(i, (np.arange(5 + i * 2) * (i + 3)) % cfg.vocab_size,
                    max_new=args.max_new) for i in range(args.requests)]

    if args.disagg:
        eng = Disaggregator(cfg, decode_slots=args.slots,
                            max_len=args.max_len, use_mtp=args.mtp,
                            chunk=args.chunk, temperature=args.temperature,
                            top_k=args.top_k, ctx=ctx,
                            prefill_ctx=_make_ctx(args.prefill_mesh,
                                                  args.moe_impl, args.wire),
                            **paged_kw)
        for r in reqs:
            eng.submit(r)
        eng.run()
        stats = eng.decode.stats
        if eng.cross_mesh:
            # prefills ran on the separate prefill pool — surface its
            # counters too, or the operator sees prefills=0 for a run
            # that did N of them
            print(f"[serve] disaggregated (cross-mesh): handoff "
                  f"{eng.handoff_bytes / 1e6:.2f} MB, decode {stats}, "
                  f"prefill {eng.prefill_pool.stats}")
        else:
            print(f"[serve] disaggregated: handoff "
                  f"{eng.handoff_bytes / 1e6:.2f} MB, {stats}")
        prefill_eng = eng.prefill_pool
        eng = eng.decode
    else:
        engine_cls = TieredServeEngine if tier_kw else ServeEngine
        eng = engine_cls(cfg, slots=args.slots, max_len=args.max_len,
                         use_mtp=args.mtp, chunk=args.chunk,
                         temperature=args.temperature, top_k=args.top_k,
                         ctx=ctx, **paged_kw, **tier_kw)
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        print(f"[serve] {eng.stats} acceptance="
              f"{eng.acceptance_rate():.2f}")
        prefill_eng = eng
    if eng.meshed:
        m = eng.ctx.mesh
        print(f"[serve] sharded over mesh "
              f"{dict(zip(m.axis_names, m.devices.shape))} "
              f"(EP degree {eng.ctx.model_size}), "
              f"moe_impl={args.moe_impl}, wire={args.wire}, decode "
              f"all-to-all {eng.decode_alltoall_bytes()} B/step (lowered)")
    # fused decode chunks per token the chunks emitted
    decode_dispatches = eng.stats["decode_dispatches"]
    decode_tokens = eng.stats["decode_tokens"]
    if decode_tokens:
        print(f"[serve] decode dispatches/token = "
              f"{decode_dispatches / decode_tokens:.3f} "
              f"(chunk={args.chunk}, prefill buckets compiled: "
              f"{prefill_eng.compiled_prefill_buckets})")
    if args.paged:
        print(f"[serve] paged cache ({args.page_storage}): "
              f"{eng.cache_bytes_per_token():.0f} B/token, "
              f"pool {eng.pool_stats()}, "
              f"peak pages {eng.stats['peak_pages_used']}")
    if args.host_tier_pages is not None:
        ts = eng.tier_stats()
        print(f"[serve] host tier ({args.host_tier_pages} pages): "
              f"suspensions {ts['suspensions']}, resumes {ts['resumes']}, "
              f"spilled {ts['spilled_pages']}p/{ts['spill_bytes']}B, "
              f"fetched {ts['fetched_pages']}p/{ts['fetch_bytes']}B, "
              f"stalls {ts['prefetch_stalls']}, degraded {ts['degraded']}, "
              f"peak resident {ts['peak_resident_pages']}p "
              f"(device pool {eng.pool_pages}p)")
    if args.mtp and not eng.use_mtp:
        print(f"[serve] --mtp ignored: {cfg.name} has no MTP module")
    elif args.mtp:
        m = measured(eng)
        print(f"[serve] MTP speedup model: acceptance={m.acceptance:.2f} "
              f"-> {m.tps_multiplier:.2f}x TPS")
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt {list(r.prompt[:6])}... -> "
              f"{r.out[:args.max_new]}")


if __name__ == "__main__":
    main()
