"""Serving hot-path benchmark: tokens/s, TTFT, dispatches per generated
token (ISSUE 2), and the paged FP8 cache's bytes/token + capacity levers
(ISSUE 4).

Measures the fused serving engine on one MLA config (deepseek-v3) and one
GQA config (qwen3-14b) at smoke scale, and writes ``BENCH_serve.json``:

    PYTHONPATH=src python benchmarks/serve_bench.py --out BENCH_serve.json

Per arch, three rows:

* ``dense``      — the ring-buffer engine: steady-state decode
  dispatches/token (one fused dispatch per ``chunk`` steps), warm TTFT,
  tokens/s, and the dense cache's bytes per token of context capacity.
* ``paged-bf16`` — the block-pool engine at native storage. Its token
  streams must be **bitwise-equal** to dense (``tokens_equal_dense``);
  CI asserts this from the JSON.
* ``paged-fp8``  — the block-pool engine at FP8 storage (per-token
  scales): ``cache_bytes_per_token`` ≤ 0.55x dense, pool occupancy, and
  ``max_resident_slots_at_dense_budget`` — how many *requests* of this
  stream fit in the memory the dense engine spends on ``slots`` rings
  (page-granular reservation x fp8 bytes; CI asserts ≥ 2x).

The MLA row also carries the analytic Table-1 numbers at the production
config (``kv_bytes_per_token``: 70272 B bf16, 35624 B fp8).

**Sharded rows** (ISSUE 5): the meshed serving engine on a (2, 4) =
data x model host mesh, per EP impl (``ep_flat`` / ``ep_dedup``), using
the train bench's MoE config (``top_k=4 > group_limit=2``, so the
paper's §4.3 node-limited dedup reduction is visible at decode): sharded
decode tokens/s, token-stream equality vs the single-device engine, and
the decode **all-to-all bytes per step** read off the compiled lowering
via ``parallel.overlap.collective_bytes`` — CI asserts ep_dedup moves
strictly fewer bytes than ep_flat from the JSON. Device count is locked
at first backend init, so ``run()`` collects these rows in an 8-device
subprocess (``--sharded-only``); the parent's jax stays 1-device.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

# (arch, engine kwargs) benchmarked per run; smoke-scaled so the suite runs
# on the CPU CI runner in seconds per config.
CONFIGS = [
    ("deepseek-v3-671b", dict(use_mtp=True)),
    ("qwen3-14b", dict()),
]

PAGE_SIZE = 8


def _smoke_cfg(arch: str):
    import dataclasses

    from repro.configs.base import get_config, smoke_config
    cfg = smoke_config(get_config(arch))
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


def _mkreq(rid: int, cfg, max_new: int):
    from repro.serve.engine import Request
    return Request(rid, (np.arange(5 + rid * 2) * (rid + 3))
                   % cfg.vocab_size, max_new=max_new)


def _stream(eng, cfg, requests: int, max_new: int):
    """Submit the canonical request stream and return its token streams
    (greedy + deterministic params, so layouts are comparable)."""
    reqs = [_mkreq(rid, cfg, max_new) for rid in range(requests)]
    for r in reqs:
        eng.submit(r)
    tic = time.perf_counter()
    eng.run_until_done()
    wall = time.perf_counter() - tic
    assert all(r.done for r in reqs)
    return [r.out for r in reqs], wall


def bench_arch(arch: str, *, slots: int = 2, max_len: int = 64,
               chunk: int = 8, requests: int = 6, max_new: int = 17,
               use_mtp: bool = False) -> dict:
    """Dense-engine row: hot-path metrics + dense cache bytes/token."""
    import jax
    from repro.serve.engine import ServeEngine

    cfg = _smoke_cfg(arch)
    eng = ServeEngine(cfg, slots=slots, max_len=max_len, chunk=chunk,
                      use_mtp=use_mtp)

    def mkreq(rid):
        return _mkreq(rid, cfg, max_new)

    # warmup: compile every prefill bucket the measured requests will hit,
    # plus the splice and the fused decode chunk — TTFT below is warm-path
    for rid in (0, requests - 1):
        eng.add_request(mkreq(rid))
        eng.run_until_done()

    # TTFT: prefill dispatch -> first token on host, per request
    ttfts = []
    reqs = [mkreq(i) for i in range(requests)]
    for r in reqs:
        t0 = time.perf_counter()
        eng.prefill_request(r)
        ttfts.append(time.perf_counter() - t0)

    # steady-state decode: fill slots, then time fused chunks only
    handoffs = [(r, *eng.prefill_request(r)) for r in reqs]
    for r, first, cache1 in handoffs[:slots]:
        eng.admit_prefilled(r, first, cache1, eng.free_slots()[0])
    rest = handoffs[slots:]
    s0 = dict(eng.stats)
    tic = time.perf_counter()
    while any(x is not None for x in eng.active) or rest:
        while rest and eng.free_slots():
            r, first, cache1 = rest.pop(0)
            eng.admit_prefilled(r, first, cache1, eng.free_slots()[0])
        eng.step()
    wall = time.perf_counter() - tic
    # pure steady-state decode: the engine's decode-chunk counters leave
    # admission work (splice dispatches, prefill-produced first tokens)
    # out, so the metric is chunks per token — as in launch/serve.py
    decode_tokens = eng.stats["decode_tokens"] - s0["decode_tokens"]
    decode_dispatches = (eng.stats["decode_dispatches"]
                         - s0["decode_dispatches"])

    # parity-reference stream on the warm engine (fresh request objects)
    stream, _ = _stream(eng, cfg, requests, max_new)

    row = {
        "arch": arch,
        "family": cfg.family,
        "attention": cfg.attention,
        "cache_layout": "dense",
        "slots": slots,
        "chunk": chunk,
        "requests": requests,
        "max_new": max_new,
        "decode_tokens": int(decode_tokens),
        "decode_dispatches": int(decode_dispatches),
        "decode_dispatches_per_token": decode_dispatches / max(decode_tokens, 1),
        "tokens_per_s": decode_tokens / wall if wall else 0.0,
        "ttft_ms_mean": float(np.mean(ttfts) * 1e3),
        "ttft_ms_p50": float(np.median(ttfts) * 1e3),
        "cache_bytes_per_token": eng.cache_bytes_per_token(),
        "prefill_buckets_compiled": eng.compiled_prefill_buckets,
        "prefill_traces": eng.trace_counts["prefill"],
        "splice_traces": eng.trace_counts["splice"],
        "decode_traces": eng.trace_counts["decode"],
        "backend": jax.default_backend(),
    }
    if cfg.attention == "mla":
        from repro.configs.base import get_config
        from repro.core import mla as mla_mod
        full = get_config(arch)
        row["kv_bytes_per_token_bf16"] = mla_mod.kv_bytes_per_token(
            full, storage="bf16")
        row["kv_bytes_per_token_fp8"] = mla_mod.kv_bytes_per_token(
            full, storage="fp8")
    if use_mtp:
        # acceptance is measured on a dedicated probe whose draft head is
        # aligned to copy the main unembedding (``mtp_align_head``): the
        # draft at step p then greedily re-predicts the token emitted at
        # p, so on a repetitive prompt the acceptance rate is positive by
        # construction. Random untrained draft weights would agree with
        # the main model only by vocab-sized accident — the old 0.0 here
        # was the dead draft path (no KV ring), not a small model.
        from repro.core.mtp import mtp_align_head
        from repro.serve.engine import Request
        probe = ServeEngine(cfg, params=mtp_align_head(eng.params),
                            slots=1, max_len=64, chunk=chunk, use_mtp=True)
        pr = Request(0, np.tile(np.array([7, 7, 7, 7], np.int32), 5),
                     max_new=24, seed=0)
        probe.submit(pr)
        probe.run_until_done()
        row["mtp_acceptance"] = probe.acceptance_rate()
        row["mtp_drafts"] = probe.stats["drafts"]
        row["mtp_accepted"] = probe.stats["accepted_drafts"]
    return row, stream


PREFIX_TOKENS = 64           # 8 pages shared across the workload
PREFIX_CHUNK = 16            # prefill chunk -> 2-page share granularity


def bench_prefix_sharing(arch: str = "qwen3-14b", *, requests: int = 8,
                         max_new: int = 8, max_len: int = 128,
                         chunk: int = 8, slots: int = 4,
                         pool_pages: int = 64) -> dict:
    """Shared-prefix workload row (ISSUE 8 scheduler): ``requests``
    prompts share a ``PREFIX_TOKENS``-token prefix (system-prompt shape).
    The chunked-prefill engine indexes prefix pages as they are written,
    so each staggered arrival claims the shared run copy-on-write and
    skips its chunks. Reports the admission hit rate, pool pages saved vs
    an unshared run, bitwise equality vs whole-prompt prefill (bf16), and
    TTFT p50 with/without chunked prefill."""
    import jax
    from repro.serve.engine import Request, ServeEngine

    cfg = _smoke_cfg(arch)
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, cfg.vocab_size, size=PREFIX_TOKENS)
    tails = [rng.integers(1, cfg.vocab_size, size=3 + rid)
             for rid in range(requests)]
    prompts = [np.concatenate([prefix, t]).astype(np.int32) for t in tails]
    # warmup prompts: same lengths, unrelated prefix — compiles every
    # dispatch without seeding the measured prefix into the index
    warm_prefix = rng.integers(1, cfg.vocab_size, size=PREFIX_TOKENS)
    warm = [np.concatenate([warm_prefix, t]).astype(np.int32)
            for t in tails]

    def measure(eng):
        """Warm the engine, then submit the workload staggered (each
        request after the previous one's first token) and collect TTFTs."""
        for rid, p in enumerate(warm):
            eng.submit(Request(1000 + rid, p, max_new=max_new))
        eng.run_until_done()
        reqs = [Request(rid, p, max_new=max_new)
                for rid, p in enumerate(prompts)]
        s0 = dict(eng.prefix_stats())
        peak0 = eng.stats["peak_pages_used"]
        eng.stats["peak_pages_used"] = 0
        ttfts = []
        tic = time.perf_counter()
        for r in reqs:
            t0 = time.perf_counter()
            eng.submit(r)
            while not r.out:
                eng.step()
            ttfts.append(time.perf_counter() - t0)
        eng.run_until_done()
        wall = time.perf_counter() - tic
        assert all(r.done for r in reqs)
        st = eng.prefix_stats()
        hits = st["hits"] - s0["hits"]
        lookups = st["lookups"] - s0["lookups"]
        eng.stats["peak_pages_used"] = max(eng.stats["peak_pages_used"],
                                           peak0)
        return reqs, [r.out for r in reqs], ttfts, hits, lookups, wall

    whole = ServeEngine(cfg, slots=slots, max_len=max_len, chunk=chunk,
                        paged=True, page_size=PAGE_SIZE,
                        pool_pages=pool_pages, page_storage="bf16")
    _, stream_whole, ttft_whole, _, _, _ = measure(whole)

    eng = ServeEngine(cfg, params=whole.params, slots=slots,
                      max_len=max_len, chunk=chunk, paged=True,
                      page_size=PAGE_SIZE, pool_pages=pool_pages,
                      page_storage="bf16", prefill_chunk=PREFIX_CHUNK)
    reqs, stream, ttfts, hits, lookups, wall = measure(eng)

    unshared_sum = int(sum(eng.pages_needed(r) for r in reqs))
    shared_sum = unshared_sum - hits
    return {
        "arch": arch,
        "family": cfg.family,
        "attention": cfg.attention,
        "cache_layout": "paged-bf16-shared-prefix",
        "workload": "shared-prefix",
        "slots": slots,
        "chunk": chunk,
        "prefill_chunk": PREFIX_CHUNK,
        "requests": requests,
        "max_new": max_new,
        "page_size": PAGE_SIZE,
        "pool_pages": pool_pages,
        "prefix_tokens": PREFIX_TOKENS,
        "decode_tokens": int(sum(len(o) for o in stream)),
        "tokens_per_s": sum(len(o) for o in stream) / wall if wall else 0.0,
        "prefix_hits": int(hits),
        "prefix_lookups": int(lookups),
        "prefix_hit_rate": hits / lookups if lookups else 0.0,
        "pages_unshared_sum": unshared_sum,
        "pages_shared_sum": int(shared_sum),
        "pages_saved_vs_unshared": unshared_sum / max(shared_sum, 1),
        "tokens_equal_unshared": stream == stream_whole,
        "ttft_ms_p50_chunked": float(np.median(ttfts) * 1e3),
        "ttft_ms_p50_whole_prompt": float(np.median(ttft_whole) * 1e3),
        "pool_pages_free_end": eng.free_pages(),
        "chunk_prefills": eng.stats["chunk_prefills"],
        "backend": jax.default_backend(),
    }


TIER_POOL_PAGES = 16         # device pool: exactly 2 full slots of KV
TIER_HOST_PAGES = 48         # host tier: 3x the device pool (§4.5 hop)
TIER_QUANTUM = 4             # decode ticks before a rotation is eligible


def bench_kv_tier(arch: str = "qwen3-14b", *, requests: int = 14,
                  max_new: int = 32, max_len: int = 64, chunk: int = 4,
                  slots: int = 2, prefill_chunk: int = 8) -> dict:
    """Host KV-tier workload row (ISSUE 9): ``requests`` requests, ~3.4x
    more resident context than the device pool holds, complete without an
    admission failure because refcount-0 / quantum-expired pages spill to
    the host tier and are prefetched back before the decode window needs
    them. Reports resident-context tokens vs the device-only pool, the
    no-stall prefetch gate, bitwise stream parity vs the untiered engine,
    and chaos parity under ``pcie_slow`` / ``pcie_drop`` (transfer
    retry/backoff + continuation re-queue must not change any stream)."""
    import jax
    from repro.serve.engine import ServeEngine
    from repro.serve.fault import ServeFaultInjector, TierFaultAdapter
    from repro.serve.tier import TierConfig, TieredServeEngine

    cfg = _smoke_cfg(arch)

    def mkreq(rid):
        r = _mkreq(rid, cfg, max_new)
        r.seed = rid              # seeded so a degrade re-queue is bitwise
        return r

    def run_stream(eng):
        reqs = [mkreq(rid) for rid in range(requests)]
        for r in reqs:
            eng.submit(r)
        tic = time.perf_counter()
        eng.run_until_done()
        wall = time.perf_counter() - tic
        assert all(r.done for r in reqs)
        return [r.out for r in reqs], wall

    # untiered reference: same pool, no host tier — the PR 8 scheduler
    # completes the stream by evict-and-requeue; its streams are the
    # bitwise bar the tiered engine must meet
    base = ServeEngine(cfg, slots=slots, max_len=max_len, chunk=chunk,
                       paged=True, page_size=PAGE_SIZE,
                       pool_pages=TIER_POOL_PAGES, page_storage="bf16",
                       prefill_chunk=prefill_chunk)
    stream_untiered, _ = run_stream(base)

    def tiered_engine(faults=None):
        return TieredServeEngine(
            cfg, params=base.params, slots=slots, max_len=max_len,
            chunk=chunk, paged=True, page_size=PAGE_SIZE,
            pool_pages=TIER_POOL_PAGES, page_storage="bf16",
            prefill_chunk=prefill_chunk, host_tier_pages=TIER_HOST_PAGES,
            tier_config=TierConfig(quantum=TIER_QUANTUM),
            tier_faults=faults)

    eng = tiered_engine()
    stream, wall = run_stream(eng)
    ts = eng.tier_stats()

    # chaos runs: same workload with the tier link degraded mid-decode
    # (self-clocked adapter: the engine advances the injector per step)
    def chaos(kind):
        inj = ServeFaultInjector(schedule={6: kind})
        ceng = tiered_engine(TierFaultAdapter(inj, replica=0))
        s, _ = run_stream(ceng)
        return s, ceng.tier_stats()

    stream_slow, ts_slow = chaos("pcie_slow")
    stream_drop, ts_drop = chaos("pcie_drop")

    resident_tokens = ts["peak_resident_pages"] * PAGE_SIZE
    device_only_tokens = TIER_POOL_PAGES * PAGE_SIZE
    decode_tokens = int(sum(len(o) for o in stream))
    return {
        "arch": arch,
        "family": cfg.family,
        "attention": cfg.attention,
        "cache_layout": "paged-bf16-kv-tier",
        "workload": "kv-tier",
        "slots": slots,
        "chunk": chunk,
        "prefill_chunk": prefill_chunk,
        "requests": requests,
        "max_new": max_new,
        "page_size": PAGE_SIZE,
        "pool_pages": TIER_POOL_PAGES,
        "host_tier_pages": TIER_HOST_PAGES,
        "tier_quantum": TIER_QUANTUM,
        "decode_tokens": decode_tokens,
        "tokens_per_s": decode_tokens / wall if wall else 0.0,
        "suspensions": ts["suspensions"],
        "resumes": ts["resumes"],
        "spilled_pages": ts["spilled_pages"],
        "fetched_pages": ts["fetched_pages"],
        "spill_bytes": ts["spill_bytes"],
        "fetch_bytes": ts["fetch_bytes"],
        "prefix_spilled": ts["prefix_spilled"],
        "prefetch_stalls": ts["prefetch_stalls"],
        "degraded": ts["degraded"],
        "tier_full_refusals": ts["tier_full_refusals"],
        "peak_resident_pages": ts["peak_resident_pages"],
        "resident_tokens": resident_tokens,
        "device_only_tokens": device_only_tokens,
        "resident_tokens_vs_device_only":
            resident_tokens / device_only_tokens,
        "tiered_streams_equal": stream == stream_untiered,
        "streams_equal_pcie_slow": stream_slow == stream,
        "streams_equal_pcie_drop": stream_drop == stream,
        "pcie_drop_retries": ts_drop["retries"],
        "pcie_slow_suspensions": ts_slow["suspensions"],
        "backend": jax.default_backend(),
    }


def bench_paged(arch: str, storage: str, dense_row: dict,
                dense_stream: list, *, slots: int = 2, max_len: int = 64,
                chunk: int = 8, requests: int = 6, max_new: int = 17,
                use_mtp: bool = False) -> dict:
    """Paged-engine row: same request stream through the block-pool cache."""
    import jax
    from repro.serve.engine import ServeEngine

    cfg = _smoke_cfg(arch)
    eng = ServeEngine(cfg, slots=slots, max_len=max_len, chunk=chunk,
                      use_mtp=use_mtp, paged=True, page_size=PAGE_SIZE,
                      page_storage=storage)
    # warmup: compile both prefill buckets + quant/scatter/decode/release
    # so the measured stream is warm-path like the dense row
    for rid in (0, requests - 1):
        eng.add_request(_mkreq(rid, cfg, max_new))
        eng.run_until_done()
    eng.stats["peak_pages_used"] = 0

    # steady-state decode, same accounting as the dense row: prefill all
    # requests up front, admit as pages free, time the chunk loop only
    reqs = [_mkreq(rid, cfg, max_new) for rid in range(requests)]
    handoffs = [(r, *eng.prefill_request(r)) for r in reqs]
    rest = list(handoffs)
    s0 = dict(eng.stats)
    tic = time.perf_counter()
    while any(x is not None for x in eng.active) or rest:
        while rest and eng.can_admit(rest[0][0]):
            r, first, payload = rest.pop(0)
            eng.admit_prefilled(r, first, payload, eng.free_slots()[0])
        eng.step()
    wall = time.perf_counter() - tic
    assert all(r.done for r in reqs)
    stream = [r.out for r in reqs]
    decode_tokens = (eng.stats["tokens"] - s0["tokens"]
                     - (eng.stats["first_tokens"] - s0["first_tokens"]))

    bpt = eng.cache_bytes_per_token()
    dense_bpt = dense_row["cache_bytes_per_token"]
    # capacity lever: how many of this stream's requests fit in the cache
    # memory the dense engine spends on `slots` max_len rings — pages are
    # reserved per request budget (prompt + max_new), not worst case
    page_bytes = bpt * PAGE_SIZE
    mean_req_bytes = float(np.mean([eng.pages_needed(r) for r in reqs])
                           ) * page_bytes
    dense_budget = dense_bpt * slots * max_len
    max_resident = int(dense_budget // mean_req_bytes)

    return {
        "arch": arch,
        "family": cfg.family,
        "attention": cfg.attention,
        "cache_layout": f"paged-{storage}",
        "slots": slots,
        "chunk": chunk,
        "requests": requests,
        "max_new": max_new,
        "page_size": PAGE_SIZE,
        "pool_pages": eng.pool_pages,
        "decode_tokens": int(decode_tokens),
        "tokens_per_s": decode_tokens / wall if wall else 0.0,
        "cache_bytes_per_token": bpt,
        "cache_bytes_ratio_vs_dense": bpt / dense_bpt,
        "pool_peak_pages_used": eng.stats["peak_pages_used"],
        "pool_peak_occupancy": eng.stats["peak_pages_used"]
        / max(eng.pool_pages, 1),
        "page_admits": eng.stats["page_admits"] - s0["page_admits"],
        "page_releases": eng.stats["page_releases"] - s0["page_releases"],
        "tokens_equal_dense": stream == dense_stream,
        "mean_request_pages": float(
            np.mean([eng.pages_needed(r) for r in reqs])),
        "max_resident_slots_at_dense_budget": max_resident,
        "resident_slots_ratio_vs_dense": max_resident / slots,
        "backend": jax.default_backend(),
    }


MESH_SHAPE = (2, 4)
# per-EP-shard token counts must clear the 8-row capacity floor
# (core/moe.capacity) before the dedup wire reduction can show: with
# 64 slots each model column sees 8 tokens/step, flat capacity 16 rows
# vs dedup 8 — below that both protocols bottom out at the floor and
# dedup's metadata sideband would dominate.
SHARDED_SLOTS = 64


def bench_sharded(*, slots: int = SHARDED_SLOTS, max_len: int = 32,
                  chunk: int = 8, requests: int = 8,
                  max_new: int = 17) -> list:
    """Sharded serving rows: one per EP impl on the (2, 4) host mesh.

    Must run in a process with >= 8 devices (``run()`` spawns one); uses
    the train bench's MoE config so ``top_k > group_limit`` makes the
    dedup reduction measurable.
    """
    import jax

    try:
        from benchmarks.train_bench import bench_config
    except ImportError:          # run as a script: benchmarks/ is sys.path[2]
        from train_bench import bench_config

    from repro.launch.mesh import make_mesh
    from repro.parallel import context as pctx_mod
    from repro.serve.engine import ServeEngine

    need = MESH_SHAPE[0] * MESH_SHAPE[1]
    if len(jax.devices()) < need:
        raise RuntimeError(
            f"bench_sharded needs {need} devices, found {len(jax.devices())}"
            " — run via serve_bench.run() (8-device subprocess) or set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    cfg = bench_config()

    def stream(ctx):
        eng = ServeEngine(cfg, slots=slots, max_len=max_len, chunk=chunk,
                          seed=0, ctx=ctx)
        reqs = [_mkreq(rid, cfg, max_new) for rid in range(requests)]
        # warm the compile caches so the timed run is steady-state
        warm = [_mkreq(rid, cfg, max_new) for rid in range(requests)]
        for r in warm:
            eng.submit(r)
        eng.run_until_done()
        for r in reqs:
            eng.submit(r)
        s0 = dict(eng.stats)
        tic = time.perf_counter()
        eng.run_until_done()
        wall = time.perf_counter() - tic
        assert all(r.done for r in reqs)
        toks = (eng.stats["tokens"] - s0["tokens"]
                - (eng.stats["first_tokens"] - s0["first_tokens"]))
        return eng, [r.out for r in reqs], toks, wall

    _, ref_stream, _, _ = stream(None)
    rows = []
    mesh = make_mesh(MESH_SHAPE, ("data", "model"))
    for impl in ("ep_flat", "ep_dedup"):
        ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                                   moe_impl=impl, wire="fp8")
        eng, s, toks, wall = stream(ctx)
        # dual-microbatch decode (§2.3.1 applied to the decode pod):
        # MLIR op/byte accounting off the lowering — the dual engine's
        # single scan body must carry BOTH halves' all-to-alls (2x ops,
        # each over half the tokens) so the latency-hiding scheduler can
        # fly one half's dispatch under the other half's compute. Bytes
        # land between 1x (no padding) and 2x (both halves pinned at the
        # capacity floor) the single-batch bytes.
        from repro.parallel import overlap
        oeng = ServeEngine(cfg, params=eng.params, slots=slots,
                           max_len=max_len, chunk=chunk, seed=0, ctx=ctx,
                           decode_overlap=True)
        txt = eng.decode_lowered_text()
        otxt = oeng.decode_lowered_text()
        a2a_ops = max(overlap.while_body_op_counts(txt) or [0])
        o_ops = max(overlap.while_body_op_counts(otxt) or [0])
        rows.append({
            "arch": cfg.name,
            "family": cfg.family,
            "attention": cfg.attention,
            "cache_layout": "dense-sharded",
            "mesh_shape": list(MESH_SHAPE),
            "moe_impl": impl,
            "wire": "fp8",
            "slots": slots,
            "chunk": chunk,
            "requests": requests,
            "max_new": max_new,
            "decode_tokens": int(toks),
            "tokens_per_s": toks / wall if wall else 0.0,
            "decode_alltoall_bytes": overlap.collective_bytes(txt),
            "decode_alltoall_ops_per_scan": int(a2a_ops),
            "overlap_decode_alltoall_ops_per_scan": int(o_ops),
            "overlap_decode_alltoall_bytes": overlap.collective_bytes(otxt),
            "decode_traces": eng.trace_counts["decode"],
            "tokens_equal_single_device": s == ref_stream,
            "backend": jax.default_backend(),
        })
    return rows


def sharded_rows_subprocess() -> list:
    """Collect the sharded rows in a forced-8-device subprocess (the
    parent's jax device count is locked at first init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the child measures on a virtual CPU mesh; it must never reach for
    # an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--sharded-only"],
        capture_output=True, text=True, env=env, timeout=1200,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if r.returncode != 0:
        raise RuntimeError(f"sharded serve bench failed:\n{r.stderr[-3000:]}")
    # rows ride stdout between sentinel lines (XLA noise goes to stderr)
    payload = r.stdout.split("SHARDED_JSON:", 1)[1]
    return json.loads(payload)["rows"]


def bench_all(arch: str, **kw) -> list:
    dense_row, dense_stream = bench_arch(arch, **kw)
    rows = [dense_row]
    for storage in ("bf16", "fp8"):
        rows.append(bench_paged(arch, storage, dense_row, dense_stream,
                                **kw))
    return rows


def check(rows: list) -> None:
    """ISSUE 4 + ISSUE 5 + ISSUE 8 acceptance gates, asserted from the
    written rows (CI runs the same asserts against the JSON artifact)."""
    by = {(r["arch"], r["cache_layout"]): r for r in rows
          if r["cache_layout"] != "dense-sharded"}
    for arch in {r["arch"] for r in rows if r["cache_layout"] == "dense"}:
        dense = by[(arch, "dense")]
        bf16 = by[(arch, "paged-bf16")]
        fp8 = by[(arch, "paged-fp8")]
        assert bf16["tokens_equal_dense"], \
            f"{arch}: paged-bf16 stream != dense"
        if fp8["attention"] == "gqa":
            # byte-pool fp8 storage (u8 views + LUT decode, no XLA f8
            # emulation in the scan) keeps fp8 decode within 15% of
            # native-storage throughput (PR 10 tentpole gate)
            assert fp8["tokens_per_s"] >= 0.85 * bf16["tokens_per_s"], \
                (arch, fp8["tokens_per_s"], bf16["tokens_per_s"])
        assert fp8["cache_bytes_ratio_vs_dense"] <= 0.55, \
            (arch, fp8["cache_bytes_ratio_vs_dense"])
        assert fp8["resident_slots_ratio_vs_dense"] >= 2.0, \
            (arch, fp8["resident_slots_ratio_vs_dense"])
        if "mtp_acceptance" in dense:
            assert dense["mtp_acceptance"] > 0, \
                f"{arch}: MTP acceptance must be > 0 (dead draft path)"
    for r in rows:
        if r.get("workload") == "shared-prefix":
            assert r["tokens_equal_unshared"], \
                "shared-prefix streams != whole-prompt prefill"
            assert r["pages_saved_vs_unshared"] >= 2.0, \
                r["pages_saved_vs_unshared"]
        if r.get("workload") == "kv-tier":
            assert r["tiered_streams_equal"], \
                "kv-tier streams != untiered engine"
            assert r["resident_tokens_vs_device_only"] >= 3.0, \
                r["resident_tokens_vs_device_only"]
            assert r["prefetch_stalls"] == 0, r["prefetch_stalls"]
            assert r["streams_equal_pcie_slow"], \
                "kv-tier streams changed under pcie_slow"
            assert r["streams_equal_pcie_drop"], \
                "kv-tier streams changed under pcie_drop"
    sharded = {r["moe_impl"]: r for r in rows
               if r["cache_layout"] == "dense-sharded"}
    if sharded:
        for impl, r in sharded.items():
            assert r["tokens_equal_single_device"], \
                f"sharded {impl}: stream != single-device engine"
            # decode-overlap structure: ONE scan body carries both
            # halves' all-to-alls (2x ops over half-sized operands);
            # bytes stay within [1x, 2x] (2x only when both halves pad
            # to the dispatch capacity floor)
            assert (r["overlap_decode_alltoall_ops_per_scan"]
                    == 2 * r["decode_alltoall_ops_per_scan"] > 0), \
                (impl, r["decode_alltoall_ops_per_scan"],
                 r["overlap_decode_alltoall_ops_per_scan"])
            assert (r["decode_alltoall_bytes"]
                    <= r["overlap_decode_alltoall_bytes"]
                    <= 2 * r["decode_alltoall_bytes"]), \
                (impl, r["decode_alltoall_bytes"],
                 r["overlap_decode_alltoall_bytes"])
        assert 0 < sharded["ep_dedup"]["decode_alltoall_bytes"] \
            < sharded["ep_flat"]["decode_alltoall_bytes"], \
            {k: v["decode_alltoall_bytes"] for k, v in sharded.items()}


def run(out: str | None = None, chunk: int = 8,
        sharded: bool = True) -> list:
    rows = []
    for arch, kw in CONFIGS:
        rows.extend(bench_all(arch, chunk=chunk, **kw))
    rows.append(bench_prefix_sharing(chunk=chunk))
    rows.append(bench_kv_tier())
    if sharded:
        rows.extend(sharded_rows_subprocess())
    check(rows)
    if out:
        with open(out, "w") as f:
            json.dump({"suite": "serve_bench", "rows": rows}, f, indent=2)
    return rows


def suite():
    """benchmarks/run.py hook: (name, us_per_call, derived) rows."""
    for r in run(out="BENCH_serve.json"):
        us = 1e6 / r["tokens_per_s"] if r["tokens_per_s"] else 0.0
        if r["cache_layout"] == "dense-sharded":
            yield (f"serve_sharded_{r['moe_impl']}", us,
                   f"tok/s={r['tokens_per_s']:.1f} "
                   f"a2a_B/step={r['decode_alltoall_bytes']} "
                   f"mesh={tuple(r['mesh_shape'])}")
        elif r.get("workload") == "shared-prefix":
            yield (f"serve_shared_prefix_{r['arch']}", us,
                   f"hit_rate={r['prefix_hit_rate']:.2f} "
                   f"pages_saved=x{r['pages_saved_vs_unshared']:.1f} "
                   f"ttft_p50_ms={r['ttft_ms_p50_chunked']:.1f}")
        elif r.get("workload") == "kv-tier":
            yield (f"serve_kv_tier_{r['arch']}", us,
                   f"resident=x{r['resident_tokens_vs_device_only']:.2f} "
                   f"stalls={r['prefetch_stalls']} "
                   f"spill_B={r['spill_bytes']}")
        elif r["cache_layout"] == "dense":
            yield (f"serve_decode_{r['arch']}", us,
                   f"tok/s={r['tokens_per_s']:.1f} "
                   f"ttft_ms={r['ttft_ms_mean']:.1f} "
                   f"disp/tok={r['decode_dispatches_per_token']:.3f}")
        else:
            yield (f"serve_{r['cache_layout']}_{r['arch']}", us,
                   f"tok/s={r['tokens_per_s']:.1f} "
                   f"B/tok={r['cache_bytes_per_token']:.0f} "
                   f"x{r['resident_slots_ratio_vs_dense']:.1f}slots")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--sharded-only", action="store_true",
                    help="emit only the sharded rows as JSON on stdout "
                         "(used by run()'s 8-device subprocess)")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the sharded-row subprocess")
    args = ap.parse_args()
    if args.sharded_only:
        rows = bench_sharded()
        print("SHARDED_JSON:" + json.dumps({"rows": rows}))
        return
    rows = run(out=args.out, chunk=args.chunk, sharded=not args.no_sharded)
    for r in rows:
        if r["cache_layout"] == "dense-sharded":
            print(f"[serve_bench] sharded {r['moe_impl']} "
                  f"mesh={tuple(r['mesh_shape'])}: "
                  f"{r['tokens_per_s']:.1f} tok/s, decode a2a "
                  f"{r['decode_alltoall_bytes']} B/step, streams==single: "
                  f"{r['tokens_equal_single_device']}")
        elif r.get("workload") == "kv-tier":
            print(f"[serve_bench] {r['arch']} kv-tier: "
                  f"x{r['resident_tokens_vs_device_only']:.2f} resident "
                  f"tokens vs device-only "
                  f"({r['resident_tokens']}/{r['device_only_tokens']}), "
                  f"{r['suspensions']} spills / {r['resumes']} resumes, "
                  f"{r['prefetch_stalls']} stalls, streams==untiered: "
                  f"{r['tiered_streams_equal']}, chaos equal: "
                  f"slow={r['streams_equal_pcie_slow']} "
                  f"drop={r['streams_equal_pcie_drop']} "
                  f"({r['pcie_drop_retries']} retries)")
        elif r.get("workload") == "shared-prefix":
            print(f"[serve_bench] {r['arch']} shared-prefix: "
                  f"hit rate {r['prefix_hit_rate']:.2f}, "
                  f"pages saved x{r['pages_saved_vs_unshared']:.2f} "
                  f"({r['pages_shared_sum']}/{r['pages_unshared_sum']}), "
                  f"TTFT p50 {r['ttft_ms_p50_chunked']:.1f} ms chunked vs "
                  f"{r['ttft_ms_p50_whole_prompt']:.1f} ms whole-prompt, "
                  f"streams==unshared: {r['tokens_equal_unshared']}")
        elif r["cache_layout"] == "dense":
            print(f"[serve_bench] {r['arch']} dense: "
                  f"{r['tokens_per_s']:.1f} tok/s, "
                  f"TTFT {r['ttft_ms_mean']:.1f} ms, "
                  f"{r['decode_dispatches_per_token']:.3f} disp/tok, "
                  f"{r['cache_bytes_per_token']:.0f} B/tok"
                  + (f", MTP acceptance {r['mtp_acceptance']:.2f} "
                     f"({r['mtp_accepted']}/{r['mtp_drafts']})"
                     if "mtp_acceptance" in r else ""))
        else:
            print(f"[serve_bench] {r['arch']} {r['cache_layout']}: "
                  f"{r['tokens_per_s']:.1f} tok/s, "
                  f"{r['cache_bytes_per_token']:.0f} B/tok "
                  f"({r['cache_bytes_ratio_vs_dense']:.2f}x dense), "
                  f"{r['max_resident_slots_at_dense_budget']} resident "
                  f"slots at dense budget "
                  f"({r['resident_slots_ratio_vs_dense']:.1f}x), "
                  f"streams==dense: {r['tokens_equal_dense']}")
    print(f"[serve_bench] wrote {args.out}")


if __name__ == "__main__":
    main()
