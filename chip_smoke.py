#!/usr/bin/env python3
"""Smoke run of the serving path on TPU: one chip by default, four with
``--chips 4``.

    python chip_smoke.py            # phases gqa, mla, kernels (one chip)
    python chip_smoke.py --chips 4  # phase ep: expert parallel (four chips)

Every phase goes through the entry points a user calls (``ServeEngine``,
the kernel registry) at published widths, with random weights drawn from
``--seed``, prints one line per step, and checks what comes out. A failed
check raises, so the run exits non-zero; no phase's exception is caught.
The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only when every phase passed. Times are printed for information:
this is a smoke run, not a benchmark.

Phases, in order:

* ``kernels``: every registered kernel op once at real widths under the
  ``pallas`` and ``ref`` backends (``repro.kernels.chip_cases``).
* ``gqa``: qwen1.5-4b, whole (40 layers, d_model 2560, 20 MHA heads,
  vocab 151936), answers 4 greedy requests through the default engine
  (dense cache, XLA attention) and through the paged fp8 engine with the
  Pallas kernels (``flash_prefill``, ``paged_gqa_decode``).
* ``mla``: deepseek-v3-671b cut to its 3 dense-first layers (every width
  published; MTP on): XLA attention against the dense ``mla_decode``
  kernel and the paged fp8 ``paged_mla_decode`` kernel.
* ``ep`` (``--chips 4`` only): qwen3-moe-30b-a3b at full depth with fp8
  expert storage (paper §3.1), served over a (1, 4) mesh with
  ``ep_dedup`` and compared with ``ep_flat`` on the same mesh and wire.

Serving paths are compared on logits, not sampled tokens (random weights
make near-ties common): first-token logits from each path's prefill, and
decode-step logits with every path fed the reference's tokens through
its own cache.

The script needs the repository's ``src`` beside it and a TPU: on any
other platform it exits non-zero before building anything. It holds the
chips in this one process and starts no other.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MAX_LEN = 64          # prompt (<= 16) + answer (16), a multiple of pages
MAX_NEW = 16
CHUNK = 8
N_REQUESTS = 4

# First-token logits (prefill) of two serving paths may differ by this
# share of the reference's largest |logit|. The paths differ in attention
# precision only: the XLA path rounds probabilities and outputs to bf16
# (relative error 2^-9 per rounding) while the kernels keep fp32.
# Independent roundings over 40 residual layers grow like sqrt(40) * 2^-9
# ~ 1.2%; 5% is four times that, and far below the O(1) difference a
# wrong kernel (wrong page, wrong head, missing scale) makes.
LOGIT_TOL = 0.05
# A greedy first token may differ only at a near-tie: where the
# reference's top-2 logit gap is within twice the measured difference.
TIE_FACTOR = 2.0
# Teacher-forced decode: every path is fed the reference's tokens, so its
# decode-step logits can be compared position by position (random
# weights make near-ties common, and a greedy stream parts from another
# at its first flip; the streams' token agreement is printed, not held).
# The bound is on ||logits - ref|| / ||ref|| over the vocabulary.
DECODE_STEPS = 8
# Decode logits of a random-weight model are ill-conditioned: the bf16
# roundings alone put either attention path ~9% of the logit norm away
# from the float32 answer (DeepSeek-V3 MLA, one layer at published widths,
# XLA and Pallas alike, measured on XLA:CPU with the same roundings), and
# the two bf16 paths 5.3% from each other there and 7.6% at depth 3 on a
# TPU v5e. A kernel fault is far larger: in that one-layer model, dropping
# the newest cache row moved the logits by 48%, dropping the oldest by
# 50%, ignoring the rope part by 71% and reading another slot's rows by
# 146%. The bounds sit between the two: same cache precision as the
# reference ...
DECODE_TOL_SAME_STORAGE = 0.2
# ... and fp8 pages, whose E4M3 values (3 mantissa bits) add their own
# rounding: 9.2% against the bf16 dense path in the same one-layer model,
# 4.1% for qwen1.5-4b on the chip.
DECODE_TOL_FP8 = 0.3
# Each of the four chips holds a quarter of the parameters (experts split
# by EP, attention heads and vocabulary by TP); cache, decode state and
# the allocator's slack come on top, so a device may hold up to 1.5x its
# share.
EP_MEMORY_FACTOR = 1.5


def log(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def require_tpu():
    """Exit non-zero, naming the platform found, unless JAX has a TPU and
    the kernel registry dispatches to the compiled Pallas kernels."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{platform!r}; nothing was built or run")
    from repro import kernels
    backend = kernels.active_backend()
    if backend != "pallas":
        sys.exit(f"chip_smoke: the kernel registry dispatches to "
                 f"{backend!r} on platform {platform!r}; the smoke run "
                 "needs 'pallas' (unset REPRO_KERNEL_BACKEND)")


def prompts_for(cfg, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
            for n in rng.integers(9, 17, size=N_REQUESTS)]


def serve_once(eng, prompts):
    from repro.serve.engine import Request
    reqs = [Request(i, p, max_new=MAX_NEW) for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    seconds = time.perf_counter() - t0
    check(all(r.done and len(r.out) == MAX_NEW for r in reqs),
          f"not every request was answered: {[len(r.out) for r in reqs]}")
    return [list(map(int, r.out)) for r in reqs], seconds


def first_token_logits(eng, prompts):
    """Last-prompt-position logits through the engine model's own prefill
    (the attention path the engine was built with), batched."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    toks = np.zeros((len(prompts), 16), np.int32)   # the prefill bucket
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = np.asarray([len(p) for p in prompts], np.int32)
    fn = jax.jit(lambda prm, t, n: eng.model.prefill(
        prm, {"tokens": t}, lengths=n, pctx=eng.ctx)[0][:, -1].astype(
            jnp.float32))
    out = np.asarray(fn(eng.params, jnp.asarray(toks), jnp.asarray(lens)))
    check(np.isfinite(out).all(), "non-finite first-token logits")
    return out


def decode_logits(eng, prompts, forced):
    """Teacher-forced decode through the engine's own cache: admit the
    prompts (the engine's prefill and admission, dense or paged), then
    feed ``forced`` (B, DECODE_STEPS) tokens one step at a time through
    the model's decode step with the engine's attention path. Returns the
    logits, (steps, B, V) fp32."""
    import contextlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.parallel import context as pctx_mod
    from repro.serve.engine import Request
    for i, p in enumerate(prompts):
        eng.add_request(Request(N_REQUESTS + i, p, max_new=MAX_NEW))

    def step(params, cache, tok, pos):
        scope = (pctx_mod.use(eng.ctx) if eng.ctx is not None
                 else contextlib.nullcontext())
        with scope:
            logits, cache = eng.model.decode_step(params, cache, tok, pos)
        return logits[:, -1].astype(jnp.float32), cache

    fn = jax.jit(step)
    cache, out = eng.cache, []
    pos0 = np.asarray([len(p) for p in prompts], np.int32)
    for t in range(DECODE_STEPS):
        logits, cache = fn(eng.params, cache, jnp.asarray(forced[:, t:t + 1]),
                           jnp.asarray((pos0 + t)[:, None]))
        out.append(np.asarray(logits))
    out = np.stack(out)
    check(np.isfinite(out).all(), "non-finite decode logits")
    return out


def run_variant(phase, label, cfg, params, prompts, seed=0, forced=None,
                **engine_kw):
    """Serve the prompts twice through one engine, then decode the
    ``forced`` tokens (its own stream when None) teacher-forced. Returns
    a dict of the streams, the first-token and decode logits, and the
    engine's parameters (drawn by the engine from ``seed`` when
    ``params`` is None)."""
    import jax
    from repro.serve.engine import ServeEngine
    t0 = time.perf_counter()
    eng = ServeEngine(cfg, params=params, slots=N_REQUESTS, max_len=MAX_LEN,
                      chunk=CHUNK, seed=seed, **engine_kw)
    jax.block_until_ready(eng.params)
    build_s = time.perf_counter() - t0
    streams, first_s = serve_once(eng, prompts)
    again, run_s = serve_once(eng, prompts)
    check(again == streams, f"{label}: a second pass gave other tokens")
    logits = first_token_logits(eng, prompts)
    if forced is None:
        import numpy as np
        forced = np.asarray([st[:DECODE_STEPS] for st in streams], np.int32)
    dec = decode_logits(eng, prompts, forced)
    vocab = cfg.vocab_size
    check(all(0 <= t < vocab for s in streams for t in s),
          f"{label}: token outside the vocabulary")
    extra = {}
    if eng.use_mtp:
        extra["mtp_acceptance"] = f"{eng.acceptance_rate():.3f}"
    if eng.meshed:
        model_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
        in_use = [d.memory_stats()["bytes_in_use"]
                  for d in eng.ctx.mesh.devices.flat]
        extra.update(decode_alltoall_bytes=eng.decode_alltoall_bytes(),
                     model_gib=f"{model_bytes / 2**30:.2f}",
                     bytes_in_use_gib=",".join(f"{b / 2**30:.2f}"
                                               for b in in_use))
        share = model_bytes / len(in_use)
        check(all(0 < b <= EP_MEMORY_FACTOR * share for b in in_use),
              f"{label}: bytes in use {in_use} not near each device's "
              f"share {share:.0f} of the model")
    log(phase, variant=label, build_s=f"{build_s:.2f}",
        compile_s=f"{max(first_s - run_s, 0.0):.2f}",
        run_s=f"{run_s:.2f}", requests=len(streams),
        tokens=sum(map(len, streams)), **extra)
    out = dict(streams=streams, first=logits, decode=dec, forced=forced,
               params=eng.params)
    del eng
    gc.collect()
    return out


def compare_variants(phase, ref_label, ref, label, got, decode_tol):
    import numpy as np
    rl, gl = ref["first"], got["first"]
    scale = float(np.abs(rl).max())
    diff = float(np.abs(gl - rl).max())
    top2 = np.sort(rl, axis=-1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    flips = np.argmax(rl, -1) != np.argmax(gl, -1)
    rd, gd = ref["decode"], got["decode"]
    rel = float((np.linalg.norm(gd - rd, axis=-1)
                 / np.linalg.norm(rd, axis=-1)).max())
    rs, gs = ref["streams"], got["streams"]
    agree = (sum(a == b for x, y in zip(rs, gs) for a, b in zip(x, y))
             / sum(map(len, rs)))
    log(phase, compare=f"{label}_vs_{ref_label}",
        first_logit_max_abs_diff=f"{diff:.5f}",
        first_logit_tol=f"{LOGIT_TOL * scale:.5f}",
        first_token_flips=int(flips.sum()),
        decode_logit_rel_err=f"{rel:.5f}", decode_tol=decode_tol,
        decode_max_abs_diff=f"{float(np.abs(gd - rd).max()):.5f}",
        token_agreement=f"{agree:.3f}")
    check(diff <= LOGIT_TOL * scale,
          f"{phase}: {label} first-token logits off by {diff} "
          f"(> {LOGIT_TOL} x {scale})")
    check(not (flips & (gap > TIE_FACTOR * diff)).any(),
          f"{phase}: {label} changed a first token away from a near-tie")
    check(rel <= decode_tol, f"{phase}: {label} teacher-forced decode "
          f"logits off by {rel:.4f} of their norm (> {decode_tol})")


def serve_phase(phase, cfg, seed, variants, note, **shared_kw):
    import jax
    from repro.models.api import build_model
    log(phase, config=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
        heads=cfg.num_heads, kv_heads=cfg.num_kv_heads, vocab=cfg.vocab_size,
        params_b=f"{cfg.n_params() / 1e9:.3f}", note=note,
        live_bytes_at_start=release())
    t0 = time.perf_counter()
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(phase, params_init_s=f"{time.perf_counter() - t0:.2f}")
    prompts = prompts_for(cfg, seed)
    results, forced = {}, None
    for label, kw, _ in variants:
        results[label] = run_variant(phase, label, cfg, params, prompts,
                                     forced=forced, **shared_kw, **kw)
        forced = results[label]["forced"]
    ref_label = variants[0][0]
    for label, _, tol in variants[1:]:
        compare_variants(phase, ref_label, results[ref_label], label,
                         results[label], tol)
    results.clear()
    del params
    release()


def release():
    """Drop what a phase left on the device before the next one builds
    (two models at once would not fit one chip), and say what is left."""
    import jax
    gc.collect()
    jax.clear_caches()
    return sum(x.nbytes for x in jax.live_arrays())


def phase_gqa(seed):
    from repro.configs.base import get_config
    serve_phase("gqa", get_config("qwen1.5-4b"), seed, [
        ("xla_dense", {}, None),
        ("pallas_paged_fp8", dict(paged=True, page_storage="fp8",
                                  attn_impl="pallas"), DECODE_TOL_FP8),
    ], note="whole_model")


def phase_mla(seed):
    from repro.configs.base import get_config
    cfg = get_config("deepseek-v3-671b", num_layers=3)
    serve_phase("mla", cfg, seed, [
        ("xla_dense", {}, None),
        ("pallas_dense", dict(attn_impl="pallas"), DECODE_TOL_SAME_STORAGE),
        ("pallas_paged_fp8", dict(paged=True, page_storage="fp8",
                                  attn_impl="pallas"), DECODE_TOL_FP8),
    ], note="depth_cut_61_to_3_dense_first_layers_widths_published",
        use_mtp=True)


def phase_kernels(seed):
    import jax
    from repro import kernels
    from repro.kernels import chip_cases
    worst = 0.0
    for label, case in chip_cases.CASES.items():
        args, kw = case.build(jax.random.PRNGKey(seed))
        op = kernels.get(case.op)
        with kernels.use_backend("pallas", clear_caches=False):
            t0 = time.perf_counter()
            got = jax.block_until_ready(op(*args, **kw))
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(op(*args, **kw))
            run_s = time.perf_counter() - t0
        # the oracle runs its fp32 matmuls at full precision, or the TPU's
        # default single bf16 pass would dominate the comparison
        with kernels.use_backend("ref", clear_caches=False), \
                jax.default_matmul_precision("highest"):
            ref = jax.block_until_ready(op(*args, **kw))
        r = chip_cases.compare(case, got, ref)
        worst = max(worst, r["bound_ratio"])
        log("kernels", case=label, op=case.op,
            compile_s=f"{max(first_s - run_s, 0.0):.3f}",
            run_s=f"{run_s:.4f}", max_abs_err=f"{r['max_abs_err']:.3g}",
            rtol=case.rtol, atol=case.atol,
            bound_ratio=f"{r['bound_ratio']:.3g}")
        check(r["bound_ratio"] <= 1.0,
              f"kernel {label}: error {r['bound_ratio']:.3g}x its bound")
    log("kernels", cases=len(chip_cases.CASES), ops=len(kernels.names()),
        worst_bound_ratio=f"{worst:.3g}")
    check({c.op for c in chip_cases.CASES.values()} == set(kernels.names()),
          "a registered kernel op has no chip case")


def phase_ep(seed):
    """qwen3-moe-30b-a3b over a (1, 4) mesh: ep_dedup against ep_flat, on
    the same parameters, mesh and fp8 wire. The two dispatches send the
    same quantized rows and differ only in summation order, so they are
    held to the logit tolerances of paths with the same cache precision."""
    import jax
    from repro.configs.base import get_config
    from repro.launch.mesh import make_mesh
    from repro.parallel.context import ParallelCtx
    cfg = get_config("qwen3-moe-30b-a3b", expert_dtype="float8_e4m3fn")
    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, JAX has {len(jax.devices())}")
    mesh = make_mesh((1, 4), ("data", "model"))
    log("ep", config=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
        experts=cfg.moe.num_experts, expert_dtype=cfg.expert_dtype,
        params_b=f"{cfg.n_params() / 1e9:.3f}", mesh="(1,4)", wire="fp8",
        note="full_depth_widths_published")
    prompts = prompts_for(cfg, seed)
    params, forced, results = None, None, {}
    for impl in ("ep_flat", "ep_dedup"):
        ctx = ParallelCtx(mesh=mesh, dp_axes=("data",), moe_impl=impl,
                          wire="fp8")
        # the first engine draws the parameters sharded, in place; the
        # second serves the same ones
        results[impl] = run_variant("ep", impl, cfg, params, prompts,
                                    seed=seed, forced=forced, ctx=ctx)
        params, forced = results[impl]["params"], results[impl]["forced"]
    compare_variants("ep", "ep_flat", results["ep_flat"], "ep_dedup",
                     results["ep_dedup"], DECODE_TOL_SAME_STORAGE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the expert-parallel phase on a "
                         "(1, 4) mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    require_tpu()
    from repro.launch import compile_cache
    log("setup", compile_cache=compile_cache.enable())

    import jax
    devs = jax.devices()
    log("setup", platform=devs[0].platform,
        kind=devs[0].device_kind.replace(" ", "_"), count=len(devs),
        jax=jax.__version__)
    phases = ([("ep", phase_ep)] if args.chips == 4 else
              [("kernels", phase_kernels), ("gqa", phase_gqa),
               ("mla", phase_mla)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn(args.seed)
        log(name, phase_s=f"{time.perf_counter() - t0:.2f}", ok=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
