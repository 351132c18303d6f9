"""Dual micro-batch overlap (paper §2.3.1, T7) + HLO inspection utilities.

The paper decouples MLA/MoE compute from MoE dispatch/combine all-to-all:
while micro-batch A computes, micro-batch B's all-to-all is in flight, and
vice versa. On TPU we express the *dependency structure* and let XLA's
latency-hiding scheduler place the async collective (start/done) pairs:
the two micro-batches flow through the same scanned layer step as two
independent op chains, so B's dispatch all-to-all has no data dependency
on A's expert GEMMs — exactly the freedom the scheduler needs to overlap
them. (SM-free by construction: TPU collectives ride the ICI DMA engines,
the paper's §4.4 wish.)

``dual_loss_and_metrics`` is the training-step body: two anti-phase
microbatches through one scan, averaged CE (+MTP), microbatch-averaged
MoE metrics — the meshed train step's loss function
(``Model.loss_dual``). ``dual_microbatch_loss`` is the loss-only wrapper.

The HLO helpers (``lowered_text`` / ``while_body_op_counts`` /
``collective_bytes``) turn the docstring's "inspect the compiled HLO"
claim into reusable test/bench utilities: the overlap tests assert both
microbatches' all-to-alls appear in ONE scan body, and the train bench
measures ep_flat-vs-ep_dedup wire bytes straight off the lowering. The
serving side reuses them too: the sharded engine's fused decode chunk is
a scan whose per-step MoE all-to-alls carry the same
schedulable-overlap freedom (no data dependency on the neighboring
dense compute), and ``ServeEngine.decode_alltoall_bytes()`` /
serve_bench's sharded rows read the decode wire bytes with
``collective_bytes`` exactly as the train bench does.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models.api import Model, _apply_kind, apply_remat


def dual_backbone(model: Model, params, tokensA, tokensB, ctxA, ctxB):
    """Run two microbatches through the segment stacks in one scan so each
    layer's ops for A and B are schedulable concurrently.

    Returns ``(hA, hB, statsA, statsB)`` where stats are per-segment dicts
    of layer-stacked MoE diagnostics (same shapes as the single-batch
    backbone's), so the dual loss reports load/drop/aux identically.
    """
    cfg = model.cfg
    from repro.parallel import context as pctx
    from repro.parallel.context import shard_act

    xA = model._embed(params, tokensA)
    xB = model._embed(params, tokensB)

    statsA: Dict[str, dict] = {}
    statsB: Dict[str, dict] = {}
    for seg in model.segments:
        p = params[seg.name]

        def step(carry, ps):
            hA, hB = carry
            ps = jax.lax.optimization_barrier(ps)
            hA, _, stA = _apply_kind(seg, ps, hA, cfg, ctxA, None)
            hB, _, stB = _apply_kind(seg, ps, hB, cfg, ctxB, None)
            return (shard_act(hA), shard_act(hB)), (stA, stB)

        step = apply_remat(step, pctx.get().remat)
        (xA, xB), (stA, stB) = jax.lax.scan(step, (xA, xB), p)
        if stA:
            statsA[seg.name] = stA
            statsB[seg.name] = stB
    return xA, xB, statsA, statsB


def _mkctx(tokens):
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return dict(positions=pos, causal=True), pos


def dual_loss_and_metrics(model: Model, params, batchA: Dict, batchB: Dict
                          ) -> Tuple[jax.Array, Dict]:
    """Average loss + metrics over two anti-phase microbatches.

    The CE term equals ``Model.loss`` exactly (valid-token-weighted
    combination, robust to uneven pad counts between halves). The MTP
    term reuses the CE token fractions as weights — exact when the
    halves' MTP-valid proportions match their CE-valid proportions
    (always true for unpadded training batches; an approximation under
    uneven padding). MoE metrics are microbatch-averaged. The meshed
    dual-microbatch train step therefore tracks the single-device
    reference trajectory.
    """
    cfg = model.cfg
    ctxA, posA = _mkctx(batchA["tokens"])
    ctxB, posB = _mkctx(batchB["tokens"])
    hA, hB, stA, stB = dual_backbone(model, params, batchA["tokens"],
                                     batchB["tokens"], ctxA, ctxB)
    lossA, ntokA = model._ce(params, hA, batchA["labels"])
    lossB, ntokB = model._ce(params, hB, batchB["labels"])
    # valid-token-weighted combination: equals Model.loss's global mean
    # even when pad labels (-1) leave the halves with unequal token
    # counts (reduces to 0.5/0.5 for balanced halves)
    wA = ntokA / (ntokA + ntokB)
    wB = 1.0 - wA
    loss = wA * lossA + wB * lossB
    metrics = {"ce": loss, "ntokens": ntokA + ntokB}
    aux = 0.0
    for segname in stA:
        if "aux_loss" in stA[segname]:
            aux = aux + 0.5 * (jnp.mean(stA[segname]["aux_loss"])
                               + jnp.mean(stB[segname]["aux_loss"]))
            metrics[f"{segname}/drop_frac"] = 0.5 * (
                jnp.mean(stA[segname]["drop"])
                + jnp.mean(stB[segname]["drop"]))
            metrics[f"{segname}/load_layers"] = 0.5 * (
                stA[segname]["load"] + stB[segname]["load"])   # (n, E)
    metrics["aux_loss"] = aux
    if cfg.mtp:
        mtp_l = (
            wA * model._mtp_loss(params, hA, batchA["tokens"], posA, ctxA)
            + wB * model._mtp_loss(params, hB, batchB["tokens"], posB, ctxB))
        metrics["mtp_loss"] = mtp_l
        loss = loss + mtp_l
    return loss, metrics


def dual_microbatch_loss(model: Model, params, batchA: Dict, batchB: Dict):
    """Average CE over two anti-phase microbatches (loss-only wrapper)."""
    return dual_loss_and_metrics(model, params, batchA, batchB)[0]


def dual_decode_step(model: Model, params, cacheA, cacheB, tokA, tokB,
                     posA, posB):
    """One decode step for two half-batches through ONE scanned layer step.

    The serving-side mirror of :func:`dual_backbone`: each half carries its
    own dense decode cache, and the two halves' ops inside the shared scan
    body are independent chains — half B's MoE dispatch all-to-all has no
    data dependency on half A's expert GEMMs (or attention), so the
    latency-hiding scheduler can fly one half's decode all-to-alls under
    the other half's compute, the paper's §2.3.1 overlap applied to the
    decode pod. ``while_body_op_counts`` on the lowering shows both
    halves' all-to-alls in a single while body (2x the single-batch
    count over half-sized operands — same wire bytes, overlappable).

    tokA/tokB (b, 1) int32; posA/posB (b, 1) int32; caches are per-half
    slices of a dense decode cache (batch axes per
    ``Model.cache_batch_axes``). Returns ``(logitsA, logitsB, new_cacheA,
    new_cacheB)``. Dense caches only — paged pools are shared across
    slots and have no batch axis to split.
    """
    cfg = model.cfg
    from repro.parallel import context as pctx
    from repro.parallel.context import shard_act

    ctxA = dict(positions=posA, causal=True, **model.impl_ctx)
    ctxB = dict(positions=posB, causal=True, **model.impl_ctx)
    xA = model._embed(params, tokA)
    xB = model._embed(params, tokB)
    newA: Dict[str, dict] = {}
    newB: Dict[str, dict] = {}
    for seg in model.segments:
        p = params[seg.name]
        cA = cacheA.get(seg.name)
        cB = cacheB.get(seg.name)

        def step(carry, xs):
            hA, hB = carry
            ps, csA, csB = xs
            ps = jax.lax.optimization_barrier(ps)
            if csA is not None:
                csA = jax.lax.optimization_barrier(csA)
            if csB is not None:
                csB = jax.lax.optimization_barrier(csB)
            hA, ncA, _ = _apply_kind(seg, ps, hA, cfg, ctxA, csA)
            hB, ncB, _ = _apply_kind(seg, ps, hB, cfg, ctxB, csB)
            return (shard_act(hA), shard_act(hB)), (ncA, ncB)

        step = apply_remat(step, pctx.get().remat)
        (xA, xB), (ncA, ncB) = jax.lax.scan(step, (xA, xB), (p, cA, cB))
        if ncA is not None:
            newA[seg.name] = ncA
            newB[seg.name] = ncB
    outA = dict(cacheA)
    outA.update(newA)
    outB = dict(cacheB)
    outB.update(newB)
    if "mtp_h" in outA:     # mirror decode_step's carried hidden (the
        outA["mtp_h"] = xA  # MTP draft itself is excluded under overlap)
        outB["mtp_h"] = xB
    return (model._unembed(params, xA), model._unembed(params, xB),
            outA, outB)


# ---------------------------------------------------------------------------
# HLO inspection utilities (tests + train bench)
# ---------------------------------------------------------------------------


def lowered_text(fn: Callable, *args, **kwargs) -> str:
    """StableHLO text of ``jax.jit(fn)`` lowered at the given args."""
    return jax.jit(fn).lower(*args, **kwargs).as_text()


def _match_region(txt: str, start: int) -> Tuple[int, int]:
    """(open, close) indices of the first brace-matched region at/after
    ``start``; (-1, -1) when there is none.

    Paren-aware: braces inside an argument list (MLIR arg attributes like
    ``%arg0: tensor<...> {mhlo.sharding = "..."}``) are not region
    openers — the region brace is the first ``{`` at paren depth 0.
    """
    o = -1
    pdepth = 0
    for i in range(start, len(txt)):
        ch = txt[i]
        if ch == "(":
            pdepth += 1
        elif ch == ")":
            pdepth -= 1
        elif ch == "{" and pdepth == 0:
            o = i
            break
    if o < 0:
        return -1, -1
    depth = 0
    for i in range(o, len(txt)):
        if txt[i] == "{":
            depth += 1
        elif txt[i] == "}":
            depth -= 1
            if depth == 0:
                return o, i
    return -1, -1


def _parse_funcs(txt: str) -> Dict[str, str]:
    """Map of func.func name -> brace-matched body text."""
    funcs: Dict[str, str] = {}
    for m in re.finditer(r"func\.func\s+(?:private\s+|public\s+)?@(\w+)",
                         txt):
        o, c = _match_region(txt, m.end())
        if o >= 0:
            funcs[m.group(1)] = txt[o:c + 1]
    return funcs


def _count_transitive(body: str, funcs: Dict[str, str], op: str,
                      memo: Dict[str, int], stack: Tuple[str, ...] = ()
                      ) -> int:
    """``op`` occurrences in ``body`` plus, per call site, in callees."""
    n = body.count(op)
    for cm in re.finditer(r"call\s+@(\w+)", body):
        callee = cm.group(1)
        if callee in stack or callee not in funcs:
            continue
        if callee not in memo:
            memo[callee] = _count_transitive(
                funcs[callee], funcs, op, memo, stack + (callee,))
        n += memo[callee]
    return n


def while_body_op_counts(txt: str, op: str = "all_to_all") -> List[int]:
    """Occurrences of ``op`` executed per iteration of each
    ``stablehlo.while`` loop (following outlined ``func.call`` bodies).

    One entry per while op, in textual order. This is the overlap
    structure check: a dual-microbatch scan must carry BOTH microbatches'
    dispatch/combine all-to-alls in a single loop body (2x the
    single-microbatch count) — two sequential scans would show two bodies
    with the single count each. Nested loops count their inner ops too;
    the segment scans under test are single-level.
    """
    funcs = _parse_funcs(txt)
    memo: Dict[str, int] = {}
    counts: List[int] = []
    pos = 0
    while True:
        w = txt.find("stablehlo.while", pos)
        if w < 0:
            return counts
        # a while op carries two brace regions (cond + body); collectives
        # only ever live in the body, so counting across both is exact.
        o1, c1 = _match_region(txt, w)
        if o1 < 0:
            return counts
        o2, c2 = _match_region(txt, c1 + 1)
        end = c2 if c2 > 0 else c1
        counts.append(_count_transitive(txt[o1:end + 1], funcs, op, memo))
        pos = end + 1


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8E4M3FN": 1, "f8E5M2": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1,
}


def collective_bytes(txt: str, op: str = "all_to_all") -> int:
    """Total bytes moved by ``op`` ops in a lowering (per scan iteration
    for ops inside loop bodies). Sums the operand tensor sizes of every
    line mentioning ``op`` — the paper's wire-byte accounting (§4.3) read
    directly off the compiled program, used to verify ep_dedup's M·t < k·t
    reduction on the slow fabric.
    """
    total = 0
    for line in txt.splitlines():
        if op not in line or "tensor<" not in line:
            continue
        # the op's type signature trails the attributes:
        #   ... }> : (tensor<AxBxf32>) -> tensor<AxBxf32>
        # take the result type (mirrors the operand for shifts); attribute
        # tensors (replica_groups etc.) earlier on the line are skipped
        m = re.search(r"->\s*\(?tensor<((?:\d+x)*)([a-zA-Z][a-zA-Z0-9]*)>",
                      line)
        if not m:
            continue
        dims_s, dt = m.groups()
        if dt not in _DTYPE_BYTES:
            # fail loud: silently billing an unknown element type at some
            # default width would corrupt the wire-byte accounting
            raise ValueError(f"unknown MLIR element type {dt!r} in: "
                             f"{line.strip()[:120]}")
        n = 1
        for d in dims_s.split("x"):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total
