"""Expert-parallel MoE dispatch/combine via shard_map (paper §4.2–4.3).

Two wire protocols, equivalence-tested against the local reference:

* ``ep_flat``  — plain EP: every (token, expert) routed straight to the
  expert's model-axis column. Dispatch bytes/token ∝ #distinct columns
  (up to k) — the paper's "8t" baseline.

* ``ep_dedup`` — the paper's **node-limited two-hop** protocol (T3).
  Expert groups ("nodes") map to contiguous spans of ``cpg = cols/G``
  model-axis columns. Each token is sent ONCE per selected group (≤
  ``group_limit`` = the paper's M), chunk-split across the group's columns
  inside the single all-to-all (no padding waste); hop 2 is an intra-group
  ppermute exchange (the NVLink-fanout analogue — nearest-neighbor ICI
  hops). Combine runs in reverse with an intra-group partial-sum first.
  Slow-fabric bytes drop from ~k·t to M·t — the paper's IB dedup, directly
  measurable in compiled HLO collective bytes.

Wire precision (paper §3.1/§2.3.2): dispatch buffers travel as
float8_e4m3fn + fp32 1x128-tile scales (≈1 B/elt); combine returns bf16
(2 B/elt) — the paper's asymmetric "(1 Byte + 2 Bytes)" accounting.

Note an improvement over the paper's wire model: the shared expert is
computed data-parallel outside the dispatch (no "+1" fanout), so our
bytes/token are M and k, not M+1 / 9 (recorded in EXPERIMENTS.md).

Token layout: tokens enter sharded over dp axes and replicated over the
model axis; each model column takes its 1/cols slice, so the EP domain is
dp x model (the paper's "attention is data-parallel across the EP group").
Token counts that don't divide (decode shapes) are padded globally and
masked into the overflow bucket (they consume no capacity and no wire).

Both protocols are differentiable end to end — the meshed train step
(train/trainer.py) takes grads straight through dispatch and combine.
With the fp8 wire, the payload's quantize -> bitcast -> all_to_all ->
bitcast -> dequantize chain carries cast gradients, so token gradients
across the wire differ from the fp32-wire ones only by quantization
noise (trajectory-bounded in tests/test_train_distributed.py; x-grad
vs the local reference is exact for fp32/bf16 wire, ~3% relative for
fp8 at smoke scale).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import fp8, moe as moe_mod, routing
from repro.parallel.context import ParallelCtx


# ---------------------------------------------------------------------------
# wire codecs (paper: FP8 dispatch, BF16 combine)
# ---------------------------------------------------------------------------


def _wire_encode(x: jax.Array, wire: str = "fp8"):
    """FP8 wire: (uint8 payload, fp32 1x128-tile scales). Other modes keep a
    trivial scale sideband so the protocol shape is wire-independent."""
    if wire == "fp8":
        q, s = fp8.quantize_tilewise(x.astype(jnp.float32))
        return jax.lax.bitcast_convert_type(q, jnp.uint8), s
    dt = jnp.bfloat16 if wire == "bf16" else jnp.float32
    s = jnp.ones(x.shape[:-1] + (max(1, -(-x.shape[-1] // fp8.TILE)),),
                 jnp.float32)
    return x.astype(dt), s


def _wire_decode(q: jax.Array, s: jax.Array, dtype, wire: str = "fp8"):
    if wire == "fp8":
        q = jax.lax.bitcast_convert_type(q, fp8.E4M3)
        return fp8.dequant_tilewise(q, s).astype(dtype)
    return q.astype(dtype)


def _scatter_rows(n_slots: int, dest: jax.Array, keep: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """rows: (t, k, d) or (t*k, d) scattered into (n_slots, d)."""
    d = rows.shape[-1]
    rows2 = rows.reshape(-1, d)
    return jnp.zeros((n_slots, d), rows.dtype).at[dest].add(
        jnp.where(keep[:, None], rows2, 0))


def _slice_tokens(x, mask, axis: str):
    cols = jax.lax.axis_size(axis)
    j = jax.lax.axis_index(axis)
    per = x.shape[0] // cols
    xt = jax.lax.dynamic_slice_in_dim(x, j * per, per, axis=0)
    mt = jax.lax.dynamic_slice_in_dim(mask, j * per, per, axis=0)
    return xt, mt


def _unslice_tokens(y: jax.Array, axis: str) -> jax.Array:
    return jax.lax.all_gather(y, axis, axis=0, tiled=True)


# ---------------------------------------------------------------------------
# intra-group exchange primitives (the "NVLink domain" of the paper)
# ---------------------------------------------------------------------------


def _group_perm(cols: int, cpg: int, step: int):
    """Send to the column ``step`` ranks ahead within the same group."""
    return [(c, (c // cpg) * cpg + (c % cpg + step) % cpg)
            for c in range(cols)]


def _group_allgather(z: jax.Array, axis: str, cpg: int) -> jax.Array:
    """z: this column's hop-1 chunk (owner rank = col%cpg). Returns
    (cpg, *z.shape) with index r = the chunk owned by group-rank r."""
    cols = jax.lax.axis_size(axis)
    rj = jax.lax.axis_index(axis) % cpg
    received = [z]                                   # rank rj
    for step in range(1, cpg):
        got = jax.lax.ppermute(z, axis, _group_perm(cols, cpg, step))
        received.append(got)                         # rank (rj - step) % cpg
    stacked = jnp.stack(received)
    return stacked[(rj - jnp.arange(cpg)) % cpg]


def _group_reduce(parts: jax.Array, axis: str, cpg: int) -> jax.Array:
    """parts: (cpg, ...) this column's partial outputs indexed by owner
    rank. Returns this column's own chunk summed over the group."""
    cols = jax.lax.axis_size(axis)
    rj = jax.lax.axis_index(axis) % cpg
    acc = jnp.take(parts, rj, axis=0)
    for step in range(1, cpg):
        chunk = jnp.take(parts, (rj + step) % cpg, axis=0)
        acc = acc + jax.lax.ppermute(chunk, axis, _group_perm(cols, cpg, step))
    return acc


# ---------------------------------------------------------------------------
# flat EP
# ---------------------------------------------------------------------------


def _ep_flat_local(wg, bias, w1, w3, w2, x, mask, cfg: ModelConfig,
                   axis: str, wire: str = "fp8"):
    mc = cfg.moe
    cols = jax.lax.axis_size(axis)
    E_l = mc.num_experts // cols
    xt, mt = _slice_tokens(x, mask, axis)
    t, d = xt.shape
    k = mc.top_k

    rr = routing.route(xt, wg, mc, bias=bias)
    col_of = jnp.where(mt[:, None], rr.expert_idx // E_l, cols)
    Cc = moe_mod.capacity(t, mc, experts=cols)
    plan = moe_mod.dispatch_plan(col_of, cols + 1, Cc)
    n_slots = (cols + 1) * Cc

    send = _scatter_rows(n_slots, plan.dest, plan.keep,
                         jnp.broadcast_to(xt[:, None], (t, k, d)))
    ids = jnp.full((n_slots,), -1, jnp.int32).at[plan.dest].set(
        jnp.where(plan.keep, (rr.expert_idx % E_l).reshape(-1), -1))
    wts = jnp.zeros((n_slots,), jnp.float32).at[plan.dest].set(
        jnp.where(plan.keep, rr.weights.reshape(-1), 0.0))
    send = send.reshape(cols + 1, Cc, d)[:cols]
    ids = ids.reshape(cols + 1, Cc)[:cols]
    wts = wts.reshape(cols + 1, Cc)[:cols]

    # dispatch all-to-all (FP8 wire)
    q, s = _wire_encode(send, wire)
    q = jax.lax.all_to_all(q, axis, 0, 0, tiled=True)
    s = jax.lax.all_to_all(s, axis, 0, 0, tiled=True)
    ids = jax.lax.all_to_all(ids, axis, 0, 0, tiled=True)
    wts = jax.lax.all_to_all(wts, axis, 0, 0, tiled=True)
    recv = _wire_decode(q.reshape(cols * Cc, d), s.reshape(cols * Cc, -1),
                        cfg.dtype, wire)
    ids = ids.reshape(-1)

    # local grouped GEMM over my experts (+1 overflow bucket)
    C2 = moe_mod.capacity(cols * Cc, mc, experts=E_l, k=1)
    plan2 = moe_mod.dispatch_plan(
        jnp.where(ids >= 0, ids, E_l)[:, None], E_l + 1, C2)
    buf = _scatter_rows((E_l + 1) * C2, plan2.dest, plan2.keep, recv)
    h = moe_mod.expert_ffn(buf.reshape(E_l + 1, C2, d)[:E_l], w1, w3, w2, cfg)
    h = jnp.concatenate([h, jnp.zeros((1, C2, d), h.dtype)], 0)
    y = h.reshape(-1, d)[plan2.dest] * plan2.keep[:, None]
    y = y * wts.reshape(-1, 1).astype(y.dtype)

    # combine all-to-all (BF16 wire)
    cdt = jnp.float32 if wire == "fp32" else jnp.bfloat16
    y = jax.lax.all_to_all(y.reshape(cols, Cc, d).astype(cdt),
                           axis, 0, 0, tiled=True)
    y = y.reshape(cols * Cc, d).astype(jnp.float32)
    y = jnp.concatenate([y, jnp.zeros((Cc, d), y.dtype)], 0)   # overflow rows
    back = y[plan.dest] * plan.keep[:, None]
    yt = back.reshape(t, k, d).sum(1).astype(xt.dtype)
    return _unslice_tokens(yt, axis), rr.load, plan.drop_frac, rr.aux_loss


# ---------------------------------------------------------------------------
# node-limited dedup EP (paper §4.3)
# ---------------------------------------------------------------------------


def _ep_dedup_local(wg, bias, w1, w3, w2, x, mask, cfg: ModelConfig,
                    axis: str, wire: str = "fp8"):
    mc = cfg.moe
    cols = jax.lax.axis_size(axis)
    G = mc.num_groups
    assert cols % G == 0, (cols, G)
    cpg = cols // G
    E_l = mc.num_experts // cols
    epg = mc.num_experts // G
    xt, mt = _slice_tokens(x, mask, axis)
    t, d = xt.shape
    k = mc.top_k

    rr = routing.route(xt, wg, mc, bias=bias)
    grp = rr.expert_idx // epg                          # (t, k)

    # distinct groups per token (<= group_limit), padded with G
    sg = jnp.sort(grp, axis=-1)
    first = jnp.concatenate(
        [jnp.ones((t, 1), bool), sg[:, 1:] != sg[:, :-1]], axis=1)
    marked = jnp.where(first, sg, G)
    L = min(mc.group_limit, k, G)      # max distinct groups a token can hit
    dg = jnp.sort(marked, axis=-1)[:, :L]               # (t, L)
    dg = jnp.where(mt[:, None], dg, G)

    Cg = moe_mod.capacity(t, mc, experts=G, k=L)
    Cg = -(-Cg // cpg) * cpg
    plan = moe_mod.dispatch_plan(dg, G + 1, Cg)
    n_slots = (G + 1) * Cg

    send = _scatter_rows(n_slots, plan.dest, plan.keep,
                         jnp.broadcast_to(xt[:, None], (t, L, d)))
    # per-slot metadata: the token's expert ids/weights within dest group
    tok_grp = jnp.repeat(grp, L, axis=0)                # (t*L, k)
    slot_grp = dg.reshape(-1)                           # (t*L,)
    in_grp = tok_grp == slot_grp[:, None]
    eids = jnp.where(in_grp, jnp.repeat(rr.expert_idx % epg, L, axis=0), -1)
    ews = jnp.where(in_grp, jnp.repeat(rr.weights, L, axis=0), 0.0)
    meta_e = jnp.full((n_slots, k), -1, jnp.int32).at[plan.dest].set(
        jnp.where(plan.keep[:, None], eids, -1))
    meta_w = jnp.zeros((n_slots, k), jnp.float32).at[plan.dest].set(
        jnp.where(plan.keep[:, None], ews, 0.0))
    send = send.reshape(G + 1, Cg, d)[:G]
    meta_e = meta_e.reshape(G + 1, Cg, k)[:G]
    meta_w = meta_w.reshape(G + 1, Cg, k)[:G]

    # hop 1: all-to-all, group buffers chunk-split over group columns
    Ck = Cg // cpg

    def chunks(z):
        return z.reshape((cols, Ck) + z.shape[2:])

    q, s = _wire_encode(send, wire)
    q = jax.lax.all_to_all(chunks(q), axis, 0, 0, tiled=True)  # (cols, Ck, d)
    s = jax.lax.all_to_all(chunks(s), axis, 0, 0, tiled=True)
    me = jax.lax.all_to_all(chunks(meta_e), axis, 0, 0, tiled=True)
    mw = jax.lax.all_to_all(chunks(meta_w), axis, 0, 0, tiled=True)

    # hop 2: intra-group exchange -> every column holds the full group buffer
    gq = _group_allgather(q, axis, cpg)                 # (cpg, cols, Ck, d)
    gs = _group_allgather(s, axis, cpg)
    gme = _group_allgather(me, axis, cpg)
    gmw = _group_allgather(mw, axis, cpg)

    n_recv = cpg * cols * Ck
    recv = _wire_decode(gq.reshape(n_recv, d), gs.reshape(n_recv, -1),
                        cfg.dtype, wire)
    ids_all = gme.reshape(n_recv, k)
    wts_all = gmw.reshape(n_recv, k)

    # my column's experts live at group-local ids [rj*E_l, (rj+1)*E_l)
    rj = jax.lax.axis_index(axis) % cpg
    rel = ids_all - rj * E_l
    rel = jnp.where((rel >= 0) & (rel < E_l), rel, E_l)
    C2 = moe_mod.capacity(n_recv, mc, experts=E_l, k=max(1, k // cpg))
    plan2 = moe_mod.dispatch_plan(rel, E_l + 1, C2)
    xk2 = jnp.broadcast_to(recv[:, None], (n_recv, k, d))
    buf = _scatter_rows((E_l + 1) * C2, plan2.dest, plan2.keep, xk2)
    h = moe_mod.expert_ffn(buf.reshape(E_l + 1, C2, d)[:E_l], w1, w3, w2, cfg)
    h = jnp.concatenate([h, jnp.zeros((1, C2, d), h.dtype)], 0)
    back = h.reshape(-1, d)[plan2.dest] * plan2.keep[:, None]
    back = back * wts_all.reshape(-1, 1).astype(back.dtype)
    partial = back.reshape(n_recv, k, d).sum(1)
    partial = partial.reshape(cpg, cols, Ck, d)

    # combine hop 2: intra-group partial sums back to the chunk owner
    total = _group_reduce(partial, axis, cpg)           # (cols, Ck, d)

    # combine hop 1: reverse all-to-all (BF16 wire)
    cdt = jnp.float32 if wire == "fp32" else jnp.bfloat16
    y = jax.lax.all_to_all(total.astype(cdt), axis, 0, 0, tiled=True)
    y = y.reshape(G, Cg, d).astype(jnp.float32)
    y = jnp.concatenate([y, jnp.zeros((1, Cg, d), y.dtype)], 0)
    backh = y.reshape(-1, d)[plan.dest] * plan.keep[:, None]
    yt = backh.reshape(t, L, d).sum(1).astype(xt.dtype)
    return _unslice_tokens(yt, axis), rr.load, plan.drop_frac, rr.aux_loss


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def moe_ffn_sharded(p: dict, x: jax.Array, cfg: ModelConfig,
                    pctx: ParallelCtx, valid=None):
    """MoE layer over the mesh. x: (B, S, d) global. Returns
    (y, RouteResult-like, drop_frac).

    ``valid`` ((B, S) bool, optional) marks real tokens: bucketed-prefill
    pads are folded into the same overflow bucket as divisibility padding,
    so they consume no expert capacity and no wire bytes (the serving
    engine's sharded prefill path). Note the capacity per EP shard is
    computed from the padded shard token count — when nothing drops
    (serving smoke configs run capacity_factor-headroom), results match
    the local path's exact-length dispatch token-for-token.
    """
    mc = cfg.moe
    mesh = pctx.mesh
    axis = pctx.ep_axis
    shape = x.shape
    cols_ = mesh.shape[axis]
    dedup_ok = (pctx.moe_impl == "ep_dedup" and cols_ % mc.num_groups == 0
                and mc.num_experts % cols_ == 0)
    body = _ep_dedup_local if dedup_ok else _ep_flat_local

    dp = pctx.dp_axes
    ftp = getattr(pctx, "ep_ftp", False)
    dp_total = 1
    for a in dp:
        dp_total *= mesh.shape[a]
    cols = mesh.shape[axis]

    xt = x.reshape(-1, shape[-1])
    T = xt.shape[0]
    # tokens per EP shard must divide evenly; decode shapes get padded and
    # masked into the overflow bucket (zero capacity, zero wire)
    tok_div = cols if ftp else dp_total * cols
    Tpad = -(-T // tok_div) * tok_div
    mask = jnp.arange(Tpad) < T
    if valid is not None:
        v = valid.reshape(-1).astype(bool)
        if Tpad != T:
            v = jnp.pad(v, (0, Tpad - T))
        mask = mask & v
    if Tpad != T:
        xt = jnp.pad(xt, [(0, Tpad - T), (0, 0)])

    if ftp:
        # decode mode: tokens replicated over dp; expert FF dim TP-sharded
        # over "data" (memory: E/cols * f/data per device); outputs are
        # partial sums over f -> psum over dp at the end.
        xspec = P(None, None)
        mspec = P(None)
        espec = P(axis, None, "data")
    else:
        xspec = P(dp if len(dp) > 1 else dp[0], None)
        mspec = P(dp if len(dp) > 1 else dp[0])
        espec = P(axis, None, None)

    wire = getattr(pctx, "wire", "fp8")

    def fn(wg, bias, w1, w3, w2, xloc, mloc):
        y, load, drop, aux = body(wg, bias, w1, w3, w2, xloc, mloc, cfg,
                                  axis, wire)
        if ftp:
            for a in dp:
                y = jax.lax.psum(y, a)       # combine expert-FF partials
        load = jax.lax.pmean(load, axis)
        drop = jax.lax.pmean(drop, axis)
        aux = jax.lax.pmean(aux, axis)
        for a in dp:
            load, drop, aux = (jax.lax.pmean(v, a) for v in (load, drop, aux))
        return y, load, drop, aux

    bias = p.get("bias")
    if bias is None:
        bias = jnp.zeros((mc.num_experts,), jnp.float32)
    w2spec = P(axis, "data", None) if ftp else espec   # w2: (E, f, d)
    y, load, drop, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None), P(None), espec, espec, w2spec, xspec, mspec),
        out_specs=(xspec, P(None), P(), P()),
        check_vma=False,
    )(p["w_gate"], bias, p["w1"], p["w3"], p["w2"], xt, mask)
    y = y[:T].reshape(shape)
    y = y + moe_mod.shared_expert(p, x, cfg)
    rr = routing.RouteResult(None, None, None, load, aux)
    return y, rr, drop
