"""DeepSeekMoE layer (paper §2.2, T2): fine-grained routed experts + shared
expert(s), node-limited routing (core/routing), static-capacity sort-based
dispatch (JAX adaptation — XLA needs static shapes, so we use the standard
capacity-buffer formulation; the paper's training is dropless, we default to
capacity_factor 1.25 and surface drop rates as a metric).

Three execution paths, equivalence-tested:
  * ``moe_ffn_oracle``  — brute force, no capacity (tests only)
  * ``moe_ffn``         — single-shard capacity dispatch (smoke/CPU)
  * ``parallel/ep.py``  — shard_map EP with two-hop node-limited dedup
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.core import routing
from repro.models.layers import act_fn
from repro.models.param import ParamSpec


def moe_specs(cfg: ModelConfig, layers: int) -> dict:
    mc = cfg.moe
    d, f = cfg.d_model, mc.expert_ff
    pd = cfg.param_dtype
    ed = cfg.expert_dtype or pd    # fp8 expert storage for serving
    L, la = (layers,), ("layers",)
    specs = {
        "w_gate": ParamSpec(L + (d, mc.num_experts), "float32",
                            la + ("embed", None), "normal"),
        "w1": ParamSpec(L + (mc.num_experts, d, f), ed,
                        la + ("experts", "embed", "expert_ff"), "fan_in"),
        "w3": ParamSpec(L + (mc.num_experts, d, f), ed,
                        la + ("experts", "embed", "expert_ff"), "fan_in"),
        "w2": ParamSpec(L + (mc.num_experts, f, d), ed,
                        la + ("experts", "expert_ff", "embed"), "fan_in"),
    }
    if mc.router_bias:
        # selection-only balancing bias; updated out-of-band by the trainer
        specs["bias"] = ParamSpec(L + (mc.num_experts,), "float32",
                                  la + (None,), "zeros")
    if mc.num_shared:
        fs = mc.shared_ff_dim() * mc.num_shared
        specs["ws1"] = ParamSpec(L + (d, fs), pd, la + ("embed", "mlp"), "fan_in")
        specs["ws3"] = ParamSpec(L + (d, fs), pd, la + ("embed", "mlp"), "fan_in")
        specs["ws2"] = ParamSpec(L + (fs, d), pd, la + ("mlp", "embed"), "fan_in")
    return specs


def ste_qdq_tile(x: jax.Array) -> jax.Array:
    """Straight-through 1x128-tile FP8 quant-dequant (activations)."""
    from repro.core import fp8
    return x + jax.lax.stop_gradient(fp8.qdq_tile(x) - x)


def ste_qdq_block(w: jax.Array) -> jax.Array:
    """Straight-through 128x128-block FP8 quant-dequant (weights); vmapped
    over leading expert dim if 3D."""
    from repro.core import fp8
    f = fp8.qdq_block
    if w.ndim == 3:
        f = jax.vmap(f)
    return w + jax.lax.stop_gradient(f(w) - w)


def expert_ffn(xbuf: jax.Array, w1, w3, w2, cfg: ModelConfig) -> jax.Array:
    """Grouped SwiGLU over capacity buffers. xbuf: (E, C, d)."""
    if cfg.fp8 and not cfg.expert_dtype:
        xbuf = ste_qdq_tile(xbuf)
        w1, w3, w2 = map(ste_qdq_block, (w1, w3, w2))
    elif cfg.expert_dtype:
        dt0 = jnp.dtype(cfg.dtype)
        w1, w3, w2 = (w.astype(dt0) for w in (w1, w3, w2))
    a = act_fn(cfg.act)
    dt = xbuf.dtype
    g = jnp.einsum("ecd,edf->ecf", xbuf, w1.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xbuf, w3.astype(dt))
    h = a(g) * u
    if cfg.fp8:
        h = ste_qdq_tile(h)
    return jnp.einsum("ecf,efd->ecd", h, w2.astype(dt))


def shared_expert(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if "ws1" not in p:
        return jnp.zeros_like(x)
    w1, w3, w2 = p["ws1"], p["ws3"], p["ws2"]
    if cfg.fp8:
        x = ste_qdq_tile(x)
        w1, w3, w2 = map(ste_qdq_block, (w1, w3, w2))
    dt = x.dtype
    h = act_fn(cfg.act)(x @ w1.astype(dt)) * (x @ w3.astype(dt))
    return h @ w2.astype(dt)


# ---------------------------------------------------------------------------
# Capacity dispatch plan (sort-based; O(Tk log Tk), no one-hot blowup)
# ---------------------------------------------------------------------------


def capacity(tokens: int, mc: MoEConfig, experts: Optional[int] = None,
             k: Optional[int] = None) -> int:
    """Static per-expert capacity-buffer rows for ``tokens`` assignments.

    Floors at 8 rows (rounded up to 8s for TPU-friendly tiling). The
    floor matters at decode shapes: an EP shard seeing only a few tokens
    per step pays 8 rows per expert column regardless of protocol, so
    ep_dedup's wire reduction only becomes visible once per-shard token
    counts lift capacity off the floor (serve_bench sizes its sharded
    rows accordingly)."""
    e = experts or mc.num_experts
    c = int(math.ceil(tokens * (k or mc.top_k) / e * mc.capacity_factor))
    return max(8, -(-c // 8) * 8)


class DispatchPlan(NamedTuple):
    dest: jax.Array    # (T*k,) int32 slot in (E*C,) buffer
    keep: jax.Array    # (T*k,) bool — slot within capacity
    drop_frac: jax.Array  # scalar fraction of dropped assignments


def capacity_dynamic(tokens: jax.Array, mc: MoEConfig,
                     experts: Optional[int] = None,
                     k: Optional[int] = None) -> jax.Array:
    """``capacity`` for a *traced* token count (bucketed prefill): the keep
    threshold a prompt of this many real tokens would get in an
    exact-length dispatch, while the buffer shape stays static."""
    e = experts or mc.num_experts
    c = jnp.ceil(tokens * (k or mc.top_k) * mc.capacity_factor
                 / e).astype(jnp.int32)
    return jnp.maximum(8, -(-c // 8) * 8)


def dispatch_plan(expert_idx: jax.Array, E: int, C: int,
                  valid: Optional[jax.Array] = None,
                  cap_limit: Optional[jax.Array] = None) -> DispatchPlan:
    """expert_idx: (T, k). Slot assignment per (token, choice), capacity C
    per expert, earlier tokens win (stable).

    ``valid`` (T,) demotes pad tokens below every real token in the
    per-expert ranking and drops them outright, so bucket padding can
    never displace a real token from a capacity slot; ``cap_limit`` (a
    traced scalar <= C) additionally applies the exact-length keep
    threshold so results match an unpadded dispatch token-for-token."""
    flat = expert_idx.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    if valid is None:
        key, stride = flat, 1
    else:
        validk = jnp.repeat(valid.astype(jnp.int32), expert_idx.shape[-1])
        key, stride = flat * 2 + (1 - validk), 2
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    sorted_e = sorted_key // stride
    starts = jnp.searchsorted(sorted_key,
                              stride * jnp.arange(E, dtype=sorted_key.dtype))
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    keep = rank < (C if cap_limit is None else cap_limit)
    if valid is not None:
        keep = keep & (validk > 0)
        denom = jnp.maximum(validk.sum(), 1)
    else:
        denom = n
    dest = jnp.where(keep, flat * C + rank, 0)
    drop = 1.0 - keep.sum() / denom
    return DispatchPlan(dest, keep, drop)


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig,
            capacity_override: Optional[int] = None,
            valid: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, routing.RouteResult, jax.Array]:
    """Single-shard MoE layer (all experts local). x: (B, S, d) or (T, d).
    ``valid`` masks bucket-padding tokens out of the capacity contest (see
    ``dispatch_plan``). Returns (y, route_result, drop_frac)."""
    mc = cfg.moe
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    T = xt.shape[0]
    rr = routing.route(xt, p["w_gate"], mc,
                       bias=p.get("bias") if mc.router_bias else None)
    C = capacity_override or capacity(T, mc)
    if valid is None:
        plan = dispatch_plan(rr.expert_idx, mc.num_experts, C)
    else:
        v = valid.reshape(-1)
        cap_eff = jnp.minimum(C, capacity_dynamic(v.sum(), mc))
        plan = dispatch_plan(rr.expert_idx, mc.num_experts, C,
                             valid=v, cap_limit=cap_eff)

    k = mc.top_k
    xk = jnp.repeat(xt, k, axis=0)                        # (T*k, d)
    buf = jnp.zeros((mc.num_experts * C, shape[-1]), xt.dtype)
    buf = buf.at[plan.dest].add(jnp.where(plan.keep[:, None], xk, 0))
    buf = buf.reshape(mc.num_experts, C, shape[-1])

    h = expert_ffn(buf, p["w1"], p["w3"], p["w2"], cfg)
    h = h.reshape(mc.num_experts * C, shape[-1])

    y = h[plan.dest] * plan.keep[:, None]                 # (T*k, d)
    w = rr.weights.reshape(-1)[:, None].astype(y.dtype)
    y = (y * w).reshape(T, k, shape[-1]).sum(1)
    y = y + shared_expert(p, xt, cfg)
    return y.reshape(shape), rr, plan.drop_frac


def moe_ffn_oracle(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Brute-force dropless oracle (tests): every expert runs every token."""
    mc = cfg.moe
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    rr = routing.route(xt, p["w_gate"], mc,
                       bias=p.get("bias") if mc.router_bias else None)
    a = act_fn(cfg.act)
    dt = xt.dtype

    def one_expert(w1, w3, w2):
        h = a(xt @ w1.astype(dt)) * (xt @ w3.astype(dt))
        return h @ w2.astype(dt)

    if cfg.fp8:
        xq = ste_qdq_tile(xt)
        def one_expert(w1, w3, w2):  # noqa: F811
            h = a(xq @ ste_qdq_block(w1).astype(dt)) * (
                xq @ ste_qdq_block(w3).astype(dt))
            return ste_qdq_tile(h) @ ste_qdq_block(w2).astype(dt)

    all_y = jax.vmap(one_expert)(p["w1"], p["w3"], p["w2"])  # (E, T, d)
    onehot = jax.nn.one_hot(rr.expert_idx, mc.num_experts,
                            dtype=jnp.float32)               # (T, k, E)
    wts = (onehot * rr.weights[..., None]).sum(1)            # (T, E)
    y = jnp.einsum("te,etd->td", wts.astype(dt), all_y)
    return (y + shared_expert(p, xt, cfg)).reshape(shape)
