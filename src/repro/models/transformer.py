"""Transformer block kinds: dense (GQA or MLA), MoE, cross-attention,
encoder, and encoder-decoder decoder blocks. Used by models/api.py to
assemble every transformer-family arch via scanned segments.

Blocks are pre-norm residual. Each ``*_block_specs(cfg, prefix)`` returns a
ParamSpec pytree whose leaves have leading dims ``prefix`` (the scan axes);
``block_apply`` consumes one layer slice.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import mla as mla_mod
from repro.core import moe as moe_mod
from repro.models import layers as Lyr
from repro.models.param import ParamSpec


def _norm_spec(cfg: ModelConfig, prefix: Tuple[int, ...]) -> ParamSpec:
    return ParamSpec(prefix + (cfg.d_model,), cfg.param_dtype,
                     ("layers",) * len(prefix) + (None,), "ones")


def _prefixed(specs: dict, prefix: Tuple[int, ...]) -> dict:
    """Add extra leading scan dims to a spec tree built with layers=prefix[-1].

    Spec builders accept a single ``layers`` int; for nested scans we extend
    shapes/axes with the outer dims.
    """
    extra = prefix[:-1]
    if not extra:
        return specs

    def fix(s: ParamSpec) -> ParamSpec:
        return ParamSpec(tuple(extra) + s.shape, s.dtype,
                         ("layers",) * len(extra) + s.axes, s.init, s.scale)

    return jax.tree.map(fix, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Self-attention + FFN blocks
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig, n: int) -> dict:
    if cfg.attention == "mla":
        return mla_mod.mla_specs(cfg, n)
    return Lyr.gqa_specs(cfg, n)


def dense_block_specs(cfg: ModelConfig, prefix: Tuple[int, ...],
                      d_ff: Optional[int] = None) -> dict:
    n = prefix[-1]
    return _prefixed({
        "ln1": _norm_spec(cfg, (n,)),
        "attn": attn_specs(cfg, n),
        "ln2": _norm_spec(cfg, (n,)),
        "mlp": Lyr.mlp_specs(cfg, n, d_ff),
    }, prefix)


def moe_block_specs(cfg: ModelConfig, prefix: Tuple[int, ...]) -> dict:
    n = prefix[-1]
    return _prefixed({
        "ln1": _norm_spec(cfg, (n,)),
        "attn": attn_specs(cfg, n),
        "ln2": _norm_spec(cfg, (n,)),
        "moe": moe_mod.moe_specs(cfg, n),
    }, prefix)


def cross_block_specs(cfg: ModelConfig, prefix: Tuple[int, ...]) -> dict:
    """Llama-3.2-vision style gated cross-attention layer (with its own FFN).
    Cross K/V come from patch embeddings; gates start at zero."""
    n = prefix[-1]
    return _prefixed({
        "ln1": _norm_spec(cfg, (n,)),
        "xattn": Lyr.gqa_specs(cfg, n),
        "gate_attn": ParamSpec((n,), cfg.param_dtype, ("layers",), "zeros"),
        "ln2": _norm_spec(cfg, (n,)),
        "mlp": Lyr.mlp_specs(cfg, n),
        "gate_mlp": ParamSpec((n,), cfg.param_dtype, ("layers",), "zeros"),
    }, prefix)


def decoder_block_specs(cfg: ModelConfig, prefix: Tuple[int, ...]) -> dict:
    """Enc-dec decoder block: self-attn + cross-attn + FFN (seamless)."""
    n = prefix[-1]
    return _prefixed({
        "ln1": _norm_spec(cfg, (n,)),
        "attn": Lyr.gqa_specs(cfg, n),
        "lnx": _norm_spec(cfg, (n,)),
        "xattn": Lyr.gqa_specs(cfg, n),
        "ln2": _norm_spec(cfg, (n,)),
        "mlp": Lyr.mlp_specs(cfg, n),
    }, prefix)


# ---------------------------------------------------------------------------
# Apply fns. ctx: dict(positions, memory, mem_positions, window, causal)
# cache: per-layer slice dict or None. Returns (x, new_cache)
# ---------------------------------------------------------------------------


def _self_attention(p: dict, h: jax.Array, cfg: ModelConfig, ctx: dict,
                    cache):
    # paged pool slices carry no "pos" leaf (positional validity); the
    # page table rides in ctx (one (B, pages) array shared by every layer)
    paged = cache is not None and "pos" not in cache
    if cfg.attention == "mla":
        if cache is not None:
            if paged:
                return mla_mod.mla_paged_decode_step(
                    p, cache, h, cfg=cfg, positions=ctx["positions"],
                    page_table=ctx["page_table"],
                    impl=ctx.get("mla_impl", "xla"))
            return mla_mod.mla_decode_step(
                p, cache, h, cfg=cfg, positions=ctx["positions"],
                impl=ctx.get("mla_impl", "xla"))
        if ctx.get("collect_cache"):
            out, (ckv, kr) = mla_mod.mla_attention(
                p, h, cfg=cfg, positions=ctx["positions"],
                return_cache_entries=True)
            return out, (ckv, kr)
        return mla_mod.mla_attention(p, h, cfg=cfg,
                                     positions=ctx["positions"]), None
    window = ctx.get("window", 0)
    out, new_cache = Lyr.gqa_attention(
        p, h, cfg=cfg, positions=ctx["positions"],
        causal=ctx.get("causal", True), window=window, cache=cache,
        page_table=ctx["page_table"] if paged else None,
        impl=ctx.get("gqa_impl", "xla"))
    if cache is None and ctx.get("collect_cache"):
        # prefill: return this layer's K/V entries for cache assembly
        src = h
        k = Lyr.linear(src, p["wk"], cfg, p.get("bk"))
        v = Lyr.linear(src, p["wv"], cfg, p.get("bv"))
        k = Lyr._split_heads(k, cfg.num_kv_heads)
        v = Lyr._split_heads(v, cfg.num_kv_heads)
        if cfg.qk_norm:
            k = Lyr.rmsnorm(k, p["k_norm"], cfg.rms_eps)
        k = Lyr.apply_rope(k, ctx["positions"], cfg.rope_theta)
        return out, (k, v)
    return out, new_cache


def _ffn(p: dict, h: jax.Array, cfg: ModelConfig, ctx: Optional[dict] = None):
    """Routed-MoE or dense FFN, honoring the parallel context."""
    if "moe" in p:
        from repro.parallel import context as pctx
        c = pctx.get()
        if c.ep_enabled:
            # train, prefill AND decode: bucketed-prefill pad masking (ctx
            # "valid") folds pads into the dispatch's overflow bucket, so
            # they consume no capacity and no wire (see moe_ffn_sharded)
            from repro.parallel import ep
            y, rr, drop = ep.moe_ffn_sharded(p["moe"], h, cfg, c,
                                             valid=(ctx or {}).get("valid"))
        else:
            y, rr, drop = moe_mod.moe_ffn(
                p["moe"], h, cfg, valid=(ctx or {}).get("valid"))
        return y, {"aux_loss": rr.aux_loss, "load": rr.load, "drop": drop}
    return Lyr.mlp(p["mlp"], h, cfg), {}


def block_apply(p: dict, x: jax.Array, cfg: ModelConfig, ctx: dict,
                cache=None):
    """Generic (dense|moe) self-attention block. Each sub-block, its
    residual add included (XLA may fuse the add into the output GEMM, and
    a fusion carries its root op's scope), runs under a named scope that
    the device trace's ops carry: ``attn`` and ``ffn``."""
    with jax.named_scope("attn"):
        h, cache_out = _self_attention(p["attn"],
                                       Lyr.rmsnorm(x, p["ln1"], cfg.rms_eps),
                                       cfg, ctx, cache)
        x = x + h
    with jax.named_scope("ffn"):
        f, stats = _ffn(p, Lyr.rmsnorm(x, p["ln2"], cfg.rms_eps), cfg, ctx)
        x = x + f
    return x, cache_out, stats


def cross_block_apply(p: dict, x: jax.Array, cfg: ModelConfig, ctx: dict,
                      cache=None):
    """Gated cross-attention block (vision). Memory K/V can be served from
    ``cache`` (precomputed at prefill) to skip re-projection each step."""
    mem = ctx["memory"]
    h = Lyr.rmsnorm(x, p["ln1"], cfg.rms_eps)
    out, _ = Lyr.gqa_attention(
        p["xattn"], h, cfg=cfg, positions=ctx["positions"], causal=False,
        kv_x=mem, kv_positions=ctx["mem_positions"])
    x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * out
    f = Lyr.mlp(p["mlp"], Lyr.rmsnorm(x, p["ln2"], cfg.rms_eps), cfg)
    return x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * f, None, {}


def decoder_block_apply(p: dict, x: jax.Array, cfg: ModelConfig, ctx: dict,
                        cache=None):
    """Enc-dec decoder block (self + cross + FFN)."""
    h = Lyr.rmsnorm(x, p["ln1"], cfg.rms_eps)
    out, cache_out = _self_attention(p["attn"], h, cfg, ctx, cache)
    x = x + out
    h = Lyr.rmsnorm(x, p["lnx"], cfg.rms_eps)
    out, _ = Lyr.gqa_attention(
        p["xattn"], h, cfg=cfg, positions=ctx["positions"], causal=False,
        kv_x=ctx["memory"], kv_positions=ctx["mem_positions"])
    x = x + out
    f = Lyr.mlp(p["mlp"], Lyr.rmsnorm(x, p["ln2"], cfg.rms_eps), cfg)
    return x + f, cache_out, {}


def encoder_block_apply(p: dict, x: jax.Array, cfg: ModelConfig, ctx: dict,
                        cache=None):
    """Non-causal self-attention encoder block."""
    ctx = dict(ctx, causal=False)
    return block_apply(p, x, cfg, ctx, cache=None)
