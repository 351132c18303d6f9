"""Shared layer zoo: norms, RoPE, dense linears (optionally on the FP8
path), GQA / local / cross attention with decode caches, SwiGLU MLP.

All layers are functional: ``*_specs(cfg)`` returns a ParamSpec pytree,
``apply`` style functions take the materialized params.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import fp8
from repro.models.param import ParamSpec

# ---------------------------------------------------------------------------
# Basics
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * gamma.astype(jnp.float32)).astype(dt)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def linear(x: jax.Array, w: Union[jax.Array, fp8.BlockQuantized],
           cfg: Optional[ModelConfig] = None,
           b: Optional[jax.Array] = None) -> jax.Array:
    """Dense GEMM; routes through the FP8 fine-grained-scaled path (paper
    T4) when the config enables it. A weight already held as
    ``fp8.BlockQuantized`` (serving, see ``fp8_weights``) skips its
    per-call quantization; the GEMM's operands are unchanged."""
    if fp8.is_quantized(w):
        y = fp8.prequantized_linear(x, w)
    elif cfg is not None and cfg.fp8 and w.ndim == 2 and x.shape[-1] >= 256:
        y = fp8.fp8_linear(x, w)
    else:
        y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# The weights each block passes to ``linear``, by the block's key. MLA's
# ``w_uk`` / ``w_uv`` are left out: decode absorbs them in f32 einsums.
_LINEAR_WEIGHTS = {
    "attn": ("wq", "wk", "wv", "wo", "w_dq", "w_uq", "w_dkv", "w_kr", "w_o"),
    "xattn": ("wq", "wk", "wv", "wo"),
    "mlp": ("w_gate", "w_up", "w_down"),
    "mtp": ("w_proj",),
}


def fp8_weights(params, cfg: ModelConfig) -> Dict[str, jax.Array]:
    """The bf16 weight stacks that ``linear`` sends to ``fp8_linear``,
    by ``keystr`` path: those a server may hold quantized once."""
    out: Dict[str, jax.Array] = {}
    if not cfg.fp8:
        return out

    def visit(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        if (len(names) >= 2 and not fp8.is_quantized(leaf)
                and names[-1] in _LINEAR_WEIGHTS.get(names[-2], ())
                and leaf.shape[-2] >= 256):
            out[jax.tree_util.keystr(path)] = leaf

    jax.tree_util.tree_map_with_path(visit, params, is_leaf=fp8.is_quantized)
    return out


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                         # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                              # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (also MHA/MQA; optional sliding window; optional qk-norm)
# ---------------------------------------------------------------------------


def gqa_specs(cfg: ModelConfig, layers: int) -> dict:
    d, hd = cfg.d_model, cfg.head_dim_()
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    pd = cfg.param_dtype
    L = (layers,)
    la = ("layers",)
    specs = {
        "wq": ParamSpec(L + (d, nh * hd), pd, la + ("embed", "heads"), "fan_in"),
        "wk": ParamSpec(L + (d, nkv * hd), pd, la + ("embed", "kv_heads"), "fan_in"),
        "wv": ParamSpec(L + (d, nkv * hd), pd, la + ("embed", "kv_heads"), "fan_in"),
        "wo": ParamSpec(L + (nh * hd, d), pd, la + ("heads", "embed"), "fan_in"),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec(L + (nh * hd,), pd, la + ("heads",), "zeros")
        specs["bk"] = ParamSpec(L + (nkv * hd,), pd, la + ("kv_heads",), "zeros")
        specs["bv"] = ParamSpec(L + (nkv * hd,), pd, la + ("kv_heads",), "zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec(L + (hd,), pd, la + (None,), "ones")
        specs["k_norm"] = ParamSpec(L + (hd,), pd, la + (None,), "ones")
    return specs


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))


def _attn_direct(q, k, v, *, causal: bool, q_pos, k_pos, window: int = 0,
                 scale: float):
    """Unchunked attention. q: (B,S,H,hd) k/v: (B,T,KV,hd'). Mask: attend
    iff k_pos <= q_pos (causal), q_pos - k_pos < window (if window>0), and
    k_pos >= 0 (padding slots in decode caches carry k_pos = -1)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, S, KV, G, hd)
    # operands stay in model dtype; accumulate fp32 (MXU-style) — avoids
    # materializing fp32 copies of the K/V cache (XLA would hoist the
    # upcast across the layer scan, inflating memory L-fold)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = k_pos[:, None, :] >= 0                         # (B,S?,T) valid
    if causal:
        mask = mask & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        mask = mask & (q_pos[:, :, None] - k_pos[:, None, :] < window)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    hv = v.shape[-1]
    return out.reshape(B, S, H, hv).astype(v.dtype)


# q-block size for the chunked (memory-roofline-friendly) path; blocks are
# remat'd so backward recomputes scores instead of storing S x T.
ATTN_BLOCK_Q = 512


def attention_scores(q, k, v, *, causal: bool, q_pos, k_pos,
                     window: int = 0, scale: float = 0.0,
                     block_q: int = 0, impl: str = "xla"):
    """Chunked attention: scan over query blocks; each block's S_b x T
    score tile lives only transiently (and is recomputed in backward via
    jax.checkpoint). This bounds attention memory to O(B*H*block_q*T) per
    device instead of O(B*H*S*T) — required for the 32k prefill cells and
    a first-class memory-roofline lever (EXPERIMENTS.md §Perf).

    ``impl="pallas"`` dispatches multi-token unwindowed attention through
    the ``flash_prefill`` registry kernel (block-tiled online softmax over
    the power-of-two bucket — no full S x T score matrix at all)."""
    B, S, H, hd = q.shape
    scale = scale or 1.0 / math.sqrt(hd)
    if (impl == "pallas" and S > 1 and not window
            and k.shape[-1] == hd and v.shape[-1] == hd):
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_prefill(q, k, v, q_pos, k_pos,
                                      causal=causal, scale=scale)
        return out.astype(v.dtype)
    bq = block_q or ATTN_BLOCK_Q
    if S <= bq or S % bq != 0:
        return _attn_direct(q, k, v, causal=causal, q_pos=q_pos,
                            k_pos=k_pos, window=window, scale=scale)
    nb = S // bq
    qb = jnp.moveaxis(q.reshape(B, nb, bq, H, hd), 1, 0)
    pb = jnp.moveaxis(q_pos.reshape(B, nb, bq), 1, 0)

    @jax.checkpoint
    def body(_, inp):
        qi, pi = inp
        out = _attn_direct(qi, k, v, causal=causal, q_pos=pi, k_pos=k_pos,
                           window=window, scale=scale)
        # pin the (small) block output head-sharded so GSPMD reshards HERE
        # rather than redistributing the (huge) fp32 score tiles
        from repro.parallel.context import shard_heads
        return None, shard_heads(out)

    _, ob = jax.lax.scan(body, None, (qb, pb))
    return jnp.moveaxis(ob, 0, 1).reshape(B, S, H, v.shape[-1])


def gqa_attention(p: dict, x: jax.Array, *, cfg: ModelConfig,
                  positions: jax.Array, causal: bool = True,
                  window: int = 0,
                  cache: Optional[dict] = None,
                  kv_x: Optional[jax.Array] = None,
                  kv_positions: Optional[jax.Array] = None,
                  page_table: Optional[jax.Array] = None,
                  impl: str = "xla"):
    """GQA self/cross attention. If ``cache`` is given, appends this step's
    K/V at slot ``positions`` and attends over the cache (decode). If
    ``kv_x`` is given, cross-attention over that memory (no cache logic).
    With ``page_table``, ``cache`` is a paged K/V pool slice (see
    ``core/paged.py``): the step writes this token's K/V (quantized under
    fp8 storage) into its slot's current page and attends over the slot's
    gathered pages. Returns (out, new_cache).
    """
    hd = cfg.head_dim_()
    src = x if kv_x is None else kv_x
    q = linear(x, p["wq"], cfg, p.get("bq"))
    k = linear(src, p["wk"], cfg, p.get("bk"))
    v = linear(src, p["wv"], cfg, p.get("bv"))
    q = _split_heads(q, cfg.num_heads)
    k = _split_heads(k, cfg.num_kv_heads)
    v = _split_heads(v, cfg.num_kv_heads)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_eps)
    if kv_x is None:  # self-attention -> RoPE
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions if kv_positions is None else kv_positions,
                       cfg.rope_theta)
        k_pos = positions if kv_positions is None else kv_positions
    else:
        k_pos = kv_positions
        causal = False
    if cache is None and q.shape[1] > 1:      # train/prefill layout pin
        from repro.parallel.context import shard_heads
        q, k, v = shard_heads(q), shard_heads(k), shard_heads(v)

    new_cache = None
    if cache is not None and page_table is not None:
        # paged decode: write k,v (B,S,KV,hd) into the slot's pages and
        # attend over its gathered pages (core/paged.py layout). S == 1 is
        # the fused-decode step; S > 1 is a page-aligned chunked-prefill
        # run written whole-pages-first (page_write_chunk).
        from repro.core import paged
        B, S = x.shape[:2]
        KV = cfg.num_kv_heads
        qpos = positions[:, 0]
        fp8 = "k_scale" in cache
        new_cache = dict(cache)
        if S == 1:
            def pwrite(pool, vals):
                return paged.page_write(pool, page_table, qpos, vals[:, 0])
        else:
            def pwrite(pool, vals):
                return paged.page_write_chunk(pool, page_table, qpos, vals)
        # pools hold one token's (KV, hd) entry as a flat row
        kf, vf = k.reshape(B, S, KV * hd), v.reshape(B, S, KV * hd)
        if fp8:
            qk, sk = paged.quantize_vecs(kf)
            qv, sv = paged.quantize_vecs(vf)
            new_cache["k"] = pwrite(cache["k"], qk)
            new_cache["v"] = pwrite(cache["v"], qv)
            new_cache["k_scale"] = pwrite(cache["k_scale"], sk)
            new_cache["v_scale"] = pwrite(cache["v_scale"], sv)
        else:
            new_cache["k"] = pwrite(cache["k"], kf)
            new_cache["v"] = pwrite(cache["v"], vf)
        if impl == "pallas" and S == 1 and not window:
            # registry-dispatched scalar-prefetch kernel: walks the page
            # table in SMEM, dequantizes E4M3 rows in-register, online
            # softmax with GQA head-group broadcasting — no host-side
            # gather/dequant round-trip (docs/kernel_backends.md)
            from repro.kernels.paged_attention import ops as paged_ops
            ones = jnp.ones(cache["k"].shape[:2], jnp.float32)
            kp, vp = new_cache["k"], new_cache["v"]
            if kp.dtype == jnp.uint8:  # byte pool -> E4M3 view for the kernel
                kp = jax.lax.bitcast_convert_type(kp, paged.E4M3)
                vp = jax.lax.bitcast_convert_type(vp, paged.E4M3)
            o = paged_ops.paged_gqa_decode(
                q[:, 0].astype(jnp.float32),
                kp, vp,
                new_cache.get("k_scale", ones), new_cache.get("v_scale", ones),
                page_table, qpos, scale=1.0 / math.sqrt(hd))
            out = o[:, None].astype(cfg.dtype)
        else:
            if fp8:
                kc = paged.gather_dequant(new_cache["k"], new_cache["k_scale"],
                                          page_table).astype(cfg.dtype)
                vc = paged.gather_dequant(new_cache["v"], new_cache["v_scale"],
                                          page_table).astype(cfg.dtype)
            else:
                kc = paged.table_gather(new_cache["k"], page_table)
                vc = paged.table_gather(new_cache["v"], page_table)
                kc = kc.astype(cfg.dtype) if kc.dtype != jnp.dtype(cfg.dtype) else kc
                vc = vc.astype(cfg.dtype) if vc.dtype != jnp.dtype(cfg.dtype) else vc
            T = kc.shape[1]
            kc = kc.reshape(B, T, KV, hd)
            vc = vc.reshape(B, T, KV, hd)
            # positional validity: k_pos is the logical index itself (pages
            # never ring-wrap), so attention_scores' mask k_pos <= q_pos is
            # exactly "written by this slot"; stale/trash rows sit above qpos
            kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :],
                                    (B, T))
            out = attention_scores(q, kc, vc, causal=causal,
                                   q_pos=positions, k_pos=kpos,
                                   window=window, impl=impl)
    elif cache is not None:
        # decode: write k,v (B,1,KV,hd) at ring slot position %% T per batch
        T = cache["k"].shape[1]
        B = x.shape[0]
        idx = (positions[:, 0] % T).astype(jnp.int32)     # (B,)
        ba = jnp.arange(B)
        upd = lambda buf, val: buf.at[ba, idx].set(val[:, 0].astype(buf.dtype))
        ck = upd(cache["k"], k)
        cv = upd(cache["v"], v)
        cpos = cache["pos"].at[ba, idx].set(positions[:, 0])
        new_cache = dict(k=ck, v=cv, pos=cpos)
        kc = ck.astype(cfg.dtype) if ck.dtype != jnp.dtype(cfg.dtype) else ck
        vc = cv.astype(cfg.dtype) if cv.dtype != jnp.dtype(cfg.dtype) else cv
        out = attention_scores(q, kc, vc, causal=causal,
                               q_pos=positions, k_pos=cpos, window=window,
                               impl=impl)
    else:
        out = attention_scores(q, k, v, causal=causal,
                               q_pos=positions, k_pos=k_pos, window=window,
                               impl=impl)
    out = out.reshape(out.shape[:-2] + (cfg.num_heads * hd,))
    return linear(out, p["wo"], cfg), new_cache


def init_gqa_cache(cfg: ModelConfig, layers: int, batch: int, max_len: int,
                   window: int = 0) -> dict:
    """Ring-buffer KV cache. For windowed attention the buffer is only
    ``window`` slots (RecurrentGemma-style bounded cache)."""
    T = min(max_len, window) if window else max_len
    hd = cfg.head_dim_()
    dt = jnp.dtype(cfg.cache_dtype_())
    return dict(
        k=jnp.zeros((layers, batch, T, cfg.num_kv_heads, hd), dt),
        v=jnp.zeros((layers, batch, T, cfg.num_kv_heads, hd), dt),
        pos=-jnp.ones((layers, batch, T), jnp.int32),
    )


def init_paged_gqa_cache(cfg: ModelConfig, layers: int, pool_pages: int,
                         page_size: int, storage: str) -> dict:
    """K/V page pool (no batch axis: pages are shared across slots).

    Leaves ``(layers, pool_pages+1, page, KV*hd)`` — one token's ``(KV,
    hd)`` entry is a flat row, so a page is a ``(page, KV*hd)`` tile whose
    KV heads are 128-lane blocks for the paged kernel; the last page is
    the trash page. FP8 storage adds per-token fp32 scale leaves (one
    scale over a token's whole entry). No ``pos`` leaf — validity is
    positional (see ``core/paged.py``).
    """
    from repro.core import paged
    paged.validate_storage(storage)
    fp8 = storage == "fp8"
    hd = cfg.head_dim_()
    # fp8 pools hold raw E4M3 bytes (uint8): native scan/scatter dtype —
    # see paged._to_store. Values are still E4M3, read via paged.e4m3_decode.
    dt = jnp.uint8 if fp8 else jnp.dtype(cfg.cache_dtype_())
    P1 = pool_pages + 1
    c = dict(
        k=jnp.zeros((layers, P1, page_size, cfg.num_kv_heads * hd), dt),
        v=jnp.zeros((layers, P1, page_size, cfg.num_kv_heads * hd), dt),
    )
    if fp8:
        c["k_scale"] = jnp.zeros((layers, P1, page_size), jnp.float32)
        c["v_scale"] = jnp.zeros((layers, P1, page_size), jnp.float32)
    return c


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, layers: int, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pd = cfg.param_dtype
    L, la = (layers,), ("layers",)
    return {
        "w_gate": ParamSpec(L + (d, f), pd, la + ("embed", "mlp"), "fan_in"),
        "w_up": ParamSpec(L + (d, f), pd, la + ("embed", "mlp"), "fan_in"),
        "w_down": ParamSpec(L + (f, d), pd, la + ("mlp", "embed"), "fan_in"),
    }


def mlp(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    g = act_fn(cfg.act)(linear(x, p["w_gate"], cfg))
    u = linear(x, p["w_up"], cfg)
    return linear(g * u, p["w_down"], cfg)
