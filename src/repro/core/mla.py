"""Multi-head Latent Attention (paper §2.1.2, T1; DeepSeek-V2/V3).

Two execution forms, equivalence-tested against each other:

* **naive** (train/prefill): reconstruct per-head K_nope/V from the latent
  ``c_kv`` and run standard attention — the GEMM-rich form.
* **absorbed** (decode): cache only ``(rmsnorm(c_kv), k_rope)`` per token
  (kv_lora_rank + qk_rope_dim floats — Table 1's 70 KB/token for V3), absorb
  W_uk into the query and W_uv into the output so each step is GEMVs against
  the latent cache. This is the memory-bound form the paper analyzes; the
  Pallas flash-decode kernel (kernels/mla_attention) implements it blockwise.

KV-cache bytes/token/layer = (kv_lora_rank + qk_rope_dim) * dtype_bytes —
reproduced exactly in benchmarks/table1_kv_cache.py.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import apply_rope, linear, rmsnorm
from repro.models.param import ParamSpec


def mla_specs(cfg: ModelConfig, layers: int) -> dict:
    m = cfg.mla
    assert m is not None
    d, nh = cfg.d_model, cfg.num_heads
    pd = cfg.param_dtype
    L, la = (layers,), ("layers",)
    qk = m.qk_nope_dim + m.qk_rope_dim
    return {
        "w_dq": ParamSpec(L + (d, m.q_lora_rank), pd, la + ("embed", None), "fan_in"),
        "q_norm": ParamSpec(L + (m.q_lora_rank,), pd, la + (None,), "ones"),
        "w_uq": ParamSpec(L + (m.q_lora_rank, nh * qk), pd, la + (None, "heads"), "fan_in"),
        "w_dkv": ParamSpec(L + (d, m.kv_lora_rank), pd, la + ("embed", None), "fan_in"),
        "kv_norm": ParamSpec(L + (m.kv_lora_rank,), pd, la + (None,), "ones"),
        "w_kr": ParamSpec(L + (d, m.qk_rope_dim), pd, la + ("embed", None), "fan_in"),
        "w_uk": ParamSpec(L + (m.kv_lora_rank, nh * m.qk_nope_dim), pd,
                          la + (None, "heads"), "fan_in"),
        "w_uv": ParamSpec(L + (m.kv_lora_rank, nh * m.v_head_dim), pd,
                          la + (None, "heads"), "fan_in"),
        "w_o": ParamSpec(L + (nh * m.v_head_dim, d), pd, la + ("heads", "embed"), "fan_in"),
    }


def _queries(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    m = cfg.mla
    nh = cfg.num_heads
    cq = rmsnorm(linear(x, p["w_dq"], cfg), p["q_norm"], cfg.rms_eps)
    q = linear(cq, p["w_uq"], cfg)
    q = q.reshape(q.shape[:-1] + (nh, m.qk_nope_dim + m.qk_rope_dim))
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _latents(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """Per-token cached quantities: normalized latent + shared RoPE key."""
    m = cfg.mla
    ckv = rmsnorm(linear(x, p["w_dkv"], cfg), p["kv_norm"], cfg.rms_eps)
    kr = linear(x, p["w_kr"], cfg)
    kr = apply_rope(kr[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return ckv, kr


def mla_attention(p: dict, x: jax.Array, *, cfg: ModelConfig,
                  positions: jax.Array,
                  return_cache_entries: bool = False):
    """Naive (train/prefill) MLA: full causal attention.

    x: (B, S, d). Returns out (B, S, d) and optionally the latent cache
    entries (ckv (B,S,rank), kr (B,S,rope)) for prefill cache fill.
    """
    m = cfg.mla
    nh = cfg.num_heads
    B, S, _ = x.shape
    q_nope, q_rope = _queries(p, x, cfg, positions)
    ckv, kr = _latents(p, x, cfg, positions)
    k_nope = linear(ckv, p["w_uk"], cfg).reshape(B, S, nh, m.qk_nope_dim)
    v = linear(ckv, p["w_uv"], cfg).reshape(B, S, nh, m.v_head_dim)

    # combined-head form: K = [k_nope ; kr] shared-rope concat, so the
    # chunked attention path (layers.attention_scores) serves MLA too
    from repro.models.layers import attention_scores
    from repro.parallel.context import shard_heads
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)        # (B,S,nh,192)
    kk = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None], (B, S, nh, m.qk_rope_dim))],
        axis=-1)
    qq, kk, v = shard_heads(qq), shard_heads(kk), shard_heads(v)
    out = attention_scores(qq, kk, v, causal=True, q_pos=positions,
                           k_pos=positions, scale=scale)
    out = out.reshape(B, S, nh * m.v_head_dim).astype(x.dtype)
    out = linear(out, p["w_o"], cfg)
    if return_cache_entries:
        return out, (ckv, kr)
    return out


# ---------------------------------------------------------------------------
# Decode: latent cache + weight-absorbed attention
# ---------------------------------------------------------------------------


def init_mla_cache(cfg: ModelConfig, layers: int, batch: int,
                   max_len: int) -> dict:
    m = cfg.mla
    dt = jnp.dtype(cfg.cache_dtype_())
    return dict(
        ckv=jnp.zeros((layers, batch, max_len, m.kv_lora_rank), dt),
        kr=jnp.zeros((layers, batch, max_len, m.qk_rope_dim), dt),
        pos=-jnp.ones((layers, batch, max_len), jnp.int32),
    )


def init_paged_mla_cache(cfg: ModelConfig, layers: int, pool_pages: int,
                         page_size: int, storage: str) -> dict:
    """Latent page pool (no batch axis: pages are shared across slots).

    Leaves ``(layers, pool_pages+1, page, rank/rope)``; the last page is
    the trash page. FP8 storage adds per-token fp32 scale leaves. No
    ``pos`` leaf — paged validity is positional (see core/paged.py).
    """
    from repro.core import paged
    m = cfg.mla
    paged.validate_storage(storage)
    fp8 = storage == "fp8"
    # fp8 pools hold raw E4M3 bytes (uint8): native scan/scatter dtype —
    # see paged._to_store. Values are still E4M3, read via paged.e4m3_decode.
    dt = jnp.uint8 if fp8 else jnp.dtype(cfg.cache_dtype_())
    P1 = pool_pages + 1
    c = dict(
        ckv=jnp.zeros((layers, P1, page_size, m.kv_lora_rank), dt),
        kr=jnp.zeros((layers, P1, page_size, m.qk_rope_dim), dt),
    )
    if fp8:
        c["ckv_scale"] = jnp.zeros((layers, P1, page_size), jnp.float32)
        c["kr_scale"] = jnp.zeros((layers, P1, page_size), jnp.float32)
    return c


def _absorb_queries(p: dict, q_nope: jax.Array, cfg: ModelConfig):
    """q_abs[h] = q_nope[h] @ W_uk[h]^T — queries into latent space."""
    m, nh = cfg.mla, cfg.num_heads
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, nh, m.qk_nope_dim)
    return jnp.einsum("bshn,chn->bshc", q_nope.astype(jnp.float32),
                      w_uk.astype(jnp.float32))           # (B,1,nh,rank)


def _absorbed_attention(q_abs, q_rope, ckv, kr, valid, cfg: ModelConfig):
    """Shared absorbed-decode softmax over a dense latent view.

    ckv/kr: (B, T, rank/rope) cache rows (any layout origin — ring or
    gathered pages); valid: (B, T) shared across queries, or (B, S, T)
    per-query (chunked prefill, where validity ``l <= qpos_i`` also covers
    intra-chunk causality). One implementation so the dense and paged XLA
    paths are bitwise-identical given identical rows and masks. Returns
    o_lat (B, S, nh, rank) fp32.
    """
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if ckv.dtype != jnp.dtype(cfg.dtype):   # fp8 cache -> compute dtype
        ckv = ckv.astype(cfg.dtype)
        kr = kr.astype(cfg.dtype)
    cdt = ckv.dtype
    scores = (jnp.einsum("bshc,btc->bhst", q_abs.astype(cdt), ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_rope.astype(cdt), kr,
                           preferred_element_type=jnp.float32)) * scale
    mask = valid[:, None, None, :] if valid.ndim == 2 else valid[:, None]
    scores = jnp.where(mask, scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,btc->bshc", attn.astype(cdt), ckv,
                      preferred_element_type=jnp.float32)


def _absorbed_out(p: dict, o_lat: jax.Array, x: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """Absorb W_uv on the way out: out[h] = o_lat[h] @ W_uv[h]."""
    m, nh = cfg.mla, cfg.num_heads
    B, S = o_lat.shape[:2]
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, nh, m.v_head_dim)
    out = jnp.einsum("bshc,chv->bshv", o_lat, w_uv.astype(jnp.float32))
    out = out.reshape(B, S, nh * m.v_head_dim).astype(x.dtype)
    return linear(out, p["w_o"], cfg)


def mla_decode_step(p: dict, cache: dict, x: jax.Array, *,
                    cfg: ModelConfig, positions: jax.Array,
                    impl: str = "xla") -> Tuple[jax.Array, dict]:
    """Absorbed-form decode. x: (B, 1, d); cache leaves are per-layer slices
    (B, T, ...). Returns (out (B,1,d), new_cache)."""
    m = cfg.mla
    B = x.shape[0]
    T = cache["ckv"].shape[1]

    q_nope, q_rope = _queries(p, x, cfg, positions)       # (B,1,nh,*)
    ckv_new, kr_new = _latents(p, x, cfg, positions)      # (B,1,rank/rope)

    idx = (positions[:, 0] % T).astype(jnp.int32)     # (B,)
    ba = jnp.arange(B)
    ckv = cache["ckv"].at[ba, idx].set(ckv_new[:, 0].astype(cache["ckv"].dtype))
    kr = cache["kr"].at[ba, idx].set(kr_new[:, 0].astype(cache["kr"].dtype))
    pos = cache["pos"].at[ba, idx].set(positions[:, 0])
    new_cache = dict(ckv=ckv, kr=kr, pos=pos)

    q_abs = _absorb_queries(p, q_nope, cfg)

    if impl == "pallas":
        # registry-dispatched kernel op (backend per repro.kernels.registry)
        from repro.kernels.mla_attention import ops as mla_ops
        o_lat = mla_ops.mla_decode(
            q_abs[:, 0], q_rope[:, 0].astype(jnp.float32), ckv, kr, pos,
            positions[:, 0], scale=1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim))
        o_lat = o_lat[:, None]
    else:
        valid = (pos >= 0) & (pos <= positions)   # (B,T); positions (B,1)
        o_lat = _absorbed_attention(q_abs, q_rope, ckv, kr, valid, cfg)

    return _absorbed_out(p, o_lat, x, cfg), new_cache


def paged_kernel_decode(impl: str, S: int = 1) -> bool:
    """Whether paged attention over ``S`` query positions runs the
    ``paged_mla_decode`` kernel: the ``pallas`` impl, one position.
    Chunked prefill (``S > 1``) and the ``xla`` impl take the XLA
    gather."""
    return impl == "pallas" and S == 1


def mla_paged_decode_step(p: dict, cache: dict, x: jax.Array, *,
                          cfg: ModelConfig, positions: jax.Array,
                          page_table: jax.Array,
                          impl: str = "xla") -> Tuple[jax.Array, dict]:
    """Paged absorbed-form decode (paper §2.1.2 quantized compression).

    cache: one layer's pool slice — ckv/kr ``(P+1, page, ...)`` plus
    ``*_scale`` leaves under fp8 storage. page_table: (B, pages_per_slot)
    physical page ids. The step quantizes this token's latents into its
    slot's current page, then attends over the slot's gathered pages —
    in-register dequantization on the ``pallas`` impl, an XLA gather that
    reuses the dense softmax (bitwise-identical at native storage) on
    ``xla``. Also serves chunked prefill: ``x`` may carry ``S > 1`` tokens
    (a page-aligned run — positions[:, 0] on a page boundary, S a multiple
    of the page size); the run is written whole-pages-first, then attended
    with per-query validity, which subsumes intra-chunk causality. The
    pallas kernel stays single-token; S > 1 always takes the XLA path.
    Returns (out (B,S,d), new_cache).
    """
    from repro.core import paged
    m = cfg.mla
    S = x.shape[1]
    qpos = positions[:, 0]
    fp8 = "ckv_scale" in cache

    q_nope, q_rope = _queries(p, x, cfg, positions)       # (B,S,nh,*)
    ckv_new, kr_new = _latents(p, x, cfg, positions)      # (B,S,rank/rope)

    new_cache = dict(cache)
    if S == 1:
        def write(pool, vals):
            return paged.page_write(pool, page_table, qpos, vals[:, 0])
    else:
        def write(pool, vals):
            return paged.page_write_chunk(pool, page_table, qpos, vals)
    with jax.named_scope("cache_write"):
        if fp8:
            qc, sc = paged.quantize_vecs(ckv_new)
            qk, sk = paged.quantize_vecs(kr_new)
            new_cache["ckv"] = write(cache["ckv"], qc)
            new_cache["kr"] = write(cache["kr"], qk)
            new_cache["ckv_scale"] = write(cache["ckv_scale"], sc)
            new_cache["kr_scale"] = write(cache["kr_scale"], sk)
        else:
            new_cache["ckv"] = write(cache["ckv"], ckv_new)
            new_cache["kr"] = write(cache["kr"], kr_new)

    q_abs = _absorb_queries(p, q_nope, cfg)
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)

    if paged_kernel_decode(impl, S):
        from repro.kernels.paged_attention import ops as paged_ops
        ones = jnp.ones(cache["ckv"].shape[:2], jnp.float32)
        ckv_p, kr_p = new_cache["ckv"], new_cache["kr"]
        if ckv_p.dtype == jnp.uint8:   # byte pool -> E4M3 view for the kernel
            ckv_p = jax.lax.bitcast_convert_type(ckv_p, paged.E4M3)
            kr_p = jax.lax.bitcast_convert_type(kr_p, paged.E4M3)
        o_lat = paged_ops.paged_mla_decode(
            q_abs[:, 0], q_rope[:, 0].astype(jnp.float32),
            ckv_p, kr_p,
            new_cache.get("ckv_scale", ones), new_cache.get("kr_scale", ones),
            page_table, qpos, scale=scale)
        o_lat = o_lat[:, None]
    else:
        if fp8:
            # fused byte-gather + LUT dequant (paged.gather_dequant): same
            # values as table_gather + dequantize_vecs, one pass
            ckv_t = paged.gather_dequant(new_cache["ckv"],
                                         new_cache["ckv_scale"],
                                         page_table).astype(cfg.dtype)
            kr_t = paged.gather_dequant(new_cache["kr"],
                                        new_cache["kr_scale"],
                                        page_table).astype(cfg.dtype)
        else:
            ckv_t = paged.table_gather(new_cache["ckv"], page_table)
            kr_t = paged.table_gather(new_cache["kr"], page_table)
        T = ckv_t.shape[1]
        # positional validity: everything at or below the query's position
        # was written by this slot (pages never ring-wrap). Per-query for
        # multi-token runs, which is exactly intra-chunk causal masking.
        if S == 1:
            valid = jnp.arange(T, dtype=jnp.int32)[None, :] <= qpos[:, None]
        else:
            valid = (jnp.arange(T, dtype=jnp.int32)[None, None, :]
                     <= positions[:, :, None])
        o_lat = _absorbed_attention(q_abs, q_rope, ckv_t, kr_t, valid, cfg)

    return _absorbed_out(p, o_lat, x, cfg), new_cache


def kv_bytes_per_token(cfg: ModelConfig, dtype_bytes: int = 2,
                       storage: str = "") -> int:
    """Table 1 quantity: latent-cache bytes per token across all layers.

    ``storage`` overrides ``dtype_bytes`` with the paged-cache storage
    formats: ``"bf16"`` is the paper's 2-byte row (70 KB/token for V3);
    ``"fp8"`` is 1 byte/element plus the per-token fp32 scale pair
    (ckv + k_rope) each layer — just over half the bf16 row.
    """
    m = cfg.mla
    row = m.kv_lora_rank + m.qk_rope_dim
    if storage:
        from repro.core import paged
        paged.validate_storage(storage)
        if storage == "fp8":
            return (row + 2 * 4) * cfg.num_layers
        dtype_bytes = 2
    return row * dtype_bytes * cfg.num_layers
