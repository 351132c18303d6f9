"""Paged MLA / GQA absorbed-decode flash kernels (paper §2.1.2 + §2.3.2).

The dense flash-decode kernel (kernels/mla_attention) streams a slot's
*contiguous* latent cache. Under the paged cache (core/paged.py) a slot's
tokens live in non-contiguous fixed-size pages of a shared pool, stored
FP8 with per-token scales — so the kernel must follow the slot's page
table and dequantize in-register:

  grid = (B, pages_per_slot); step (b, t) DMAs physical page
  ``table[b, t]`` of the pool into VMEM via **scalar-prefetch indexing**
  (the page table is an SMEM-resident prefetch operand consumed by the
  BlockSpec index maps), and folds the page into an online softmax over
  the latent dimension. The per-token scales multiply the score columns
  and the probability columns, never the (page, R) value tile, so the
  scale sideband arrives as a lane row ``(1, page)``:

    s   = (q_abs @ q8^T) * cs + (q_rope @ kr8^T) * ks   (H, page)
    online-softmax accumulate  o = sum (p * cs) @ q8   (H, R)

Validity is positional: logical row ``t*page + i`` of slot ``b`` is
attendable iff it is ``<= qpos[b]`` (paged caches never ring-wrap, so
everything at or below the current decode position was written by this
slot; trash/stale rows all sit above it).

The MLA kernel's work follows ``qpos``: slot ``b``'s last live page is
``last = max(qpos[b], 0) // page`` (capped at ``pp - 1``), and every row
of a later page lies above ``qpos``. Its page index maps read
``table[b, min(t, last)]``, so a dead step ``t > last`` repeats the
previous step's block index and the pipeline issues no DMA for it; its
dots and softmax update sit under ``pl.when(t <= last)``. A dead step
still costs one grid step's bookkeeping (the index map's scalar
arithmetic and the ``pl.when`` tests), not a page fetch or a dot. The
output is bitwise what a walk over all ``pp`` pages gives for a finite
pool (a dead page only folds ``acc * 1 + 0`` and ``l * 1 + 0``), and a
dead page's bytes, NaN included, never reach it. A slot with
``qpos < 0`` computes its first page fully masked and emits zeros.

HBM traffic is one pass over the slot's *live* pages at 1 byte/elem
(+4/token scales) — the memory-bound decode path the paper's Table 1 /
§2.3.2 roofline is about, at roughly half the bf16 bytes. The GQA
kernel still walks all ``pp`` pages.

TPU tiling: every block's last two dims are either whole array dims or
(8, 128)-aligned. Pools are ``(P+1, page, width)`` so a page block
``(1, page, width)`` always spans whole dims; scale sidebands are viewed
as ``(P+1, 1, page)``. Mosaic refuses ``uint8 -> f32`` converts in-kernel,
so byte pools are bitcast to E4M3 before the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b.T`` with fp32 accumulation at full fp32 operand precision
    (the attention kernels' score product)."""
    return _dot(a, b, (((1,), (1,)), ((), ())))


def dot_nn(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` with fp32 accumulation at full fp32 operand precision."""
    return _dot(a, b, (((1,), (0,)), ((), ())))


def _online_softmax_step(s, valid, v_scaled_p, m_ref, l_ref, acc_ref, v):
    """Fold one (rows, page) score tile into the running softmax state.
    ``v_scaled_p`` maps probabilities to the value-side weights (per-token
    dequant scale folded onto the probability columns)."""
    s = jnp.where(valid, s, NEG)
    m_prev = m_ref[...]                                # (rows, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)      # (rows, page)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + dot_nn(v_scaled_p(p), v)
    m_ref[...] = m_new


def _last_live_page(qpos, page: int, pp: int):
    """A slot's last live page: ``max(qpos, 0) // page``, capped at
    ``pp - 1``. Every row of a later page lies above ``qpos``."""
    return jnp.minimum(jax.lax.div(jnp.maximum(qpos, 0), page), pp - 1)


def live_pages(qpos: np.ndarray, page: int, pp: int) -> np.ndarray:
    """How many pages ``paged_mla_decode`` fetches and computes for each
    query position in ``qpos`` (host numpy, any shape): the pages up to
    :func:`_last_live_page`, the kernel's own rule."""
    return np.minimum(np.maximum(qpos, 0) // page, pp - 1) + 1


def _kernel(table_ref, qpos_ref, qa_ref, qr_ref, ckv_ref, kr_ref,
            cs_ref, ks_ref, o_ref, m_ref, l_ref, acc_ref, *, page: int):
    b = pl.program_id(0)
    t = pl.program_id(1)
    pp = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t <= _last_live_page(qpos_ref[b], page, pp))
    def _live():
        ckv = ckv_ref[0].astype(jnp.float32)           # (page, R)
        kr = kr_ref[0].astype(jnp.float32)             # (page, Rr)
        cs = cs_ref[0]                                 # (1, page)
        s = (dot_nt(qa_ref[0], ckv) * cs
             + dot_nt(qr_ref[0], kr) * ks_ref[0])   # (H, page)
        # positional validity: logical row index vs current decode position
        lpos = t * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        valid = lpos <= qpos_ref[b]                    # (1, page)
        _online_softmax_step(s, valid, lambda p: p * cs, m_ref, l_ref,
                             acc_ref, ckv)

    @pl.when(t == pp - 1)
    def _emit():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _row_scales(s: jax.Array) -> jax.Array:
    """(P+1, page) per-token scales -> (P+1, 1, page) lane rows."""
    return s.reshape(s.shape[0], 1, s.shape[1]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_mla_decode_kernel(q_abs: jax.Array, q_rope: jax.Array,
                            ckv: jax.Array, kr: jax.Array,
                            ckv_s: jax.Array, kr_s: jax.Array,
                            table: jax.Array, qpos: jax.Array, *,
                            scale: float,
                            interpret: bool = False) -> jax.Array:
    """q_abs (B,H,R) / q_rope (B,H,Rr); ckv (P+1, page, R) and kr (P+1,
    page, Rr) in E4M3 or the native dtype; ckv_s/kr_s (P+1, page) fp32
    per-token scales (ones for native storage); table (B, pp) physical
    page ids; qpos (B,). Returns the latent output (B, H, R) fp32.
    Grid (B, pp), of which only slot ``b``'s live pages ``t <=
    max(qpos[b], 0) // page`` are fetched and computed (module
    docstring)."""
    B, H, R = q_abs.shape
    Rr = q_rope.shape[-1]
    page = ckv.shape[1]
    pp = table.shape[1]

    # scale folded into q (fp8 rows are scaled per token, so the score
    # scale distributes onto the query side for free)
    qa = q_abs.astype(jnp.float32) * scale
    qr = q_rope.astype(jnp.float32) * scale

    def live_page(b, t, tbl, qp):
        # a dead step repeats the last live page's block index, so the
        # pipeline fetches nothing for it
        return (tbl[b, jnp.minimum(t, _last_live_page(qp[b], page, pp))],
                0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # table, qpos
        grid=(B, pp),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, t, tbl, qp: (b, 0, 0)),
            pl.BlockSpec((1, H, Rr), lambda b, t, tbl, qp: (b, 0, 0)),
            pl.BlockSpec((1, page, R), live_page),
            pl.BlockSpec((1, page, Rr), live_page),
            pl.BlockSpec((1, 1, page), live_page),
            pl.BlockSpec((1, 1, page), live_page),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda b, t, tbl, qp: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        interpret=interpret,
        name="paged_mla_decode",
    )(table, qpos, qa, qr, ckv, kr, _row_scales(ckv_s), _row_scales(kr_s))


def _gqa_kernel(table_ref, qpos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                o_ref, m_ref, l_ref, acc_ref, *, page: int):
    """Grid (B, KV, pp): one KV head's page run per (b, kv); the G query
    heads of that group ride along in the block (GQA broadcasting is the
    (G, page) score tile against one shared K page)."""
    b = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = k_ref[0].astype(jnp.float32)                   # (page, hd)
    v = v_ref[0].astype(jnp.float32)
    vs = vs_ref[0]                                     # (1, page)
    s = dot_nt(q_ref[0, 0], k) * ks_ref[0]          # (G, page)
    # positional validity: logical row index vs current decode position
    lpos = t * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = lpos <= qpos_ref[b]                        # (1, page)
    _online_softmax_step(s, valid, lambda p: p * vs, m_ref, l_ref, acc_ref,
                         v)

    @pl.when(t == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_gqa_decode_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                            k_s: jax.Array, v_s: jax.Array,
                            table: jax.Array, qpos: jax.Array, *,
                            scale: float,
                            interpret: bool = False) -> jax.Array:
    """Paged GQA decode: same scalar-prefetch page walk as the MLA kernel,
    with the head axis split (KV, G) so each grid step streams one KV
    head's page while its G query heads accumulate online-softmax state.

    q (B, H, hd); k/v (P+1, page, KV*hd) E4M3 or native — one token's
    K (or V) entry is a flat row, KV head ``j`` at lanes ``[j*hd,
    (j+1)*hd)``; k_s/v_s (P+1, page) fp32 per-token scales (ones for
    native storage); table (B, pp) physical page ids; qpos (B,). On TPU
    ``hd`` must be a multiple of 128 (or KV == 1). Returns (B, H, hd) fp32.
    """
    B, H, hd = q.shape
    page, KV = k.shape[1], k.shape[2] // hd
    G = H // KV
    pp = table.shape[1]

    # scale folded into q (fp8 rows carry per-token scales, so the score
    # scale distributes onto the query side for free); head axis factors
    # as (KV, G) — the _split_heads / _attn_direct convention
    qf = (q.astype(jnp.float32) * scale).reshape(B, KV, G, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # table, qpos
        grid=(B, KV, pp),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, kv, t, tbl, qp: (b, kv, 0, 0)),
            pl.BlockSpec((1, page, hd),
                         lambda b, kv, t, tbl, qp: (tbl[b, t], 0, kv)),
            pl.BlockSpec((1, page, hd),
                         lambda b, kv, t, tbl, qp: (tbl[b, t], 0, kv)),
            pl.BlockSpec((1, 1, page),
                         lambda b, kv, t, tbl, qp: (tbl[b, t], 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b, kv, t, tbl, qp: (tbl[b, t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, kv, t, tbl, qp: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        interpret=interpret,
        name="paged_gqa_decode",
    )(table, qpos, qf, k, v, _row_scales(k_s), _row_scales(v_s))
    return out.reshape(B, H, hd)
