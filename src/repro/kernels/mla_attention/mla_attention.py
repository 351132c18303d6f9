"""MLA absorbed-decode flash kernel (paper §2.1.2 / §2.3.2).

The decode hot loop the paper identifies as memory-bound: one query head
set against the latent cache — GEMV-shaped, bytes-dominated. Streaming the
(T, R) latent cache through VMEM in ``bt`` blocks with an online softmax
keeps exactly one pass over HBM (the roofline minimum):

  scores_blk = (q_abs @ ckv_blk^T + q_rope @ kr_blk^T) * scale  (H, bt)
  online-softmax accumulate  o = sum p * ckv_blk                (H, R)

Inputs (per batch element b, handled by the grid's first axis):
  q_abs (B, H, R)  — W_uk-absorbed queries (R = kv_lora_rank)
  q_rope (B, H, Rr), ckv (B, T, R), kr (B, T, Rr)
  pos (B, T) int32 cache-slot positions (-1 = empty), qpos (B,) int32

Output: o_lat (B, H, R) fp32 — latent-space attention output (W_uv applied
by the caller).

Block shapes: (H, R) = (128, 512) query tile is MXU-aligned; bt=256 cache
rows/step => VMEM ≈ bt*(R+Rr)*4B ≈ 0.6 MB plus (H,bt) scores — well within
budget while the arithmetic stays (H x bt x R) matmuls (MXU-friendly).
TPU tiling: ``pos`` is viewed as (B, 1, T) so its block is a lane row
(``bt`` a multiple of 128 or the whole cache), and ``qpos`` rides in SMEM
as a scalar-prefetch operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.paged_attention import (NEG, dot_nn,
                                                           dot_nt)


def _kernel(qpos_ref, qa_ref, qr_ref, ckv_ref, kr_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ckv = ckv_ref[0].astype(jnp.float32)           # (bt, R)
    kr = kr_ref[0].astype(jnp.float32)             # (bt, Rr)
    pos = pos_ref[0]                               # (1, bt)
    s = (dot_nt(qa_ref[0], ckv) + dot_nt(qr_ref[0], kr)) * scale
    valid = (pos >= 0) & (pos <= qpos_ref[b])      # (1, bt)
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[...]                            # (H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (H, bt)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + dot_nn(p, ckv)
    m_ref[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bt", "interpret"))
def mla_decode_kernel(q_abs: jax.Array, q_rope: jax.Array, ckv: jax.Array,
                      kr: jax.Array, pos: jax.Array, qpos: jax.Array, *,
                      scale: float, bt: int = 256,
                      interpret: bool = False) -> jax.Array:
    B, H, R = q_abs.shape
    Rr = q_rope.shape[-1]
    T = ckv.shape[1]
    assert T % bt == 0, (T, bt)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # qpos
        grid=(B, T // bt),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, t, qp: (b, 0, 0)),
            pl.BlockSpec((1, H, Rr), lambda b, t, qp: (b, 0, 0)),
            pl.BlockSpec((1, bt, R), lambda b, t, qp: (b, t, 0)),
            pl.BlockSpec((1, bt, Rr), lambda b, t, qp: (b, t, 0)),
            pl.BlockSpec((1, 1, bt), lambda b, t, qp: (b, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda b, t, qp: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        interpret=interpret,
        name="mla_decode",
    )(qpos.astype(jnp.int32), q_abs.astype(jnp.float32),
      q_rope.astype(jnp.float32), ckv, kr,
      pos.astype(jnp.int32).reshape(B, 1, T))
