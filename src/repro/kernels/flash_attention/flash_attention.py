"""Flash-style bucketed prefill attention kernel.

``Model.prefill`` / ``prefill_chunk`` pad prompts to power-of-two length
buckets and attend with a full (S, T) score matrix per head
(``layers._attn_direct``). This kernel computes the same masked softmax
block-tiled — grid ``(B, H, S/bq, T/bk)`` with an online softmax over the
key blocks — so prefill attention memory is O(bq*bk) per step instead of
O(S*T) per head, the standard FlashAttention recurrence over the bucket.

Masking matches ``_attn_direct`` exactly: a key is attendable iff
``k_pos >= 0`` (pad slots carry ``k_pos = -1`` in decode-cache layouts),
``k_pos <= q_pos`` under causal, with pads above real positions excluded
by causality in bucketed prefill. A query row with *no* valid key (a pad
row past every real token) emits zeros rather than the uniform mix the
dense softmax produces — pad-row outputs are dropped by the trash-row /
valid-mask contract (serving.md §2), so only junk differs.

Inputs:
  q (B, S, H, hd), k/v (B, T, KV, hd) in the compute dtype
  q_pos (B, S) i32, k_pos (B, T) i32
Output: (B, S, H, hd) f32.

TPU tiling: heads are lane blocks of the merged ``(B, S, H*hd)`` view
(``hd`` a multiple of 128, or a single head), query positions arrive as a
``(bq, 1)`` column and key positions as a ``(1, bk)`` lane row (``bk`` a
multiple of 128 or the whole key axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.paged_attention import (NEG, dot_nn,
                                                           dot_nt)


def _kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, m_ref, l_ref,
            acc_ref, *, causal: bool, scale: float):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = dot_nt(q, k) * scale                           # (bq, bk)
    qp = qp_ref[0]                                     # (bq, 1)
    kp = kp_ref[0]                                     # (1, bk)
    valid = kp >= 0
    if causal:
        valid = valid & (kp <= qp)
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[...]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)      # (bq, bk)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + dot_nn(p, v)
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _emit():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "bq", "bk",
                                    "interpret"))
def flash_prefill_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                         q_pos: jax.Array, k_pos: jax.Array, *,
                         causal: bool, scale: float, bq: int, bk: int,
                         interpret: bool = False) -> jax.Array:
    """q (B,S,H,hd); k/v (B,T,KV,hd); q_pos (B,S); k_pos (B,T). S % bq and
    T % bk must be 0 (the registry wrapper pads). Each (b, h) walks its
    KV head's key blocks; GQA maps query head h to KV head
    ``h // (H // KV)`` in the index maps."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(B, H, S // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, h, qi, ki: (b, qi, h)),
            pl.BlockSpec((1, bk, hd), lambda b, h, qi, ki: (b, ki, h // G)),
            pl.BlockSpec((1, bk, hd), lambda b, h, qi, ki: (b, ki, h // G)),
            pl.BlockSpec((1, bq, 1), lambda b, h, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, qi, ki: (b, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, h, qi, ki: (b, qi, h)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H * hd), jnp.float32),
        interpret=interpret,
        name="flash_prefill",
    )(q.reshape(B, S, H * hd), k.reshape(B, T, KV * hd),
      v.reshape(B, T, KV * hd), q_pos.astype(jnp.int32).reshape(B, S, 1),
      k_pos.astype(jnp.int32).reshape(B, 1, T))
    return out.reshape(B, S, H, hd)
