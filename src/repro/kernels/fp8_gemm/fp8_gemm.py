"""Fine-grained-scaled FP8 GEMM Pallas kernel (DeepGEMM adapted to TPU).

Computes y = (xq * xs) @ (wq * ws) where
  xq: (M, K) float8_e4m3fn, xs: (M, K/128) fp32   (1x128 tiles)
  wq: (K, N) float8_e4m3fn, ws: (K/128, N/128) fp32 (128x128 blocks)

TPU adaptation of the paper's §3.1.2 "native fine-grained quantization"
ask: the per-tile scales are applied to the MXU *partial sums* inside the
kernel (valid because scales are constant within each K=128 group), so no
separate dequant pass ever touches HBM. Operands feed the MXU as bf16
(fp8->bf16 is exact: E4M3 ⊂ bf16), accumulation is fp32 in VMEM scratch —
the "increased accumulation precision" the paper requests, natively.

Grid: (M/bm, N/bn, K/128), K innermost for sequential accumulation.
Default tiles bm=256, bn=256: VMEM ≈ bm*bk + bk*bn (bf16) + bm*bn*4 (acc)
≈ 0.4 MB — far under the ~16 MB/core budget, MXU-aligned (128 multiples).

TPU tiling: the scale sidebands are re-laid out before the call so each
block's last two dims are whole or (8, 128)-aligned — activation scales
as a ``(K/128, M, 1)`` column per K-group, weight scales broadcast to a
``(K/128, 1, N)`` lane row per K-group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128  # scale granularity (fixed by the format)


def _kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = xq_ref[...].astype(jnp.bfloat16)          # (bm, 128) exact upcast
    b = wq_ref[...].astype(jnp.bfloat16)          # (128, bn)
    part = jnp.dot(a, b, preferred_element_type=jnp.float32)
    # scales constant within this K-group: apply to the partial result
    acc_ref[...] += part * (xs_ref[0] * ws_ref[0])    # (bm,1)*(1,bn)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fp8_gemm(xq: jax.Array, xs: jax.Array, wq: jax.Array, ws: jax.Array,
             *, bm: int = 256, bn: int = 256,
             interpret: bool = False) -> jax.Array:
    M, K = xq.shape
    _, N = wq.shape
    assert K % BLOCK == 0 and M % bm == 0 and N % bn == 0, (M, K, N)
    assert xs.shape == (M, K // BLOCK) and ws.shape == (K // BLOCK, N // BLOCK)
    xs_col = xs.astype(jnp.float32).T[:, :, None]               # (kb, M, 1)
    ws_row = jnp.repeat(ws.astype(jnp.float32), BLOCK, axis=1)[:, None, :]

    grid = (M // bm, N // bn, K // BLOCK)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, BLOCK), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, bm, 1), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((BLOCK, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="fp8_gemm",
    )(xq, xs_col, wq, ws_row)
