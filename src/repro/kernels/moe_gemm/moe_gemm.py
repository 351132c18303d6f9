"""Grouped expert GEMM Pallas kernel (DeepSeekMoE compute hot-spot).

Computes y[e] = x[e] @ w[e] for capacity-buffer layouts:
  x: (E, C, D), w: (E, D, F) -> y: (E, C, F)

This is the MoE analogue of DeepGEMM's grouped GEMM: per-expert tiles are
streamed through VMEM with fp32 accumulation; E rides the outermost grid
axis so one expert's weights stay resident while its capacity rows stream.
Tiles MXU-aligned (multiples of 128 where shapes allow).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # operands in their own dtype: bf16 feeds the MXU natively with exact
    # products; fp32 asks for full operand precision (Mosaic accepts the
    # precision attribute on fp32 operands only)
    a, b = x_ref[0], w_ref[0]
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _emit():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bk", "interpret"))
def moe_gemm(x: jax.Array, w: jax.Array, *, bc: int = 128, bf: int = 256,
             bk: int = 256, interpret: bool = False) -> jax.Array:
    E, C, D = x.shape
    _, _, F = w.shape
    w = w.astype(x.dtype)
    bc, bf, bk = min(bc, C), min(bf, F), min(bk, D)
    assert C % bc == 0 and F % bf == 0 and D % bk == 0, (C, D, F)
    from jax.experimental.pallas import tpu as pltpu

    grid = (E, C // bc, F // bf, D // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda e, c, f, k: (e, c, k)),
            pl.BlockSpec((1, bk, bf), lambda e, c, f, k: (e, k, f)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, c, f, k: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
        name="moe_gemm",
    )(x, w)
