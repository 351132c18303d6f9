"""FP8 fine-grained mixed-precision path (paper §3.1, T4).

Faithful reproduction of the DeepSeek-V3 recipe, adapted to TPU:

* activations: 1x128 tile-wise scales along the contraction dim
* weights:     128x128 block-wise scales
* accumulation: fp32 (the TPU MXU accumulates in fp32 natively — this is
  exactly the paper's §3.1.2 "increased accumulation precision" ask, so on
  TPU the recipe needs no FP22-style workaround)
* gradients:   1x128 tile-wise E4M3 on both backward GEMMs (custom_vjp)

Storage uses ``jnp.float8_e4m3fn`` (a real 1-byte dtype in JAX), so memory
and communication byte counts are genuine. Compute upcasts tiles to fp32 —
on TPU the MXU runs bf16/fp32; the byte savings (HBM + ICI) are where FP8
wins on this hardware, as laid out in DESIGN.md §2.

The GEMM runs inline in jnp (``scaled_matmul_ref``), forward and both
backward GEMMs alike.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2
E4M3_MAX = 448.0
E5M2_MAX = 57344.0

TILE = 128   # paper's 1x128 activation tiles
BLOCK = 128  # paper's 128x128 weight blocks


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def quantize_tilewise(x: jax.Array, tile: int = TILE,
                      dtype=E4M3) -> Tuple[jax.Array, jax.Array]:
    """Quantize along the last axis in 1 x ``tile`` groups.

    Returns (q, scales): q has x's shape (padded-to-tile then sliced back is
    avoided: we require the caller's last dim; padding handled internally),
    scales has shape x.shape[:-1] + (ceil(d/tile),), fp32.
    """
    d = x.shape[-1]
    xp = _pad_to(x.astype(jnp.float32), -1, tile)
    t = xp.reshape(xp.shape[:-1] + (-1, tile))
    maxv = E4M3_MAX if dtype == E4M3 else E5M2_MAX
    amax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / maxv
    q = (t / scale).astype(dtype)
    q = q.reshape(xp.shape)[..., :d]
    return q, scale[..., 0]


def quantize_blockwise(w: jax.Array, block: int = BLOCK,
                       dtype=E4M3) -> Tuple[jax.Array, jax.Array]:
    """Quantize a (m, n) weight in ``block`` x ``block`` squares.

    Returns (q (m,n), scales (ceil(m/b), ceil(n/b)) fp32).
    """
    m, n = w.shape
    wp = _pad_to(_pad_to(w.astype(jnp.float32), 0, block), 1, block)
    M, N = wp.shape
    t = wp.reshape(M // block, block, N // block, block)
    maxv = E4M3_MAX if dtype == E4M3 else E5M2_MAX
    amax = jnp.max(jnp.abs(t), axis=(1, 3), keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / maxv
    q = (t / scale).astype(dtype).reshape(M, N)[:m, :n]
    return q, scale[:, 0, :, 0]


def dequant_tilewise(q: jax.Array, scale: jax.Array, tile: int = TILE) -> jax.Array:
    d = q.shape[-1]
    qp = _pad_to(q.astype(jnp.float32), -1, tile)
    t = qp.reshape(qp.shape[:-1] + (-1, tile)) * scale[..., None]
    return t.reshape(qp.shape)[..., :d]


def dequant_blockwise(q: jax.Array, scale: jax.Array, block: int = BLOCK) -> jax.Array:
    m, n = q.shape
    qp = _pad_to(_pad_to(q.astype(jnp.float32), 0, block), 1, block)
    M, N = qp.shape
    t = qp.reshape(M // block, block, N // block, block)
    t = t * scale[:, None, :, None]
    return t.reshape(M, N)[:m, :n]


def qdq_tile(x: jax.Array, tile: int = TILE, dtype=E4M3) -> jax.Array:
    q, s = quantize_tilewise(x, tile, dtype)
    return dequant_tilewise(q, s, tile).astype(x.dtype)


def qdq_block(w: jax.Array, block: int = BLOCK, dtype=E4M3) -> jax.Array:
    q, s = quantize_blockwise(w, block, dtype)
    return dequant_blockwise(q, s, block).astype(w.dtype)


def scaled_matmul_ref(xq, xs, wq, ws, tile: int = TILE) -> jax.Array:
    """Oracle: per-tile scaled GEMM with fp32 accumulation.

    xq: (..., d) fp8, xs: (..., d/tile) fp32
    wq: (d, f) fp8, ws: (d/block, f/block) fp32
    Mathematically identical to dequantize-then-matmul (scales are constant
    within each contraction tile), which is what we do — the Pallas kernel
    applies scales per-tile on the accumulator instead (the paper's
    "inside the Tensor Core" version). The dequantization runs under the
    ``fp8_quant`` scope, the GEMM outside it.
    """
    with jax.named_scope("fp8_quant"):
        x = dequant_tilewise(xq, xs, tile)
        w = dequant_blockwise(wq, ws)
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


class BlockQuantized(NamedTuple):
    """A weight held as ``quantize_blockwise``'s pair, computed once.

    Leading dims are stack dims (a segment's layers): as a pytree node the
    pair is sliced by a layer scan like the array it replaces.
    """
    q: jax.Array       # (..., m, n) E4M3
    scale: jax.Array   # (..., ceil(m/BLOCK), ceil(n/BLOCK)) fp32

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.q.shape


def is_quantized(w) -> bool:
    return isinstance(w, BlockQuantized)


def quantize_weight(w) -> BlockQuantized:
    """``quantize_blockwise`` over each trailing (m, n) matrix of ``w``;
    an already-quantized pair passes through unchanged."""
    if is_quantized(w):
        return w
    f = quantize_blockwise
    for _ in range(w.ndim - 2):
        f = jax.vmap(f)
    return BlockQuantized(*f(w))


def prequantized_linear(x: jax.Array, w: BlockQuantized) -> jax.Array:
    """``fp8_linear``'s forward on a weight quantized ahead of time: the
    activations still take per-call 1x128 tile scales."""
    with jax.named_scope("fp8_quant"):
        xq, xs = quantize_tilewise(x)
    return scaled_matmul_ref(xq, xs, w.q, w.scale).astype(x.dtype)


def _matmul_qdq(x: jax.Array, w: jax.Array) -> jax.Array:
    """y = Q(x) @ Q(w) with fine-grained scales, fp32 accum."""
    with jax.named_scope("fp8_quant"):
        xq, xs = quantize_tilewise(x)
        wq, ws = quantize_blockwise(w)
    return scaled_matmul_ref(xq, xs, wq, ws)


@jax.custom_vjp
def fp8_linear(x: jax.Array, w: jax.Array) -> jax.Array:
    """FP8-path linear: fwd and both bwd GEMMs run quantized (paper recipe).

    x: (..., d) bf16/f32, w: (d, f). Returns (..., f) in x.dtype.
    """
    return _matmul_qdq(x, w).astype(x.dtype)


def _fp8_linear_fwd(x, w):
    y = _matmul_qdq(x, w).astype(x.dtype)
    return y, (x, w)


def _fp8_linear_bwd(res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    # dx = Q(g) @ Q(w^T): tile-quantize g along f, block-quantize w
    gq, gs = quantize_tilewise(gf)
    wtq, wts = quantize_blockwise(w.T.astype(jnp.float32))
    dx = scaled_matmul_ref(gq, gs, wtq, wts).astype(x.dtype)
    # dw = Q(x)^T @ Q(g): contraction over tokens; tile-quantize along tokens
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    g2 = gf.reshape(-1, gf.shape[-1])
    xtq, xts = quantize_tilewise(x2.T)           # (d, T) tiles along tokens
    gtq, gts = quantize_blockwise(g2)            # (T, f) blocks
    dw = scaled_matmul_ref(xtq, xts, gtq, gts).astype(w.dtype)
    return dx, dw


fp8_linear.defvjp(_fp8_linear_fwd, _fp8_linear_bwd)
