"""LogFMT-nBit encode/decode Pallas kernels (paper §3.2, §6.5).

The paper found fusing log/exp codecs into Hopper all-to-all costs 50–100 %
(slow SFU log/exp + register pressure). On TPU the VPU runs transcendentals
wide; these kernels put the codec next to the data in VMEM so the wire
format (n-bit codes + per-tile sideband) is produced in one pass — the
"native compression unit" the paper asks hardware for (§3.2.2).

Layout: x (N, D) with D % 128 == 0; per 1x128 tile emits uint8/16 codes
plus fp32 (mn, step) sideband. A block is ``bn`` whole rows ``(bn, D)``:
the sideband block ``(bn, D/128)`` then spans its whole last dim, which
TPU tiling requires, and the kernel walks the row's tiles as static
128-lane slices.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 128
RANGE_CLAMP = 32.0 * math.log(2.0)


def _encode_kernel(x_ref, code_ref, mn_ref, step_ref, *, n_bits: int):
    levels = 2 ** (n_bits - 1) - 1
    for j in range(x_ref.shape[1] // TILE):
        lanes = slice(j * TILE, (j + 1) * TILE)
        t = x_ref[:, lanes].astype(jnp.float32)        # (bn, TILE)
        a = jnp.abs(t)
        nz = a > 0.0
        loga = jnp.where(nz, jnp.log(jnp.where(nz, a, 1.0)), jnp.inf)
        neg = jnp.where(nz, -loga, jnp.inf)
        mx = -jnp.min(neg, axis=-1, keepdims=True)
        mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
        mn = jnp.min(jnp.where(nz, loga, jnp.inf), axis=-1, keepdims=True)
        mn = jnp.where(jnp.isfinite(mn), mn, 0.0)
        mn = jnp.maximum(mn, mx - RANGE_CLAMP)
        step = jnp.maximum((mx - mn) / max(levels - 1, 1), 1e-12)

        tt = jnp.clip((loga - mn) / step, 0.0, levels - 1)
        k0 = jnp.floor(tt)
        lo = jnp.exp(mn + step * k0)
        hi = jnp.exp(mn + step * jnp.minimum(k0 + 1, levels - 1))
        k = jnp.where((a - lo) > (hi - a), jnp.minimum(k0 + 1, levels - 1),
                      k0)
        code = jnp.where(nz, k.astype(jnp.int32) + 1, 0)
        sign = (t < 0).astype(jnp.int32)
        packed = (sign << (n_bits - 1)) | code
        code_ref[:, lanes] = packed.astype(code_ref.dtype)
        mn_ref[:, j:j + 1] = mn
        step_ref[:, j:j + 1] = step


def _decode_kernel(code_ref, mn_ref, step_ref, o_ref, *, n_bits: int):
    sign_mask = 1 << (n_bits - 1)
    for j in range(code_ref.shape[1] // TILE):
        lanes = slice(j * TILE, (j + 1) * TILE)
        c = code_ref[:, lanes].astype(jnp.int32)
        sign = jnp.where((c & sign_mask) != 0, -1.0, 1.0)
        k = (c & (sign_mask - 1)).astype(jnp.float32)
        mag = jnp.exp(mn_ref[:, j:j + 1] + step_ref[:, j:j + 1] * (k - 1.0))
        val = jnp.where(k == 0, 0.0, sign * mag)
        o_ref[:, lanes] = val.astype(o_ref.dtype)


def _code_dtype(n_bits):
    return jnp.uint8 if n_bits <= 8 else jnp.uint16


@functools.partial(jax.jit, static_argnames=("n_bits", "bn", "interpret"))
def logfmt_encode(x: jax.Array, *, n_bits: int = 8, bn: int = 32,
                  interpret: bool = False):
    N, D = x.shape
    bn = min(bn, N)
    assert N % bn == 0 and D % TILE == 0, (N, D, bn)
    return pl.pallas_call(
        functools.partial(_encode_kernel, n_bits=n_bits),
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, D // TILE), lambda i: (i, 0)),
            pl.BlockSpec((bn, D // TILE), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((N, D), _code_dtype(n_bits)),
            jax.ShapeDtypeStruct((N, D // TILE), jnp.float32),
            jax.ShapeDtypeStruct((N, D // TILE), jnp.float32),
        ),
        interpret=interpret,
        name="logfmt_encode",
    )(x)


@functools.partial(jax.jit, static_argnames=("n_bits", "bn", "dtype",
                                             "interpret"))
def logfmt_decode(codes: jax.Array, mn: jax.Array, step: jax.Array, *,
                  n_bits: int = 8, bn: int = 32, dtype=jnp.float32,
                  interpret: bool = False):
    N, D = codes.shape
    bn = min(bn, N)
    assert N % bn == 0 and D % TILE == 0, (N, D, bn)
    return pl.pallas_call(
        functools.partial(_decode_kernel, n_bits=n_bits),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, D // TILE), lambda i: (i, 0)),
            pl.BlockSpec((bn, D // TILE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), dtype),
        interpret=interpret,
        name="logfmt_decode",
    )(codes, mn, step)
