"""Registry entry points for the LogFMT-nBit codec.

``encode(x, n_bits=...)`` / ``decode(codes, mn, step, n_bits=...,
dtype=...)`` reshape any ``(..., D)`` activation to 2D and dispatch
through ``repro.kernels.registry``: ``pallas``/``interpret`` run the VPU
codec kernels with block shapes from the shape-bucketed table below;
``ref`` is the pure-jnp codec from ``repro.core.logfmt``. The feature dim
must be a multiple of the 128-lane tile (fundamental to the wire format —
pad upstream); rows are padded to the block grid here and sliced back
(padded rows encode/decode zeros, so the sideband stays exact). A block
is ``bn`` whole feature rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.logfmt.logfmt import TILE, logfmt_decode, logfmt_encode
from repro.kernels.logfmt.ref import logfmt_decode_ref, logfmt_encode_ref

# rows per block, bucketed by row count: 32 rows is the 8-bit code tile
# height on TPU; one (32, 7168) fp32 block is ~0.9 MB of VMEM
BLOCKS = registry.BlockTable({
    1: dict(bn=32),
})

encode = registry.kernel("logfmt_encode", blocks=BLOCKS)
decode = registry.kernel("logfmt_decode", blocks=BLOCKS)


def _as2d(x: jax.Array) -> jax.Array:
    assert x.shape[-1] % TILE == 0, (
        f"LogFMT feature dim must be a multiple of {TILE}, got {x.shape}")
    return x.reshape(-1, x.shape[-1])


@encode.backend("ref")
@functools.partial(jax.jit, static_argnames=("n_bits",))
def _encode_ref(x: jax.Array, *, n_bits: int = 8):
    shape = x.shape
    codes, mn, step = logfmt_encode_ref(_as2d(x), n_bits)
    return (codes.reshape(shape), mn.reshape(shape[:-1] + (-1,)),
            step.reshape(shape[:-1] + (-1,)))


@encode.backend("pallas", "interpret")
@functools.partial(jax.jit, static_argnames=("n_bits", "interpret"))
def _encode_kernel(x: jax.Array, *, n_bits: int = 8, interpret: bool):
    shape = x.shape
    x2 = _as2d(x)
    N = x2.shape[0]
    bn = BLOCKS.block(N, "bn")
    codes, mn, step = logfmt_encode(registry.pad_to_multiple(x2, 0, bn),
                                    n_bits=n_bits, bn=bn,
                                    interpret=interpret)
    codes, mn, step = codes[:N], mn[:N], step[:N]
    return (codes.reshape(shape), mn.reshape(shape[:-1] + (-1,)),
            step.reshape(shape[:-1] + (-1,)))


@decode.backend("ref")
@functools.partial(jax.jit, static_argnames=("n_bits", "dtype"))
def _decode_ref(codes: jax.Array, mn: jax.Array, step: jax.Array, *,
                n_bits: int = 8, dtype=jnp.bfloat16):
    shape = codes.shape
    y = logfmt_decode_ref(codes.reshape(-1, shape[-1]),
                          mn.reshape(-1, mn.shape[-1]),
                          step.reshape(-1, step.shape[-1]),
                          n_bits, dtype=dtype)
    return y.reshape(shape)


@decode.backend("pallas", "interpret")
@functools.partial(jax.jit, static_argnames=("n_bits", "dtype", "interpret"))
def _decode_kernel(codes: jax.Array, mn: jax.Array, step: jax.Array, *,
                   n_bits: int = 8, dtype=jnp.bfloat16, interpret: bool):
    shape = codes.shape
    c2 = _as2d(codes)
    N = c2.shape[0]
    bn = BLOCKS.block(N, "bn")
    pad = registry.pad_to_multiple
    y = logfmt_decode(pad(c2, 0, bn), pad(mn.reshape(N, -1), 0, bn),
                      pad(step.reshape(N, -1), 0, bn), n_bits=n_bits, bn=bn,
                      dtype=dtype, interpret=interpret)
    return y[:N].reshape(shape)
