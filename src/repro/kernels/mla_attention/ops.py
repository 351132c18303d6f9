"""Registry entry point for the MLA absorbed-decode flash kernel.

``mla_decode(q_abs, q_rope, ckv, kr, pos, qpos, scale=...)`` dispatches
through ``repro.kernels.registry``: ``pallas``/``interpret`` stream the
latent cache blockwise with an online softmax (block length from the
shape-bucketed table below, cache padded with ``pos = -1`` so padding is
masked); ``ref`` is the full-softmax jnp oracle.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import registry
from repro.kernels.mla_attention.mla_attention import mla_decode_kernel
from repro.kernels.mla_attention.ref import mla_decode_ref

# cache rows streamed per grid step: caches under 128 rows take the whole
# cache in one block, longer ones 128/256 rows (~0.6 MB VMEM per step, see
# mla_attention.py) — the ``pos`` lane row needs bt % 128 == 0 or bt == T
BLOCKS = registry.BlockTable({
    1: dict(bt=128),
    512: dict(bt=256),
})

mla_decode = registry.kernel("mla_decode", blocks=BLOCKS)


@mla_decode.backend("ref")
@functools.partial(jax.jit, static_argnames=("scale",))
def _mla_decode_ref(q_abs, q_rope, ckv, kr, pos, qpos, *, scale: float):
    return mla_decode_ref(q_abs, q_rope, ckv, kr, pos, qpos, scale=scale)


@mla_decode.backend("pallas", "interpret")
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _mla_decode_kernel(q_abs, q_rope, ckv, kr, pos, qpos, *, scale: float,
                       interpret: bool):
    T = ckv.shape[1]
    bt = min(BLOCKS.block(T, "bt"), T)
    ckv = registry.pad_to_multiple(ckv, 1, bt)
    kr = registry.pad_to_multiple(kr, 1, bt)
    pos = registry.pad_to_multiple(pos, 1, bt, value=-1)  # padding = empty
    return mla_decode_kernel(q_abs, q_rope, ckv, kr, pos, qpos,
                             scale=scale, bt=bt, interpret=interpret)
