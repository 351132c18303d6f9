"""Registry entry point for the flash bucketed-prefill attention kernel.

``flash_prefill(q, k, v, q_pos, k_pos, causal=..., scale=...)``
dispatches through ``repro.kernels.registry``: ``pallas``/``interpret``
run the block-tiled online-softmax recurrence (block sizes from the
shape-bucketed table below; ragged query/key axes are padded to the block
grid, pad keys with ``k_pos = -1`` so they are masked); ``ref`` is the
full-matrix jnp oracle. Pad rows (no valid key) emit zeros on every
backend.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import registry
from repro.kernels.flash_attention.flash_attention import \
    flash_prefill_kernel
from repro.kernels.flash_attention.ref import flash_prefill_ref

# rows per query/key block: buckets under 128 take the whole bucket in one
# block; larger ones tile at 128 (MXU-aligned, ~bq*bk fp32 scores in
# VMEM). The key-position lane row needs bk % 128 == 0 or bk == T.
BLOCKS = registry.BlockTable({
    1: dict(bq=128, bk=128),
})

flash_prefill = registry.kernel("flash_prefill", blocks=BLOCKS)


@flash_prefill.backend("ref")
@functools.partial(jax.jit, static_argnames=("causal", "scale"))
def _flash_prefill_ref(q, k, v, q_pos, k_pos, *, causal: bool,
                       scale: float):
    return flash_prefill_ref(q, k, v, q_pos, k_pos, causal=causal,
                             scale=scale)


@flash_prefill.backend("pallas", "interpret")
@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "interpret"))
def _flash_prefill_kernel(q, k, v, q_pos, k_pos, *, causal: bool,
                          scale: float, interpret: bool):
    S, T = q.shape[1], k.shape[1]
    bq = min(BLOCKS.block(S, "bq"), S)
    bk = min(BLOCKS.block(T, "bk"), T)
    pad = registry.pad_to_multiple
    out = flash_prefill_kernel(
        pad(q, 1, bq), pad(k, 1, bk), pad(v, 1, bk), pad(q_pos, 1, bq),
        pad(k_pos, 1, bk, value=-1), causal=causal, scale=scale, bq=bq,
        bk=bk, interpret=interpret)
    return out[:, :S]
