"""Pallas TPU kernels for the paper's compute hot-spots, behind one
dispatch surface (``repro.kernels.registry``).

Subpackages — each has ``<name>.py`` (the ``pl.pallas_call`` + BlockSpec
VMEM tiling) plus ``ops.py`` (the registered entry point) and ``ref.py``
(pure-jnp oracle):

  fp8_gemm/       fine-grained-scaled FP8 GEMM (DeepGEMM -> TPU, T4)
  mla_attention/  MLA absorbed-decode flash kernel over the latent cache (T1)
  logfmt/         LogFMT-nBit encode/decode (T5)
  moe_gemm/       grouped expert GEMM (T2)

Kernel backends
---------------
Every op registers named backends with the registry — ``pallas`` (the
real TPU kernel), ``interpret`` (same kernel through the Pallas
interpreter; the CPU correctness path), and ``ref`` (jnp oracle). Callers
invoke the op with no implementation kwargs; the backend is resolved per
call from one policy:

  1. ``with kernels.use_backend("ref"):``   thread-local override
  2. ``REPRO_KERNEL_BACKEND`` env var       process-level default
  3. platform auto-detect                   TPU -> pallas, CPU -> interpret
                                            (any other platform raises)

The selection is threaded into each backend's ``jax.jit`` boundary as a
static argument, and ``use_backend`` drops jit caches when the backend
actually changes so outer-jitted callers retrace onto the new path. To
add a kernel or a backend, see ``docs/kernel_backends.md`` and the
``registry.kernel`` docstring.

Kernels target TPU (MXU-aligned 128 tiles, fp32 accumulation); block
sizes come from per-kernel shape-bucketed ``BlockTable``s in each
``ops.py``.
"""
from repro.kernels import registry
from repro.kernels.registry import (
    BACKENDS,
    BlockTable,
    active_backend,
    get,
    kernel,
    names,
    pad_to_multiple,
    use_backend,
)

__all__ = [
    "BACKENDS",
    "BlockTable",
    "active_backend",
    "get",
    "kernel",
    "names",
    "pad_to_multiple",
    "registry",
    "use_backend",
]
