"""Unified kernel dispatch: backend registry, shared tiling, one entry point.

Every Pallas kernel in ``repro.kernels`` registers itself here as a
:class:`KernelOp` with named *backends*:

  ``pallas``     the real ``pl.pallas_call`` kernel, compiled for TPU
  ``interpret``  the same kernel run through the Pallas interpreter
                 (CPU-exact semantics; the correctness path off-TPU)
  ``ref``        a pure-jnp oracle (tests, accuracy studies)

Callers never pick an implementation, never pass ``use_ref=`` or
``interpret=``: they call the op (``fp8_matmul(x, w)``) and the registry
resolves the backend from a single policy, in priority order:

  1. ``with kernels.use_backend("ref"):`` — thread-local override
  2. ``REPRO_KERNEL_BACKEND=pallas|interpret|ref`` environment variable
  3. platform auto-detect: TPU -> ``pallas``, CPU -> ``interpret``; any
     other platform raises rather than run the interpreter unnoticed

This replaces the old per-subpackage ``ops.py`` convention where every
wrapper grew its own ``use_ref``/``interpret`` kwargs and an
``interpret=True`` default that would have silently crippled TPU runs.

jit composition
---------------
The selected backend is *threaded into the kernels' jit boundary as a
static argument*: each backend impl is its own ``jax.jit`` entry (with
``interpret`` in ``static_argnames`` for the shared pallas/interpret
function), so each backend owns a distinct executable — dispatch is never
a traced-in global read inside one compiled function. For callers that
wrap kernel ops inside their *own* ``jax.jit``, the backend choice is
captured when that outer function traces; to keep ``use_backend`` honest
there too, entering/leaving the context drops jit caches whenever the
active backend actually changes, forcing outer jits to retrace onto the
new path (pass ``clear_caches=False`` to skip this when you know the op
is not embedded in an outer jit, e.g. tight test sweeps).

Shared tiling layer
-------------------
:func:`pad_to_multiple` is the one padding helper (replacing per-package
``_pad`` copies), and :class:`BlockTable` is a per-kernel block-size
table keyed by shape buckets (replacing ad-hoc heuristics like
``bc = 128 if C % 128 == 0 else 8``). See ``docs/kernel_backends.md``
for how to register a new kernel or backend.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
import inspect
import os
import threading
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

BACKENDS = ("pallas", "interpret", "ref")
ENV_VAR = "REPRO_KERNEL_BACKEND"

# Modules that register kernels at import time. Imported lazily the first
# time the registry is queried — the ops modules import this module, so an
# eager import here would cycle.
_KERNEL_MODULES = (
    "repro.kernels.fp8_gemm.ops",
    "repro.kernels.mla_attention.ops",
    "repro.kernels.moe_gemm.ops",
    "repro.kernels.logfmt.ops",
    "repro.kernels.paged_attention.ops",
    "repro.kernels.flash_attention.ops",
)

_REGISTRY: Dict[str, "KernelOp"] = {}
_local = threading.local()


# ---------------------------------------------------------------------------
# Shared padding / tiling layer
# ---------------------------------------------------------------------------


def pad_to_multiple(x: jax.Array, axis: int, mult: int, *,
                    value=0) -> jax.Array:
    """Pad ``x`` along ``axis`` up to the next multiple of ``mult``.

    The one padding helper for every kernel wrapper (pad inputs up to the
    block grid, slice the output back down). ``value`` fills the padded
    region (e.g. ``-1`` for position buffers whose sentinel is "empty").
    """
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@dataclasses.dataclass(frozen=True)
class BlockTable:
    """Per-kernel block sizes keyed by shape buckets.

    ``table`` maps a bucket floor (int) to a dict of named block sizes.
    :meth:`lookup` selects the entry with the largest floor ``<= n``; an
    ``n`` below every floor gets the smallest entry. Kernel wrappers look
    up each tiled dimension and pad it to the chosen block with
    :func:`pad_to_multiple`, so the table is the single place block-size
    policy lives (and the single place to retune it per platform).

    >>> t = BlockTable({1: dict(bm=8), 128: dict(bm=128)})
    >>> t.block(40, "bm"), t.block(512, "bm")
    (8, 128)
    """

    table: Mapping[int, Mapping[str, int]]

    def __post_init__(self):
        if not self.table:
            raise ValueError("BlockTable needs at least one bucket")
        floors = tuple(sorted(self.table))
        if any(f < 1 for f in floors):
            raise ValueError(f"bucket floors must be >= 1, got {floors}")
        object.__setattr__(self, "_floors", floors)

    def lookup(self, n: int) -> Dict[str, int]:
        """Block sizes for a dimension of size ``n``."""
        chosen = self._floors[0]
        for f in self._floors:
            if f > n:
                break
            chosen = f
        return dict(self.table[chosen])

    def block(self, n: int, name: str) -> int:
        return self.lookup(n)[name]


# ---------------------------------------------------------------------------
# Backend selection policy
# ---------------------------------------------------------------------------


def _validate_backend(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {name!r}; expected one of {BACKENDS}")
    return name


@functools.lru_cache(maxsize=None)
def _platform_default() -> str:
    platform = jax.default_backend()
    if platform == "tpu":
        return "pallas"
    if platform == "cpu":
        return "interpret"
    raise RuntimeError(
        f"no default kernel backend on platform {platform!r}: the Pallas "
        "kernels compile for TPU and run through the interpreter on CPU "
        f"only; choose one with {ENV_VAR} or kernels.use_backend(...)")


def active_backend() -> str:
    """The backend ops dispatch to right now (override > env > platform)."""
    override = getattr(_local, "backend", None)
    if override is not None:
        return override
    env = os.environ.get(ENV_VAR, "").strip().lower()
    if env:
        return _validate_backend(env)
    return _platform_default()


@contextlib.contextmanager
def use_backend(name: str, *, clear_caches: bool = True):
    """Force every registry-dispatched kernel onto ``name`` in this block.

    Thread-local, reentrant. When the active backend actually changes and
    ``clear_caches`` is True (default), jit caches are dropped on entry
    and exit so functions jitted *around* kernel ops retrace onto the new
    backend instead of replaying the path captured at their first trace.
    """
    _validate_backend(name)
    prev = getattr(_local, "backend", None)
    changed = name != active_backend()
    _local.backend = name
    if changed and clear_caches:
        jax.clear_caches()
    try:
        yield
    finally:
        if prev is None:
            del _local.backend
        else:
            _local.backend = prev
        if changed and clear_caches:
            jax.clear_caches()


# ---------------------------------------------------------------------------
# Registration + dispatch
# ---------------------------------------------------------------------------


def _wants_interpret(fn: Callable) -> bool:
    """Does the impl declare an ``interpret`` parameter for us to thread?
    Inspected once at registration (jax.jit preserves signatures)."""
    try:
        return "interpret" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


class KernelOp:
    """One logical kernel op: the single public entry point for all of its
    backends. Create via :func:`kernel`; attach impls with
    :meth:`backend`; call like a function.

    The usual shared form registers one function under both ``"pallas"``
    and ``"interpret"``: any pallas/interpret impl that declares an
    ``interpret`` parameter gets ``interpret=True/False`` threaded in as a
    (jit-static) keyword argument, so the real kernel and its interpreter
    run share one implementation — and a standalone impl that requires the
    flag can never be dispatched without it.
    """

    def __init__(self, name: str, *, blocks: Optional[BlockTable] = None):
        self.name = name
        self.blocks = blocks
        self._impls: Dict[str, Callable] = {}
        self._threads_interpret: Dict[str, bool] = {}

    def backend(self, *names: str) -> Callable:
        """Decorator: register the wrapped function for each backend name."""
        if not names:
            raise ValueError("backend() needs at least one backend name")
        for n in names:
            _validate_backend(n)
            if n in self._impls:
                raise ValueError(
                    f"kernel {self.name!r}: backend {n!r} already registered")

        def deco(fn: Callable) -> Callable:
            wants = _wants_interpret(fn)
            for n in names:
                self._impls[n] = fn
                self._threads_interpret[n] = (
                    wants and n in ("pallas", "interpret"))
            return fn

        return deco

    def backends(self) -> Tuple[str, ...]:
        return tuple(sorted(self._impls))

    def __call__(self, *args, **kwargs):
        backend = active_backend()
        fn = self._impls.get(backend)
        if fn is None:
            raise NotImplementedError(
                f"kernel {self.name!r} has no {backend!r} backend "
                f"(registered: {self.backends()}); pick one with "
                f"kernels.use_backend(...) or {ENV_VAR}")
        if self._threads_interpret[backend]:
            kwargs["interpret"] = backend == "interpret"
        return fn(*args, **kwargs)

    def __repr__(self) -> str:
        return f"KernelOp({self.name!r}, backends={self.backends()})"


def kernel(name: str, *, blocks: Optional[BlockTable] = None) -> KernelOp:
    """Create and register the entry point for a logical kernel op.

    Usage (in a subpackage's ``ops.py``)::

        fp8_matmul = registry.kernel("fp8_gemm", blocks=BLOCKS)

        @fp8_matmul.backend("ref")
        @jax.jit
        def _ref(x, w): ...

        @fp8_matmul.backend("pallas", "interpret")
        @functools.partial(jax.jit, static_argnames=("interpret",))
        def _kernel(x, w, *, interpret): ...
    """
    if name in _REGISTRY:
        raise ValueError(f"kernel {name!r} already registered")
    op = KernelOp(name, blocks=blocks)
    _REGISTRY[name] = op
    return op


def _ensure_populated() -> None:
    for mod in _KERNEL_MODULES:
        importlib.import_module(mod)


def get(name: str) -> KernelOp:
    """Fetch a registered kernel op by name (imports kernel modules)."""
    _ensure_populated()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no kernel {name!r}; registered: {names()}") from None


def names() -> Tuple[str, ...]:
    """All registered kernel names (imports kernel modules)."""
    _ensure_populated()
    return tuple(sorted(_REGISTRY))
