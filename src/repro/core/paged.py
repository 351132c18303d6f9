"""Paged decode-cache core: block pool, page tables, FP8 page storage.

The paper's serving constraint is memory *capacity*: MLA shrinks the
per-token KV footprint to the latent ``(c_kv, k_rope)`` pair (Table 1) and
§2.1.2 pairs it with low-precision storage so HBM stretches further. The
dense engine still reserved a full ``max_len`` ring buffer per slot, so
slot count was bounded by worst-case context. This module provides the
building blocks for the paged alternative:

* **Pool layout** — per attention segment, one shared pool of fixed-size
  token blocks ("pages"): value leaves of shape ``(layers, pool_pages+1,
  page, ...)``. The final page index (:func:`trash_page`) is a scratch
  page that absorbs writes from freed/unmapped slots so a recycled page
  can never be corrupted by a stale writer.
* **Page table** — per decode slot, ``(B, max_len // page)`` int32 of
  physical page ids (``trash`` where unmapped). Token position ``p`` lives
  at page ``table[b, p // page]``, offset ``p % page``. Pages are written
  strictly in position order and never ring-wrap, so *validity needs no
  stored ``pos`` array*: slot ``b``'s cache row at logical position ``l``
  is valid iff ``l <= qpos_b`` — everything at or below the current decode
  position has been written by this slot, everything above is stale or
  unwritten and is masked out.
* **FP8 storage** — value leaves quantize per *token vector* (one fp32
  scale per token per layer per leaf, the finest-grained analogue of the
  paper's 1x128 activation tiles: the whole latent/KV vector of one token
  is one tile). ``<leaf>_scale`` leaves have shape ``(layers, P+1, page)``.
  Recurrent (SSM / RG-LRU) state never pages and stays full precision.

``storage`` is ``"fp8"`` (E4M3 values + scales) or ``"bf16"`` (the model's
native cache dtype, scale-free — named for the production configs; smoke
configs store float32). At native storage the paged decode path is
bitwise-identical to the dense ring cache (same values, same mask, same
einsums), which the parity tests pin.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

E4M3 = jnp.float8_e4m3fn
E4M3_MAX = 448.0

STORAGES = ("fp8", "bf16")


def validate_storage(storage: str) -> str:
    if storage not in STORAGES:
        raise ValueError(
            f"unknown page storage {storage!r}; expected one of {STORAGES}")
    return storage


def trash_page(pool_pages: int) -> int:
    """Index of the scratch page (pools allocate ``pool_pages + 1``)."""
    return pool_pages


def pages_for(tokens: int, page_size: int) -> int:
    """Host-side page budget for a request that will hold ``tokens``."""
    return -(-tokens // page_size)


def pool_model_axes(leaf_name: str, ndim: int):
    """Model-axis shardability of one pool leaf, declared by name (the
    paged analogue of ``Model.paged_aux_axes`` / sharding's name-driven
    ``_CACHE_AXES``): GQA K/V pools ``(layers, P+1, page, KV*hd)`` can
    shard their flat head axis over the model axis (whole heads per
    device when KV divides by the axis size); per-token scale
    sidebands ``(layers, P+1, page)`` and the MLA latent/rope pools (no
    head axis — the latent is shared by every head, which is the whole
    point of MLA) replicate. The *page* axis is never sharded: admission
    scatters and decode gathers index physical page ids, and splitting
    those across devices would turn every table lookup into a collective.
    """
    if leaf_name in ("k", "v") and ndim == 4:
        return 3
    return None


def e4m3_decode(q: jax.Array) -> jax.Array:
    """E4M3 -> fp32 via a 256-entry decode table (bit-exact).

    XLA's CPU backend emulates the ``f8E4M3FN -> f32`` convert per element
    (~5x slower than a byte gather at decode-cache sizes, and the dominant
    cost of the paged-fp8 hot path). Reading the value through a table
    indexed by the raw byte is bit-identical to ``astype(float32)`` for
    every non-NaN code (NaN codes decode to NaN either way) — the kernel
    property sweep pins all 256 codes. The table itself is built from a
    constant ``iota`` so XLA folds it at compile time.
    """
    lut = jax.lax.bitcast_convert_type(
        jnp.arange(256, dtype=jnp.uint8), E4M3).astype(jnp.float32)
    u8 = (q if q.dtype == jnp.uint8
          else jax.lax.bitcast_convert_type(q, jnp.uint8))
    return lut[u8.astype(jnp.int32)]


def _to_store(pool: jax.Array, vals: jax.Array) -> jax.Array:
    """Coerce token values to the pool's storage dtype.

    FP8 pools store raw E4M3 *bytes* (uint8): XLA CPU legalizes
    dynamic-update-slice/scan over f8 operands by round-tripping the whole
    operand through f16 (per-element emulated — it dominated the paged-fp8
    decode step), while u8 slices/scatters are native moves. Quantized
    E4M3 values are bitcast (not value-converted) into the byte pool.
    Callers write the matching per-token scale sideband (from
    :func:`quantize_vecs`) into the scale pool alongside — values never
    travel without their scales; the fallback ``astype`` here only
    normalizes already-scaled values handed over in E4M3-compatible form.
    """
    if pool.dtype == jnp.uint8 and vals.dtype != jnp.uint8:
        q = vals if vals.dtype == E4M3 else vals.astype(E4M3)
        return jax.lax.bitcast_convert_type(q, jnp.uint8)
    return vals.astype(pool.dtype)


def quantize_vecs(x: jax.Array, vec_ndim: int = 1
                  ) -> Tuple[jax.Array, jax.Array]:
    """Per-token-vector FP8 quantization.

    The trailing ``vec_ndim`` axes form one token's vector (1 for the MLA
    latent / rope rows, 2 for a GQA ``(KV, hd)`` entry); everything before
    them indexes tokens. Returns ``(q, scale)`` with ``q`` in E4M3 of x's
    shape and ``scale`` fp32 of the token shape.
    """
    xf = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - vec_ndim, x.ndim))
    amax = jnp.max(jnp.abs(xf), axis=axes)
    scale = jnp.maximum(amax, 1e-12) / E4M3_MAX
    q = (xf / scale.reshape(scale.shape + (1,) * vec_ndim)).astype(E4M3)
    return q, scale


def dequantize_vecs(q: jax.Array, scale: jax.Array,
                    vec_ndim: int = 1) -> jax.Array:
    """Inverse of :func:`quantize_vecs` (fp32 out)."""
    qf = (e4m3_decode(q) if q.dtype in (E4M3, jnp.uint8)
          else q.astype(jnp.float32))
    return qf * scale.reshape(scale.shape + (1,) * vec_ndim)


# ---------------------------------------------------------------------------
# Pool read/write primitives (operate on one layer's pool slice)
# ---------------------------------------------------------------------------


def page_write(pool: jax.Array, table: jax.Array, positions: jax.Array,
               vals: jax.Array) -> jax.Array:
    """Write one token per slot into the pool.

    pool: ``(P+1, page, ...)``; table: ``(B, pages_per_slot)`` physical
    ids; positions: ``(B,)`` the token's position; vals: ``(B, ...)``.
    Unmapped/freed slots write to the trash page (their table rows point
    there), so concurrent owners of recycled pages are never clobbered.
    """
    page = pool.shape[1]
    lp = jnp.clip(positions // page, 0, table.shape[1] - 1)
    off = positions % page
    phys = jnp.take_along_axis(table, lp[:, None], axis=1)[:, 0]
    return pool.at[phys, off].set(_to_store(pool, vals))


def page_write_chunk(pool: jax.Array, table: jax.Array, start: jax.Array,
                     vals: jax.Array) -> jax.Array:
    """Write a contiguous, page-aligned run of tokens per slot.

    pool: ``(P+1, page, ...)``; table: ``(B, pages_per_slot)``; start:
    ``(B,)`` page-aligned first position of the run; vals: ``(B, C, ...)``
    with ``C`` a multiple of the page size. The chunked-prefill analogue of
    :func:`page_write`: one scatter covers ``C // page`` whole pages per
    slot. Rows past a slot's reserved pages land in the trash page via the
    table's padding, same as the single-token path.
    """
    page = pool.shape[1]
    B, C = vals.shape[:2]
    n = C // page
    lp = start[:, None] // page + jnp.arange(n)[None, :]        # (B, n)
    lp = jnp.clip(lp, 0, table.shape[1] - 1)
    phys = jnp.take_along_axis(table, lp, axis=1)               # (B, n)
    v = vals.reshape((B, n, page) + vals.shape[2:])
    return pool.at[phys].set(_to_store(pool, v))


def table_gather(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Gather each slot's pages into a dense view.

    pool: ``(P+1, page, ...)``; table ``(B, pp)`` -> ``(B, pp*page, ...)``
    in the pool dtype. Logical position ``l`` of row ``b`` lands at index
    ``l`` of the result; rows past the slot's reserved pages come from the
    trash page and must be masked by the caller (``l <= qpos``).
    """
    g = pool[table]                                   # (B, pp, page, ...)
    B, pp, page = g.shape[:3]
    return g.reshape((B, pp * page) + g.shape[3:])


def gather_dequant(pool: jax.Array, scale_pool: jax.Array,
                   table: jax.Array) -> jax.Array:
    """Fused ``table_gather`` + ``dequantize_vecs`` (fp32 out).

    Bit-identical to the unfused pair, but the E4M3 pool is bitcast to
    bytes *before* the gather so the page gather moves raw uint8 and the
    convert is a single table lookup (:func:`e4m3_decode`) — the XLA-path
    fp8 decode hot-path read. Non-fp8 pools gather + upcast directly.
    """
    if pool.dtype in (E4M3, jnp.uint8):
        u8 = (pool if pool.dtype == jnp.uint8
              else jax.lax.bitcast_convert_type(pool, jnp.uint8))
        vals = e4m3_decode(table_gather(u8, table))
    else:
        vals = table_gather(pool, table).astype(jnp.float32)
    return vals * table_gather(scale_pool, table)[..., None]


# ---------------------------------------------------------------------------
# Prefill -> pages (quantize a bucket-shaped prefill cache into page data)
# ---------------------------------------------------------------------------


def entries_to_pages(leaf: jax.Array, page_size: int, storage: str,
                     store_dtype) -> Dict[str, jax.Array]:
    """Reshape a batch-1 prefill cache leaf into quantized page data.

    leaf: ``(n, 1, T, width)`` with ``T`` the (bucket) prompt capacity laid
    out position-identically (no wrap — guaranteed for ``T >= length``).
    Returns ``{"q": (n, T//page, page, width)}`` plus ``{"scale": ...}`` for
    fp8 storage. Pad rows (already zeroed by prefill assembly) quantize to
    zero pages, keeping recycled-pool contents deterministic.
    """
    n, b1, T = leaf.shape[:3]
    assert b1 == 1, leaf.shape
    if T % page_size:
        raise ValueError(f"prefill capacity {T} not a multiple of the "
                         f"page size {page_size}")
    paged = leaf.reshape((n, T // page_size, page_size) + leaf.shape[3:])
    if storage == "fp8":
        q, s = quantize_vecs(paged)
        return {"q": q, "scale": s}
    return {"q": paged.astype(store_dtype)}


def scatter_pages(pool: jax.Array, pages: jax.Array,
                  ids: jax.Array) -> jax.Array:
    """Write page data into the pool at physical ids.

    pool: ``(n, P+1, page, ...)``; pages: ``(n, nP, page, ...)``; ids:
    ``(nP,)`` physical page ids (trash-padded entries land in the scratch
    page). Layer-stacked: the scatter covers all ``n`` layers at once.
    """
    return pool.at[:, ids].set(_to_store(pool, pages))


# ---------------------------------------------------------------------------
# Host-side page accounting: refcounts + copy-on-write prefix index
# ---------------------------------------------------------------------------


def prefix_keys(prompt: Sequence[int], page_size: int,
                n_pages: int) -> List[bytes]:
    """Exact-content index keys for a prompt's first ``n_pages`` full pages.

    Key ``j`` is the byte image of ``prompt[:(j+1)*page_size]`` — the whole
    prefix, not just the page's own tokens, so a hit at page ``j`` implies
    every earlier page matched too (no hash-collision hazard: keys compare
    by content).
    """
    arr = np.asarray(prompt, dtype=np.int32)
    return [arr[:(j + 1) * page_size].tobytes() for j in range(n_pages)]


class PrefixPageAllocator:
    """Refcounted physical-page allocator with a prefix → page index.

    Pure host/numpy bookkeeping over a pool of ``pool_pages`` physical ids
    (the trash page is outside the pool and never allocated). Pages shared
    between slots are immutable by construction: only *full* prompt pages
    are ever indexed, decode writes start past the prompt, and chunked
    prefill skips chunks whose pages were claimed from the index — so no
    copy is ever needed and "copy-on-write fork" degenerates to "allocate
    fresh pages from the divergence point".

    Free pages live in two pools: ``plain`` (unindexed — recycled decode
    and divergence pages) and ``cached`` (refcount-0 pages still holding an
    indexed prefix, kept warm LRU so a later request with the same prefix
    revives them). Allocation drains plain first, then evicts the oldest
    cached page and purges its index entry.
    """

    def __init__(self, pool_pages: int):
        self.pool_pages = pool_pages
        self.refs = np.zeros((pool_pages,), np.int32)
        self._free_plain: List[int] = list(range(pool_pages))
        self._free_cached: "OrderedDict[int, bytes]" = OrderedDict()
        self._index: Dict[bytes, int] = {}
        self._page_key: Dict[int, bytes] = {}
        self.prefix_hits = 0
        self.prefix_lookups = 0

    def free_pages(self) -> int:
        return len(self._free_plain) + len(self._free_cached)

    def plain_free(self) -> int:
        """Free pages with no cached prefix content."""
        return len(self._free_plain)

    def cached_free(self) -> int:
        """Refcount-0 pages parked in the warm prefix cache (reclaimable,
        and the harvest pool for host-tier prefix spills)."""
        return len(self._free_cached)

    def indexed_pages(self) -> int:
        return len(self._index)

    def is_indexed(self, pid: int) -> bool:
        """Whether ``pid`` currently backs a prefix-index entry."""
        return pid in self._page_key

    def lookup(self, key: bytes) -> Optional[int]:
        """Physical page currently indexed under ``key`` (None = miss)."""
        return self._index.get(key)

    def _hit_run(self, keys: Sequence[bytes], granularity: int) -> List[int]:
        hits: List[int] = []
        for key in keys:
            pid = self._index.get(key)
            if pid is None:
                break
            hits.append(pid)
        # chunked prefill can only skip whole chunks, so the shared run is
        # rounded down to a chunk-multiple of pages
        return hits[:len(hits) // granularity * granularity]

    def _take_free(self) -> int:
        if self._free_plain:
            pid = self._free_plain.pop()
        else:
            pid, key = self._free_cached.popitem(last=False)  # oldest
            del self._index[key]
            del self._page_key[pid]
        self.refs[pid] = 1
        return pid

    def can_admit(self, keys: Sequence[bytes], total_pages: int,
                  granularity: int = 1) -> bool:
        """Pure capacity probe for ``admit`` — no counters, no mutation."""
        hits = self._hit_run(keys, granularity)
        revived = sum(1 for pid in hits if self.refs[pid] == 0)
        return total_pages - len(hits) <= self.free_pages() - revived

    def admit(self, keys: Sequence[bytes], total_pages: int,
              granularity: int = 1) -> Tuple[List[int], List[int]]:
        """Atomically claim the longest indexed run of ``keys`` and allocate
        fresh pages for the remainder of ``total_pages``.

        Returns ``(hit_ids, fresh_ids)``; raises ``RuntimeError`` without
        mutating any state when capacity is short. Keys must be contiguous
        from page 0 (``prefix_keys`` order) — the run stops at the first
        miss so a shared run is always a prefix of the page table row.
        """
        hits = self._hit_run(keys, granularity)
        n_fresh = total_pages - len(hits)
        # hit pages currently parked in the cached pool are about to be
        # revived, so they can't also satisfy the fresh allocation
        revived = sum(1 for pid in hits if self.refs[pid] == 0)
        if n_fresh > self.free_pages() - revived:
            raise RuntimeError("no free pages")
        self.prefix_lookups += len(keys)
        self.prefix_hits += len(hits)
        for pid in hits:
            if self.refs[pid] == 0:
                del self._free_cached[pid]
            self.refs[pid] += 1
        fresh = [self._take_free() for _ in range(n_fresh)]
        return hits, fresh

    def alloc(self, n: int) -> List[int]:
        """Allocate ``n`` fresh (refcount-1, unindexed) pages."""
        if n > self.free_pages():
            raise RuntimeError("no free pages")
        return [self._take_free() for _ in range(n)]

    def register(self, key: bytes, pid: int) -> bool:
        """Index a live page's content under ``key`` (first writer wins)."""
        if key in self._index:
            return False
        self._index[key] = pid
        self._page_key[pid] = key
        return True

    def release(self, ids: Sequence[int]) -> None:
        """Drop one reference per id; refcount-0 pages return to the free
        pools (cached if indexed, plain otherwise)."""
        for pid in ids:
            self.refs[pid] -= 1
            assert self.refs[pid] >= 0, f"page {pid} over-released"
            if self.refs[pid] == 0:
                key = self._page_key.get(pid)
                if key is not None:
                    self._free_cached[pid] = key
                    self._free_cached.move_to_end(pid)
                else:
                    self._free_plain.append(pid)

    def harvest(self, n: int) -> List[Tuple[int, bytes]]:
        """Pin up to ``n`` of the coldest warm-cached pages for spilling.

        Pops refcount-0 indexed pages in LRU order, purges their index
        entries, and pins each ref to 1 so a concurrent ``admit`` can
        neither revive nor recycle a page while its bytes are in flight to
        the host tier. Returns ``[(pid, key), ...]``; the caller must
        ``release`` the ids once the host copy is durable (or on abort),
        which sends them to the *plain* free pool.
        """
        out: List[Tuple[int, bytes]] = []
        while self._free_cached and len(out) < n:
            pid, key = self._free_cached.popitem(last=False)  # oldest
            del self._index[key]
            del self._page_key[pid]
            self.refs[pid] = 1
            out.append((pid, key))
        return out
