"""Host KV page tier: the paged device pool as a cache over host memory.

:class:`TieredServeEngine` is a paged ``ServeEngine`` behind which a
:class:`HostPageTier` of ``host_tier_pages`` pages holds what the device
pool cannot: a suspended slot's whole page set (spilled when a
higher-priority arrival or the rotation quantum preempts it, fetched back
ahead of its resume) and cold refcount-0 prefix pages (harvested when the
plain free pool runs dry, fetched back by a chunked admission that hits
them). The base engine knows nothing of it: the subclass masks spilling
slots out of decode, holds prefilling slots whose prefix pages are
inbound, and adds its phases around the base tick.

Every byte that crosses the boundary rides the same explicit host hop
the §4.5 PCIe disagg handoff uses — a staged ``device_get``/``device_put``
*between* engine ticks, never inside a jitted trace. The two staged
helpers below are the **only** sanctioned crossing points (repro-lint
R1-host-sync enforces this for the tier: a raw ``jax.device_get``/
``jax.device_put`` anywhere else in this module is a lint error), so
transfer volume stays auditable: every call site is either one of these
helpers or carries a reviewed waiver.

Transfers are modeled on the engine's tick clock by :class:`TransferClock`,
not measured: each in-flight :class:`TierTransfer` counts down an ETA
(stretched by an injected ``pcie_slow`` factor), a completion attempt can
be failed by ``pcie_drop`` (bounded retry with exponential backoff), and
a transfer that outlives ``timeout_ticks`` escalates to a hard failure —
the engine's degradation ladder (resume-in-place for spills, continuation
re-queue for fetches) takes over from there.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.configs.base import ModelConfig
from repro.serve.engine import STARVATION_LIMIT, ServeEngine, _splice


def staged_get(tree: Any) -> Any:
    """Stage a device pytree to host numpy — the §4.5 PCIe/DMA hop.

    Called between ticks with already-computed arrays (a gathered page
    payload), so the sync is the transfer itself, not a hidden stall of
    the decode dispatch pipeline.
    """
    # repro-lint: disable=R1-host-sync -- the staged-transfer helper: the
    # documented tier/disagg host hop, one audited crossing point
    return jax.device_get(tree)


def staged_put(tree: Any) -> Any:
    """Stage a host pytree onto the default device(s).

    The inverse hop: fetched page bytes re-enter device memory here and
    only here; the jitted scatter that installs them into the pool takes
    these arrays as ordinary operands.
    """
    # repro-lint: disable=R1-host-sync -- the staged-transfer helper: the
    # documented tier/disagg host hop, one audited crossing point
    return jax.device_put(tree)


@dataclasses.dataclass
class TierConfig:
    """Knobs for the tier's transfer model and scheduler policy."""
    xfer_ticks: int = 1        # base ticks per device<->host transfer
    max_retries: int = 3       # completion attempts after the first
    timeout_ticks: int = 32    # hard escalation: transfer age limit
    quantum: int = 8           # decode ticks a resident runs before it can
                               # be rotated out for a waiter
    harvest_batch: int = 4     # warm-LRU prefix pages spilled per sweep


class NullFaultHook:
    """Fault hook that never fires (the no-chaos default)."""

    def on_tick(self) -> None:
        pass

    def drop(self) -> bool:
        return False

    def slow(self) -> float:
        return 1.0

    def full(self) -> bool:
        return False


# transfer kinds
SPILL = "spill"              # suspended slot: device -> host
FETCH = "fetch"              # suspended slot: host -> device
PREFIX_SPILL = "prefix-spill"  # harvested warm prefix pages -> host
PREFIX_FETCH = "prefix-fetch"  # tier prefix hit -> fresh device pages


@dataclasses.dataclass
class TierTransfer:
    """One in-flight device<->host page transfer on the tick clock."""
    kind: str
    rid: Optional[str]             # owning request (None for prefix spills)
    eid: Optional[int]             # HostPageTier entry id (slot transfers)
    nbytes: int
    eta: int                       # ticks until the current attempt lands
    meta: dict = dataclasses.field(default_factory=dict)
    retries: int = 0
    backoff: int = 0
    age: int = 0
    failure: Optional[str] = None  # set when the clock gives up


class TransferClock:
    """Advances in-flight transfers once per engine tick.

    ``advance(hook)`` returns ``(completed, failed)``: transfers whose
    attempt landed this tick, and transfers that exhausted their retry
    budget or outlived the timeout. The caller finalizes completions
    (the actual staged copy / pool scatter) and walks failures down the
    degradation ladder.
    """

    def __init__(self, cfg: TierConfig):
        self.cfg = cfg
        self.inflight: List[TierTransfer] = []
        self.retries = 0
        self.timeouts = 0

    def submit(self, kind: str, rid: Optional[str], eid: Optional[int],
               nbytes: int, slow: float = 1.0, **meta) -> TierTransfer:
        eta = max(1, math.ceil(self.cfg.xfer_ticks * slow))
        t = TierTransfer(kind=kind, rid=rid, eid=eid, nbytes=nbytes,
                         eta=eta, meta=meta)
        self.inflight.append(t)
        return t

    def cancel(self, pred) -> List[TierTransfer]:
        """Drop in-flight transfers matching ``pred`` (cancelled request);
        returns them so the caller can release their resources."""
        dropped = [t for t in self.inflight if pred(t)]
        self.inflight = [t for t in self.inflight if not pred(t)]
        return dropped

    def advance(self, hook) -> Tuple[List[TierTransfer], List[TierTransfer]]:
        completed: List[TierTransfer] = []
        failed: List[TierTransfer] = []
        keep: List[TierTransfer] = []
        for t in self.inflight:
            t.age += 1
            if t.age > self.cfg.timeout_ticks:
                t.failure = "timeout"
                self.timeouts += 1
                failed.append(t)
                continue
            if t.backoff > 0:
                t.backoff -= 1
                if t.backoff == 0:
                    # next attempt begins at the link speed of *this* tick
                    t.eta = max(1, math.ceil(self.cfg.xfer_ticks
                                             * hook.slow()))
                keep.append(t)
                continue
            t.eta -= 1
            if t.eta > 0:
                keep.append(t)
                continue
            # the attempt lands this tick — unless the link drops it
            if hook.drop():
                t.retries += 1
                self.retries += 1
                if t.retries > self.cfg.max_retries:
                    t.failure = "retries exhausted"
                    failed.append(t)
                    continue
                t.backoff = 2 ** (t.retries - 1)
                keep.append(t)
                continue
            completed.append(t)
        self.inflight = keep
        return completed, failed


def trim_pages(payload: Any, n: int) -> Any:
    """Keep the first ``n`` pages (axis 1) of a gathered payload, as host
    numpy arrays (gathers pad to the static pages-per-slot width)."""
    return jax.tree.map(lambda a: np.ascontiguousarray(a[:, :n]), payload)


def pad_pages(payload: Any, k: int) -> Any:
    """Zero-pad a host payload back to the static width ``k`` (axis 1) so
    the install scatter sees one shape; padded rows target the trash page."""
    def _pad(a):
        a = np.asarray(a)
        if a.shape[1] == k:
            return a
        pad = np.zeros((a.shape[0], k - a.shape[1]) + a.shape[2:], a.dtype)
        return np.concatenate([a, pad], axis=1)
    return jax.tree.map(_pad, payload)


def slice_page(payload: Any, j: int) -> Any:
    """Extract page ``j`` as its own single-page payload (axis 1 kept)."""
    return jax.tree.map(
        lambda a: np.ascontiguousarray(np.asarray(a)[:, j:j + 1]), payload)


def concat_pages(payloads: List[Any]) -> Any:
    """Stitch single-page payloads back into one multi-page payload."""
    return jax.tree.map(lambda *xs: np.concatenate(xs, axis=1), *payloads)


# ---------------------------------------------------------------------------
# Host page store
# ---------------------------------------------------------------------------

# Residency states of a tier entry. A page set starts on DEVICE (no entry),
# enters SPILLING when a host reservation is made and the device->host
# transfer is in flight, becomes HOST once the bytes are durable, and
# FETCHING while a host->device transfer is in flight; a completed fetch
# frees the entry (back to DEVICE). Transitions outside this cycle raise.
TIER_SPILLING = "spilling"
TIER_HOST = "host"
TIER_FETCHING = "fetching"

_TIER_TRANSITIONS = {
    (TIER_SPILLING, TIER_HOST),     # commit
    (TIER_HOST, TIER_FETCHING),     # begin_fetch
    (TIER_FETCHING, TIER_HOST),     # abort_fetch (retry / preempted fetch)
}


def payload_page_crcs(payload: Any, n_pages: int) -> List[int]:
    """CRC32 per page over a gathered page payload.

    ``payload`` is a pytree of host numpy arrays whose axis 1 is the page
    axis (``(layers, n_pages, page, ...)`` — the shape ``gather_pages``
    hands back). Each page's checksum folds that page's bytes from every
    leaf in deterministic pytree order, so a single flipped byte anywhere
    in a spilled page is caught at fetch time.
    """
    crcs = [0] * n_pages
    for leaf in jax.tree.leaves(payload):
        a = np.asarray(leaf)
        for j in range(n_pages):
            crcs[j] = zlib.crc32(np.ascontiguousarray(a[:, j]).tobytes(),
                                 crcs[j])
    return crcs


def payload_crc(payload: Any) -> int:
    """Single CRC32 over a whole pytree of host arrays (aux leaves)."""
    crc = 0
    for leaf in jax.tree.leaves(payload):
        crc = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(),
                         crc)
    return crc


def payload_nbytes(payload: Any) -> int:
    """Total byte size of a pytree of host arrays (transfer accounting)."""
    return sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(payload))


class TierEntry:
    """One suspended slot's page set parked in (or moving through) the
    host tier. Payloads are opaque pytrees of host numpy arrays; the tier
    validates residency transitions and capacity, nothing else."""

    __slots__ = ("eid", "n_pages", "state", "payload", "aux", "crcs",
                 "aux_crc")

    def __init__(self, eid: int, n_pages: int):
        self.eid = eid
        self.n_pages = n_pages
        self.state = TIER_SPILLING
        self.payload: Any = None
        self.aux: Any = None
        self.crcs: List[int] = []
        self.aux_crc: int = 0


class HostPageTier:
    """Host-side (numpy) page store behind the device pool.

    Capacity is counted in pages. Two kinds of content share it:

    * **Slot entries** — a suspended request's whole page set plus its
      decode aux leaves, reserved atomically via :meth:`reserve` and
      tracked through the SPILLING -> HOST -> FETCHING state machine.
    * **Prefix pages** — individual refcount-0 warm-LRU pages harvested
      from the device allocator's prefix cache, one page each, kept in
      their own LRU. They are cache copies, not the only copy, so they are
      always evictable: a slot reservation squeezes the oldest prefix
      pages out first.

    Every spilled page carries a CRC32 (:func:`payload_page_crcs`) checked
    at fetch time; the tier itself never touches a device buffer — staging
    device<->host is the caller's job (:func:`staged_get`,
    :func:`staged_put`).
    """

    def __init__(self, capacity_pages: int):
        if capacity_pages <= 0:
            raise ValueError(f"host tier needs capacity > 0, "
                             f"got {capacity_pages}")
        self.capacity_pages = capacity_pages
        self._entries: Dict[int, TierEntry] = {}
        self._next_eid = 0
        # key -> (payload, crc); insertion order is LRU order
        self._prefix: "collections.OrderedDict[bytes, Tuple[Any, int]]" = \
            collections.OrderedDict()
        self.prefix_evictions = 0

    # -- capacity ----------------------------------------------------------

    def slot_pages(self) -> int:
        return sum(e.n_pages for e in self._entries.values())

    def prefix_pages(self) -> int:
        return len(self._prefix)

    def used_pages(self) -> int:
        return self.slot_pages() + self.prefix_pages()

    def free_pages(self) -> int:
        return self.capacity_pages - self.used_pages()

    def occupancy(self) -> float:
        return self.used_pages() / self.capacity_pages

    def entries(self) -> int:
        return len(self._entries)

    # -- slot entries ------------------------------------------------------

    def reserve(self, n_pages: int) -> Optional[int]:
        """Reserve ``n_pages`` for a suspending slot; returns an entry id
        (state SPILLING) or None when the tier cannot fit it. Oldest
        prefix pages are evicted to make room — they are cache copies and
        a suspension is the only copy."""
        if n_pages > self.capacity_pages:
            return None
        while self.free_pages() < n_pages and self._prefix:
            self._prefix.popitem(last=False)
            self.prefix_evictions += 1
        if self.free_pages() < n_pages:
            return None
        eid = self._next_eid
        self._next_eid += 1
        self._entries[eid] = TierEntry(eid, n_pages)
        return eid

    def _entry(self, eid: int, *states: str) -> TierEntry:
        e = self._entries.get(eid)
        if e is None:
            raise KeyError(f"tier entry {eid} does not exist")
        if states and e.state not in states:
            raise ValueError(f"tier entry {eid} is {e.state}, "
                             f"expected one of {states}")
        return e

    def _transition(self, e: TierEntry, to: str) -> None:
        if (e.state, to) not in _TIER_TRANSITIONS:
            raise ValueError(f"illegal tier transition {e.state} -> {to} "
                             f"for entry {e.eid}")
        e.state = to

    def commit(self, eid: int, payload: Any, aux: Any,
               crcs: Sequence[int], aux_crc: int) -> None:
        """Land a spill: SPILLING -> HOST with the page bytes durable."""
        e = self._entry(eid, TIER_SPILLING)
        if len(crcs) != e.n_pages:
            raise ValueError(f"entry {eid}: {len(crcs)} CRCs for "
                             f"{e.n_pages} pages")
        self._transition(e, TIER_HOST)
        e.payload, e.aux, e.crcs, e.aux_crc = payload, aux, list(crcs), aux_crc

    def begin_fetch(self, eid: int) -> TierEntry:
        """HOST -> FETCHING; returns the entry (payload/crcs readable)."""
        e = self._entry(eid, TIER_HOST)
        self._transition(e, TIER_FETCHING)
        return e

    def abort_fetch(self, eid: int) -> None:
        """FETCHING -> HOST (failed/preempted fetch keeps the host copy)."""
        e = self._entry(eid, TIER_FETCHING)
        self._transition(e, TIER_HOST)

    def state(self, eid: int) -> str:
        return self._entry(eid).state

    def free(self, eid: int) -> None:
        """Drop an entry in any state (fetch completed, cancel, degrade)."""
        self._entry(eid)
        del self._entries[eid]

    # -- prefix page cache -------------------------------------------------

    def put_prefix(self, key: bytes, payload: Any, crc: int) -> bool:
        """Park one harvested prefix page under ``key``. Evicts older
        prefix pages LRU to fit, never slot entries; returns False when
        slot entries alone leave no room."""
        if key in self._prefix:
            self._prefix.move_to_end(key)
            return True
        while self.free_pages() < 1 and self._prefix:
            self._prefix.popitem(last=False)
            self.prefix_evictions += 1
        if self.free_pages() < 1:
            return False
        self._prefix[key] = (payload, crc)
        return True

    def prefix_run(self, keys: Sequence[bytes], granularity: int = 1) -> int:
        """Length (pages, rounded down to ``granularity``) of the leading
        contiguous run of ``keys`` present in the prefix cache."""
        n = 0
        for key in keys:
            if key not in self._prefix:
                break
            n += 1
        return n // granularity * granularity

    def take_prefix(self, keys: Sequence[bytes]
                    ) -> List[Tuple[Any, int]]:
        """Read ``(payload, crc)`` per key (all must be present), touching
        each entry to MRU. Entries stay cached — a fetch copies them back
        to the device, it does not remove the host copy."""
        out = []
        for key in keys:
            if key not in self._prefix:
                raise KeyError("prefix page vanished from the tier")
            self._prefix.move_to_end(key)
            out.append(self._prefix[key])
        return out

    def drop_prefix(self, key: bytes) -> None:
        self._prefix.pop(key, None)


# ---------------------------------------------------------------------------
# The tiered engine
# ---------------------------------------------------------------------------


def _slot_slice(batch_cache, slot, axes):
    """Read slot ``slot`` out of a batch cache as a batch-1 pytree — the
    inverse of ``engine._splice`` for full-length leaves (``slot``
    traced). The suspension gather captures a slot's aux leaves (encoder
    memory, MTP state) with it alongside its pages."""
    def f(big, ax):
        starts = tuple(slot if i == ax else 0 for i in range(big.ndim))
        sizes = tuple(1 if i == ax else big.shape[i]
                      for i in range(big.ndim))
        return jax.lax.dynamic_slice(big, starts, sizes)

    return jax.tree.map(f, batch_cache, axes)


class TieredServeEngine(ServeEngine):
    """A paged ``ServeEngine`` whose device pool is a cache over a host
    tier of ``host_tier_pages`` pages (module docstring).

    Takes ``ServeEngine``'s arguments (``paged=True`` required) plus the
    tier's: ``tier_config`` (:class:`TierConfig`) and ``tier_faults``, a
    fault hook with :class:`NullFaultHook`'s methods that the transfer
    clock consults (``serve/fault.TierFaultAdapter`` injects chaos).
    """

    def __init__(self, cfg: ModelConfig, params=None, *,
                 host_tier_pages: int,
                 tier_config: Optional[TierConfig] = None,
                 tier_faults=None, **kw):
        if not kw.get("paged"):
            raise ValueError("TieredServeEngine requires paged=True: the "
                             "tier spills page sets, dense rings have none")
        super().__init__(cfg, params, **kw)
        self.tier = HostPageTier(host_tier_pages)
        self.tier_cfg = (tier_config if tier_config is not None
                         else TierConfig())
        self.tier_faults = (tier_faults if tier_faults is not None
                            else NullFaultHook())
        self._xfers = TransferClock(self.tier_cfg)
        # rid -> suspension entry; insertion order is the resume order
        self._suspended: "collections.OrderedDict[int, Dict[str, Any]]" = \
            collections.OrderedDict()
        self._spilling_slots: Dict[int, int] = {}   # slot -> rid
        # rotation quantum clock: the tick each slot last started decoding
        self._slot_tick0 = np.zeros((self.slots,), np.int64)
        self._tick = 0
        self.tstats = {"suspensions": 0, "resumes": 0, "spilled_pages": 0,
                       "fetched_pages": 0, "spill_bytes": 0,
                       "fetch_bytes": 0, "prefetch_stalls": 0,
                       "degraded": 0, "crc_failures": 0, "spill_aborts": 0,
                       "tier_full_refusals": 0, "peak_resident_pages": 0,
                       "prefix_spilled": 0, "prefix_fetched": 0}
        self._tier_gather_traces = 0
        self._tier_scatter_traces = 0
        self._tier_resume_traces = 0
        self._install_tier_jits()

    def _install_tier_jits(self):
        """The three tier entry points, each compile-once: gather reads a
        fixed pages_per_slot-wide id vector (trash-padded) plus the slot's
        aux leaves; scatter installs a payload of the same static width
        (no page-table change — FETCHING pages hold bytes before any row
        references them); resume installs the table row + aux splice when
        a slot frees up. Meshed engines pin the cache-carrying outputs to
        the pool's shardings, as the base engine's jits do."""
        cache_out = self._cache_shardings if self.meshed else None

        def tier_gather(cache, ids, slot):
            self._tier_gather_traces += 1
            pages = self.model.gather_pages(cache, ids)
            aux = {}
            if self._aux_axes:
                aux = _slot_slice({k: cache[k] for k in self._aux_axes},
                                  slot, self._aux_axes)
            return pages, aux

        gather_out = None
        if self.meshed:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.parallel import sharding as sh_mod
            zids = np.zeros((self.pages_per_slot,), np.int32)
            pay_s, aux_s = jax.eval_shape(tier_gather, self.cache, zids, 0)
            self._tier_gather_traces = 0   # eval_shape traced once
            rep = NamedSharding(self.ctx.mesh, PartitionSpec())
            gather_out = (
                sh_mod.tier_payload_pspecs(pay_s, self.ctx.mesh,
                                           self.ctx.tp_axis or "model"),
                jax.tree.map(lambda _: rep, aux_s))
        # repro-lint: disable=R2-jit-contract -- the cache is only read,
        # never donated: the suspended slot's gather must keep decoding
        # peers' pool buffer alive
        self._tier_gather_fn = jax.jit(tier_gather, out_shardings=gather_out)

        def tier_scatter(cache, pages, ids):
            self._tier_scatter_traces += 1
            return self.model.install_pages(cache, pages, ids)

        self._tier_scatter_fn = jax.jit(
            tier_scatter, donate_argnums=(0,), out_shardings=cache_out)

        def tier_resume(cache, aux, row, slot):
            self._tier_resume_traces += 1
            table = cache["page_table"]
            out = dict(cache)
            out["page_table"] = jax.lax.dynamic_update_slice(
                table, row[None].astype(table.dtype), (slot, 0))
            if aux:
                big = {k: out[k] for k in aux}
                out.update(_splice(big, aux, slot, self._aux_axes))
            return out

        self._tier_resume_fn = jax.jit(
            tier_resume, donate_argnums=(0,), out_shardings=cache_out)

    # -- the base engine's hooks ---------------------------------------------
    @property
    def trace_counts(self) -> Dict[str, int]:
        """The base counts plus the tier's gather/scatter/resume, each
        ≤ 1 — every transfer pads to the static pages_per_slot width."""
        out = super().trace_counts
        out.update(tier_gather=self._tier_gather_traces,
                   tier_scatter=self._tier_scatter_traces,
                   tier_resume=self._tier_resume_traces)
        return out

    def _on_decode_start(self, slot: int):
        self._slot_tick0[slot] = self._tick

    def _masked_slots(self) -> set:
        # mid-spill slots hold captured-in-flight pages; their
        # authoritative mirrors ride their tier entry
        return super()._masked_slots() | set(self._spilling_slots)

    def _runnable_prefills(self) -> List[int]:
        # slots whose prefix pages are inbound from the tier wait
        return [s for s in super()._runnable_prefills()
                if not self._prefilling[s].get("tier_xfer")]

    def _admit_chunked(self, req, extras, slot: int) -> int:
        shared = super()._admit_chunked(req, extras, slot)
        self._probe_tier_prefix(slot, shared)
        return shared

    def _try_evict(self, inc: int) -> bool:
        """Prefer *spilling* the victim over evicting it — its KV moves to
        the host instead of being recomputed — in which case this returns
        False: the capacity arrives asynchronously when the spill lands,
        and the caller must not keep preempting for the same arrival this
        tick. With no victim, abort the fetch of a strictly-lower-priority
        suspended entry (its host copy survives; the fetch restarts
        later) before the base engine's last rung."""
        slot = self._eviction_victim(inc)
        if slot is not None and self._begin_suspend(slot):
            return False
        if slot is None:
            fetching = [(e["req"].priority, rid)
                        for rid, e in self._suspended.items()
                        if e["state"] == "fetching"
                        and e["req"].priority < inc]
            if fetching:
                rid = min(fetching)[1]
                e = self._suspended[rid]
                self._xfers.cancel(lambda t: t.rid == rid
                                   and t.kind == FETCH)
                self.tier.abort_fetch(e["eid"])
                self._alloc.release(e["fetch_pages"])
                e["fetch_pages"], e["tier_entry"] = None, None
                e["state"] = "host"
                return True
        return super()._try_evict(inc)

    def _step(self):
        """Tier phases around the base tick: advance the transfer clock
        (landing spills free slots/pages, landing fetches ready resumes),
        admit, resume fetched entries into leftover slots, rotate a
        quantum-expired resident out for waiters, start prefetches with
        leftover pages, and harvest cold prefix pages — then prefill and
        decode as usual with spilling slots masked out. Fetches restart
        once more after decode (``_harvest``) so pages freed by
        completions this tick are already in flight by the next."""
        self._tick += 1
        self.tier_faults.on_tick()
        self._advance_transfers()
        with _span("engine.admit"):
            admitted = self._admit_pending()
        resumed = self._resume_ready()
        if (not admitted and not resumed and self.free_slots()
                and any(e["state"] in ("host", "fetching")
                        for e in self._suspended.values())):
            # a slot sat idle this tick because tiered KV wasn't back
            # yet — the prefetch schedule exists to keep this at 0
            self.tstats["prefetch_stalls"] += 1
        self._rotate()
        self._start_fetches()
        self._harvest_prefix()
        live = sum(len(p) for p in self._slot_pages) + sum(
            e["n"] for e in self._suspended.values()
            if e["state"] != "spilling")
        self.tstats["peak_resident_pages"] = max(
            self.tstats["peak_resident_pages"], live)
        self._prefill_and_decode()

    def _harvest(self, toks, emitted, host):
        super()._harvest(toks, emitted, host)
        # pages freed by completions this tick feed the prefetch schedule
        # immediately: the fetch lands on next tick's clock advance,
        # before the freed slot is rescheduled — the no-stall overlap
        # the serve_bench gate asserts
        self._start_fetches()

    def cancel(self, rid: int) -> bool:
        """The base engine's cancel, plus whichever tier state the request
        is in (SPILLING/HOST/FETCHING/ready): device and host pages both
        free and any in-flight transfer is dropped from the clock."""
        self._xfers.cancel(lambda t: t.rid == rid)
        e = self._suspended.pop(rid, None)
        if e is None:
            return super().cancel(rid)
        st = e["state"]
        if st == "spilling":
            # slot + device pages still held; row already trashed
            slot = e["slot"]
            del self._spilling_slots[slot]
            self.tier.free(e["eid"])
            self._release_slot(slot)
        elif st == "host":
            self.tier.free(e["eid"])
        elif st == "fetching":
            self.tier.free(e["eid"])
            if e["fetch_pages"]:
                self._alloc.release(e["fetch_pages"])
        else:   # ready: tier entry already freed, device pages held
            self._alloc.release(e["pages"])
        return True

    def has_work(self) -> bool:
        """Also suspended entries parked in the host tier and transfers
        still on the clock: with every request suspended the engine still
        owes those requests their resumes."""
        return (super().has_work() or bool(self._suspended)
                or bool(self._xfers.inflight))

    def pool_stats(self) -> Dict[str, Any]:
        """The device pool's occupancy plus the host side, so capacity
        dashboards see both levels of the hierarchy."""
        out = super().pool_stats()
        out.update(host_pages_total=self.tier.capacity_pages,
                   host_pages_free=self.tier.free_pages(),
                   host_occupancy=self.tier.occupancy())
        return out

    def prefix_stats(self) -> Dict[str, Any]:
        out = super().prefix_stats()
        out.update(tier_prefix_pages=self.tier.prefix_pages(),
                   tier_prefix_evictions=self.tier.prefix_evictions,
                   tier_prefix_fetched=self.tstats["prefix_fetched"])
        return out

    def tier_stats(self) -> Dict[str, Any]:
        """Host-tier residency and transfer counters (``tstats`` plus the
        live tier/clock occupancy)."""
        out = dict(self.tstats)
        out.update(host_pages_total=self.tier.capacity_pages,
                   host_pages_used=self.tier.used_pages(),
                   host_pages_free=self.tier.free_pages(),
                   host_occupancy=self.tier.occupancy(),
                   host_prefix_pages=self.tier.prefix_pages(),
                   suspended=len(self._suspended),
                   transfers_inflight=len(self._xfers.inflight),
                   retries=self._xfers.retries,
                   timeouts=self._xfers.timeouts)
        return out

    # -- suspension, fetch and resume ----------------------------------------
    def _probe_tier_prefix(self, slot: int, h: int):
        """Extend a chunked admission's shared-prefix run (``h`` pages
        from the device index) with host-tier prefix pages: pages past
        the device hit run that the tier holds are fetched into the
        slot's fresh pages instead of recomputed. The prefill cursor only
        advances when the fetch lands CRC-clean
        (``_finish_prefix_fetch``); until then the slot waits — decode
        never reads a page before its bytes are installed."""
        p, C = self.page_size, self.prefill_chunk
        ppc = C // p
        ps = self._prefilling[slot]
        keys, L = ps["keys"], len(ps["prompt"])
        if ps["next"] != h * p:
            return   # device hits already reach the final-chunk bound
        bound_pages = ((L - 1) // C * C) // p
        run = min(self.tier.prefix_run(keys[h:], ppc),
                  bound_pages - h) // ppc * ppc
        if run <= 0:
            return
        tkeys = keys[h:h + run]
        stored = self.tier.take_prefix(tkeys)
        ps["tier_xfer"] = True
        self._xfers.submit(
            PREFIX_FETCH, ps["req"].rid, None,
            sum(payload_nbytes(pg) for pg, _ in stored),
            slow=self.tier_faults.slow(), slot=slot, req=ps["req"],
            keys=tkeys, stored=stored,
            pages=self._slot_pages[slot][h:h + run], end=(h + run) * p)

    def _begin_suspend(self, slot: int) -> bool:
        """Start spilling ``slot``'s whole page set to the host tier.

        The gather + staged copy happen eagerly (the slot's masked decode
        lane would otherwise keep mutating aux state, and a reused slot
        would overwrite it), the page-table row is trashed immediately so
        no later dispatch can write into the captured pages, and the
        *transfer clock* models when the host copy becomes durable — the
        slot and its device pages stay held until the spill lands, so a
        failed spill resumes in place with zero lost work. Returns False
        when the tier cannot take the pages (caller falls back to the
        base engine's evict-and-requeue rung)."""
        req = self.active[slot]
        pages = self._slot_pages[slot]
        n = len(pages)
        if n == 0:
            return False
        if self.tier_faults.full():
            self.tstats["tier_full_refusals"] += 1
            return False
        eid = self.tier.reserve(n)
        if eid is None:
            self.tstats["tier_full_refusals"] += 1
            return False
        ids = self._page_row(pages)
        self.stats["dispatches"] += 1
        pay_dev, aux_dev = self._tier_gather_fn(self.cache,
                                                jnp.asarray(ids), slot)
        payload = trim_pages(staged_get(pay_dev), n)
        aux = staged_get(aux_dev)
        crcs = payload_page_crcs(payload, n)
        aux_crc = payload_crc(aux)
        nbytes = (payload_nbytes(payload)
                  + payload_nbytes(aux))
        # trash the row now: the captured bytes must stay immutable while
        # the transfer is in flight (the lane is masked out of decode, but
        # masked lanes still write through their row)
        self.stats["dispatches"] += 1
        self.cache = self._release_fn(self.cache, slot)
        mirrors = dict(pos=int(self.positions[slot]),
                       tok=int(self._tokens[slot]),
                       left=int(self._left[slot]),
                       eos=int(self._eos[slot]),
                       rng=self._rngs[slot].copy(),
                       tix=int(self._tix[slot]))
        self._xfers.submit(SPILL, req.rid, eid, nbytes,
                           slow=self.tier_faults.slow())
        self._suspended[req.rid] = dict(
            req=req, extras=self._slot_extras[slot], state="spilling",
            eid=eid, n=n, slot=slot, pages=None, fetch_pages=None,
            tier_entry=None, payload=payload, aux=aux, crcs=crcs,
            aux_crc=aux_crc, mirrors=mirrors)
        self._spilling_slots[slot] = req.rid
        self.tstats["suspensions"] += 1
        return True

    def _finish_spill(self, t: TierTransfer):
        """A spill landed: the host copy is durable, so the device side —
        slot and pages — finally frees (the row was trashed at suspend)."""
        e = self._suspended.get(t.rid)
        if e is None or e["state"] != "spilling":
            return   # cancelled while in flight
        self.tier.commit(e["eid"], e["payload"], e["aux"], e["crcs"],
                         e["aux_crc"])
        slot = e.pop("slot")
        del self._spilling_slots[slot]
        self._alloc.release(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self.stats["page_releases"] += 1
        self.active[slot] = None
        self._slot_extras[slot] = None
        e["state"] = "host"
        e["payload"] = None   # the tier owns the bytes now
        self.tstats["spilled_pages"] += e["n"]
        self.tstats["spill_bytes"] += t.nbytes

    def _fail_spill(self, t: TierTransfer):
        """Spill transfer failed terminally: resume in place. The device
        pages were never released, so re-installing the row + aux loses
        nothing — the degradation ladder's cheapest rung."""
        e = self._suspended.pop(t.rid, None)
        if e is None:
            return
        self.tier.free(e["eid"])
        slot = e["slot"]
        del self._spilling_slots[slot]
        self.tstats["spill_aborts"] += 1
        row = self._page_row(self._slot_pages[slot])
        self.stats["dispatches"] += 1
        self.cache = self._tier_resume_fn(self.cache, e["aux"],
                                          jnp.asarray(row), slot)
        self._restore_mirrors(slot, e["mirrors"])
        self._slot_tick0[slot] = self._tick

    def _restore_mirrors(self, slot: int, m: Dict[str, Any]):
        self.positions[slot] = m["pos"]
        self._tokens[slot] = m["tok"]
        self._left[slot] = m["left"]
        self._eos[slot] = m["eos"]
        self._rngs[slot] = m["rng"]
        self._tix[slot] = m["tix"]

    def _start_fetches(self):
        """Prefetch-ahead: start host->device transfers for suspended
        entries, FIFO (oldest suspension first), using whatever pool pages
        admission left over this tick. A page-blocked entry blocks the
        ones behind it (no small-latecomer jumping — that is the
        admission queue's starvation lesson applied here), and when the
        pending head's starvation guard has tripped, freed pages are its
        alone, so no fetch starts at all."""
        if self.pending and self._hol_skips >= STARVATION_LIMIT:
            return
        for rid, e in self._suspended.items():
            if e["state"] != "host":
                continue
            n = e["n"]
            if n > self.free_pages():
                break
            e["fetch_pages"] = self._alloc.alloc(n)
            ent = self.tier.begin_fetch(e["eid"])
            e["tier_entry"] = ent
            e["state"] = "fetching"
            nbytes = (payload_nbytes(ent.payload)
                      + payload_nbytes(ent.aux))
            self._xfers.submit(FETCH, rid, e["eid"], nbytes,
                               slow=self.tier_faults.slow())

    def _finish_fetch(self, t: TierTransfer):
        """A fetch landed: CRC-check the host bytes, scatter them into the
        reserved device pages, and mark the entry ready to resume the
        moment a slot frees. CRC mismatch walks the degradation ladder."""
        e = self._suspended.get(t.rid)
        if e is None or e["state"] != "fetching":
            return
        ent, n = e["tier_entry"], e["n"]
        if (payload_page_crcs(ent.payload, n) != ent.crcs
                or payload_crc(ent.aux) != ent.aux_crc):
            self.tstats["crc_failures"] += 1
            self._degrade(t.rid)
            return
        pages = e["fetch_pages"]
        ids = self._page_row(pages)
        payload = pad_pages(ent.payload, self.pages_per_slot)
        self.stats["dispatches"] += 1
        self.cache = self._tier_scatter_fn(
            self.cache, staged_put(payload), jnp.asarray(ids))
        e["aux"] = ent.aux
        e["pages"], e["fetch_pages"] = pages, None
        e["tier_entry"] = None
        e["state"] = "ready"
        self.tier.free(e["eid"])
        self.tstats["fetched_pages"] += n
        self.tstats["fetch_bytes"] += t.nbytes

    def _degrade(self, rid: int):
        """Unrecoverable fetch (retries exhausted / timeout / CRC): drop
        the tiered copy and re-queue the request as a
        continuation — ``_effective`` re-prefills prompt+delivered at the
        advanced stream offset, so a seeded request's completed stream
        stays bitwise-identical to the no-fault run."""
        e = self._suspended.pop(rid, None)
        if e is None:
            return
        if e["fetch_pages"]:
            self._alloc.release(e["fetch_pages"])
        self.tier.free(e["eid"])
        self.tstats["degraded"] += 1
        self.pending.appendleft((e["req"], e["extras"]))

    def _resume_ready(self) -> int:
        """Re-admit fetched entries (suspension order) into free slots:
        one jitted row+aux install each, host mirrors restored — no
        prefill, no recompute. Runs after admissions so new requests get
        first claim on slots (least-attained-service first)."""
        resumed = 0
        for rid in list(self._suspended):
            e = self._suspended[rid]
            if e["state"] != "ready":
                continue
            free = self.free_slots()
            if not free:
                break
            slot = free[0]
            pages = e["pages"]
            row = self._page_row(pages)
            self.stats["dispatches"] += 1
            self.cache = self._tier_resume_fn(self.cache, e["aux"],
                                              jnp.asarray(row), slot)
            del self._suspended[rid]
            self._slot_pages[slot] = pages
            self._slot_extras[slot] = e["extras"]
            self.active[slot] = e["req"]
            self._restore_mirrors(slot, e["mirrors"])
            self._slot_tick0[slot] = self._tick
            self.tstats["resumes"] += 1
            resumed += 1
        return resumed

    def _rotate(self):
        """Time-slice rotation: when waiters exist (queued requests or
        suspended entries), suspend the longest-resident decoding slot
        whose quantum expired — spill-based preemption, so oversubscribed
        workloads round-robin through the device pool instead of
        re-prefilling (the base engine's eviction) or starving the
        queue."""
        waiters = [req.priority for req, _ in self.pending]
        waiters += [e["req"].priority for e in self._suspended.values()
                    if e["state"] != "spilling"]
        if not waiters:
            return
        cap = max(waiters)
        decoding = [s for s in range(self.slots)
                    if self.active[s] is not None
                    and s not in self._prefilling
                    and s not in self._spilling_slots]
        ready = any(e["state"] == "ready"
                    for e in self._suspended.values())
        if len(decoding) <= 1 and not ready:
            return   # never idle the whole pool waiting on the PCIe link
        expired = [(self._slot_tick0[s], s) for s in decoding
                   if self._tick - self._slot_tick0[s] >= self.tier_cfg.quantum
                   and self.active[s].priority <= cap]
        if expired:
            self._begin_suspend(min(expired)[1])

    def _harvest_prefix(self):
        """Warm-LRU prefix spill: when the plain free pool runs dry and
        refcount-0 prefix pages are parked in the device cache, move the
        coldest batch to the host tier's prefix store — they come back via
        the admission-time tier probe instead of recompute. Pages stay
        pinned until the host copy is durable; a failed spill re-indexes
        them (nothing lost either way — these are cache copies)."""
        if self.prefill_chunk is None or self.tier_faults.full():
            return
        if self._alloc.plain_free() > 0 or self._alloc.cached_free() == 0:
            return
        k = min(self.tier_cfg.harvest_batch, self.pages_per_slot,
                self.tier.free_pages())
        harvested = self._alloc.harvest(k)
        if not harvested:
            return
        ids = self._page_row([pid for pid, _ in harvested])
        self.stats["dispatches"] += 1
        pay_dev, _ = self._tier_gather_fn(self.cache, jnp.asarray(ids), 0)
        payload = trim_pages(staged_get(pay_dev),
                                      len(harvested))
        self._xfers.submit(PREFIX_SPILL, None, None,
                           payload_nbytes(payload),
                           slow=self.tier_faults.slow(),
                           harvest=harvested, payload=payload)

    def _finish_prefix_spill(self, t: TierTransfer):
        for j, (pid, key) in enumerate(t.meta["harvest"]):
            pg = slice_page(t.meta["payload"], j)
            self.tier.put_prefix(key, pg, payload_crc(pg))
        self._alloc.release([pid for pid, _ in t.meta["harvest"]])
        self.tstats["prefix_spilled"] += len(t.meta["harvest"])
        self.tstats["spill_bytes"] += t.nbytes

    def _fail_prefix_spill(self, t: TierTransfer):
        # the device copy never left: re-index the pages (release parks
        # them back in the warm cache) and count the abort
        for pid, key in t.meta["harvest"]:
            self._alloc.register(key, pid)
        self._alloc.release([pid for pid, _ in t.meta["harvest"]])
        self.tstats["spill_aborts"] += 1

    def _finish_prefix_fetch(self, t: TierTransfer):
        """Tier prefix pages arrived for a chunk-prefilling slot: verify
        CRCs, scatter into the slot's already-reserved fresh pages,
        index them, and advance the prefill cursor past the covered
        chunks. Any CRC mismatch drops the poisoned tier entries and
        leaves the cursor alone — the chunks recompute into the same
        pages, bitwise-identical."""
        m = t.meta
        slot = m["slot"]
        ps = self._prefilling.get(slot)
        if ps is None or ps.get("req") is not m["req"]:
            return   # slot cancelled/recycled while the fetch flew
        ps["tier_xfer"] = False
        bad = [j for j, (pg, crc) in enumerate(m["stored"])
               if payload_crc(pg) != crc]
        if bad:
            self.tstats["crc_failures"] += 1
            for j in bad:
                self.tier.drop_prefix(m["keys"][j])
            return
        pages = m["pages"]
        ids = self._page_row(pages)
        payload = pad_pages(
            concat_pages([pg for pg, _ in m["stored"]]),
            self.pages_per_slot)
        self.stats["dispatches"] += 1
        self.cache = self._tier_scatter_fn(
            self.cache, staged_put(payload), jnp.asarray(ids))
        for j, key in enumerate(m["keys"]):
            self._alloc.register(key, pages[j])
        ps["next"] = m["end"]
        self.tstats["prefix_fetched"] += len(pages)
        self.tstats["fetch_bytes"] += t.nbytes

    def _fail_prefix_fetch(self, t: TierTransfer):
        ps = self._prefilling.get(t.meta["slot"])
        if ps is not None and ps.get("req") is t.meta["req"]:
            ps["tier_xfer"] = False   # cursor untouched: chunks recompute

    def _advance_transfers(self):
        done, failed = self._xfers.advance(self.tier_faults)
        for t in done:
            if t.kind == SPILL:
                self._finish_spill(t)
            elif t.kind == FETCH:
                self._finish_fetch(t)
            elif t.kind == PREFIX_SPILL:
                self._finish_prefix_spill(t)
            elif t.kind == PREFIX_FETCH:
                self._finish_prefix_fetch(t)
        for t in failed:
            if t.kind == SPILL:
                self._fail_spill(t)
            elif t.kind == FETCH:
                self._degrade(t.rid)
            elif t.kind == PREFIX_SPILL:
                self._fail_prefix_spill(t)
            elif t.kind == PREFIX_FETCH:
                self._fail_prefix_fetch(t)
