"""Batched serving engine: slot-based continuous batching over the decode
cache, with the entire steady-state hot path fused on device.

The decode step is the paper's §2.3.2 workload: memory-bound, TPOT- and
dispatch-latency-dominated. The engine therefore runs decode as **fused
k-step chunks** (``Model.decode_loop``: one ``lax.scan`` covering model
step, sampling, EOS/max-len masking, and the MTP draft) — one host-device
round-trip per ``chunk`` tokens per slot instead of ≥3 per token. Prefill
is jitted once per power-of-two **length bucket** (pad-masked prompts), and
slot admission splices the prefilled cache into the batch cache with a
single jitted ``dynamic_update_slice`` per leaf (donated, so the multi-GB
cache updates in place on accelerators). See docs/serving.md.

``paged=True`` swaps the dense per-slot ring buffers for the **paged FP8
cache** (paper §2.1.2 quantized compression; core/paged.py): one shared
pool of fixed-size token pages per attention segment, per-slot page
tables, and page-granular admission — a request reserves only
``ceil((prompt + max_new) / page_size)`` pages instead of a full
``max_len`` ring, and ``submit()`` admits when *pages* (not just slots)
are available. Prefill writes quantized pages; freeing a slot returns its
pages to the pool and re-points its table row at the trash page so the
slot's still-running (masked) decode lane can never corrupt recycled
pages. At ``page_storage="bf16"`` the paged engine's token streams are
bitwise-identical to the dense engine's.

``ctx=`` (a ``parallel.context.ParallelCtx`` with a mesh) makes the whole
hot path **mesh-aware** (paper §MoE: prefill EP32 / decode EP320 — MoE's
compute–communication trade-off only pays off when experts spread across
devices): params are sharded per the inference rules
(``sharding.serve_rules``: heads + dense matmuls TP over the model axis,
experts EP), the dense cache per ``sharding.cache_pspecs`` (slots over
dp, cache length over model) or the paged pools per
``sharding.paged_cache_pspecs`` (K/V-head axis over model, page tables
replicated, page allocator on host), and prefill / fused decode / slot
admission all run as sharded jitted programs — the cache-carrying ones
(decode, splice/scatter, release) with out-shardings pinned to their
input shardings, so every dispatch sees identical shardings and the
compile-once trace-count contract survives the mesh (prefill's outputs
are per-request handoff payloads, left to GSPMD). MoE
layers dispatch through ``parallel/ep``'s ``ep_flat``/``ep_dedup``
shard_maps at the ctx wire precision; XLA's latency-hiding scheduler
overlaps the decode all-to-alls with dense compute (the dependency
freedom ``parallel/overlap`` documents — its HLO helpers measure the
resulting wire bytes per step). ``ctx=None`` stays the zero-config
single-device default, bitwise-unchanged.

Throughput model and EP interplay live in ``network/perfmodel``;
disaggregation (including cross-mesh prefill->decode handoff) in
``serve/disagg``.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.configs.base import ModelConfig
from repro.core import fp8 as fp8_mod
from repro.core import paged as paged_mod
from repro.models import layers as Lyr
from repro.models.api import Model, build_model
from repro.parallel import context as pctx_mod
from repro.serve import tier as tier_mod

# Smallest prefill bucket: prompts shorter than this share one compile.
MIN_BUCKET = 8

# Admission skip-ahead starvation guard: how many times smaller
# lower-priority requests may jump a page-blocked head before the head
# gets exclusive right to the next freed pages.
STARVATION_LIMIT = 8


class AdmissionError(RuntimeError):
    """Typed capacity rejection: no free slot/page for immediate admission,
    or the bounded pending queue is full. Subclasses RuntimeError so
    pre-gateway callers keep working; the gateway catches it and converts
    it into backpressure (route elsewhere, shed, or reject upstream)."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new: int = 16            # new tokens after the prompt (the
                                 # prefill-produced first token counts)
    eos: Optional[int] = None
    seed: Optional[int] = None   # per-request sampling seed: token t of
                                 # the stream is sampled with
                                 # fold_in(PRNGKey(seed), t) regardless of
                                 # which slot/engine runs it, so a retried
                                 # request reproduces bitwise (None =
                                 # engine-rng, non-reproducible across
                                 # re-dispatch)
    sample_offset: int = 0       # stream index of the first token this
                                 # admission produces; a gateway retry
                                 # re-prefills prompt+delivered and sets
                                 # this to len(delivered)
    priority: int = 0            # scheduler class: higher admits first and
                                 # may preempt strictly-lower residents
                                 # (evicted back to pending as a bitwise
                                 # continuation); equal priorities stay
                                 # FIFO
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def bucket_length(length: int, max_len: int, min_bucket: int = MIN_BUCKET) -> int:
    """Next power-of-two bucket for a prompt length, capped at ``max_len``."""
    if length > max_len:
        raise ValueError(f"prompt length {length} exceeds max_len {max_len}")
    b = min_bucket
    while b < length:
        b *= 2
    return min(b, max_len)


def _splice(batch_cache, one_cache, slot, axes):
    """Write a batch-1 cache pytree into slot ``slot`` of the batch cache.

    ``axes`` is the model-declared batch-axis pytree
    (``Model.cache_batch_axes``); each leaf is one
    ``lax.dynamic_update_slice`` at that axis — no Python shape scanning,
    and ``slot`` stays a traced scalar so one compile serves every slot.
    Length axes shorter than the batch buffer are padded statically
    (positions with -1 so decode masks them out, values with 0).
    """
    def f(big, small, ax):
        if small.shape[ax] not in (1, big.shape[ax]):
            raise ValueError(
                f"_splice: prefill leaf batch axis {ax} has size "
                f"{small.shape[ax]}; expected 1 or {big.shape[ax]} "
                f"(shapes {small.shape} vs {big.shape})")
        widths = [(0, 0) if i == ax else (0, big.shape[i] - small.shape[i])
                  for i in range(big.ndim)]
        if any(w != (0, 0) for w in widths):
            cval = -1 if jnp.issubdtype(small.dtype, jnp.integer) else 0
            small = jnp.pad(small, widths, constant_values=cval)
        starts = tuple(slot if i == ax else 0 for i in range(big.ndim))
        return jax.lax.dynamic_update_slice(
            big, small.astype(big.dtype), starts)

    return jax.tree.map(f, batch_cache, one_cache, axes)


def _slot_slice(batch_cache, slot, axes):
    """Read slot ``slot`` out of a batch cache as a batch-1 pytree — the
    inverse of :func:`_splice` for full-length leaves (``slot`` traced).
    Used by the tier's suspension gather to capture a slot's aux leaves
    (encoder memory, MTP state) alongside its pages."""
    def f(big, ax):
        starts = tuple(slot if i == ax else 0 for i in range(big.ndim))
        sizes = tuple(1 if i == ax else big.shape[i]
                      for i in range(big.ndim))
        return jax.lax.dynamic_slice(big, starts, sizes)

    return jax.tree.map(f, batch_cache, axes)


class ServeEngine:
    """Fixed-slot batch engine (continuous batching-lite).

    All slots share one cache pytree of capacity ``max_len``. ``step()`` is
    a thin host driver: it refills free slots from the pending queue
    (bucketed jitted prefill + jitted splice admission), then launches one
    fused ``chunk``-step decode dispatch and syncs the emitted tokens back
    in a single transfer. Slot occupancy changes per chunk but every device
    shape is static (XLA-friendly), mirroring production decode pods.
    """

    def __init__(self, cfg: ModelConfig, params=None, slots: int = 4,
                 max_len: int = 128, seed: int = 0,
                 use_mtp: bool = False, chunk: int = 8,
                 temperature: float = 0.0, top_k: int = 0,
                 paged: bool = False, page_size: int = 8,
                 pool_pages: Optional[int] = None,
                 page_storage: str = "fp8",
                 max_pending: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 host_tier_pages: Optional[int] = None,
                 tier_config: Optional[tier_mod.TierConfig] = None,
                 tier_faults=None,
                 attn_impl: str = "",
                 decode_overlap: bool = False,
                 ctx: Optional[pctx_mod.ParallelCtx] = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        if attn_impl:
            # route attention through the registry kernels ("pallas":
            # paged scalar-prefetch GQA/MLA decode + flash bucketed
            # prefill) instead of the default XLA ("xla") path — merged
            # into every serving-path ctx by the model
            self.model.impl_ctx = {"gqa_impl": attn_impl,
                                   "mla_impl": attn_impl}
        self.attn_impl = attn_impl
        # paged decode of MLA layers runs the paged_mla_decode kernel
        self._mla_kernel_decode = (paged and attn_impl == "pallas"
                                   and cfg.attention == "mla")
        self.ctx = ctx
        self.meshed = ctx is not None and ctx.mesh is not None
        self._param_shardings = None
        if self.meshed:
            from repro.parallel import sharding
            rules = sharding.serve_rules(
                "pod" in ctx.mesh.axis_names,
                ep_ftp=getattr(ctx, "ep_ftp", False))
            self._param_shardings = sharding.param_shardings(
                ctx.mesh, self.model.specs(), rules)
        # meshed engines draw each device's shard in place (sharded init)
        self.params = (params if params is not None
                       else self.model.init(jax.random.PRNGKey(seed),
                                            self._param_shardings))
        self._install_params()
        self.slots = slots
        self.max_len = max_len
        self.use_mtp = use_mtp and cfg.mtp is not None
        self.decode_overlap = decode_overlap
        if decode_overlap:
            # §2.3.1 dual-microbatch decode: the fused chunk runs the
            # slots as two anti-phase halves so each half's EP
            # all-to-alls overlap the other's dense compute
            if paged:
                raise ValueError(
                    "decode_overlap requires a dense cache: paged page "
                    "pools are shared across slots and cannot be split "
                    "into independent halves")
            if self.use_mtp:
                raise ValueError("decode_overlap is incompatible with "
                                 "use_mtp: the MTP draft ring is not "
                                 "split across halves")
            if slots % 2:
                raise ValueError(f"decode_overlap needs an even slot "
                                 f"count, got {slots}")
        self.chunk = chunk
        self.temperature = temperature
        self.top_k = top_k
        self.paged = paged
        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            if not paged:
                raise ValueError(
                    "prefill_chunk requires paged=True: chunked prefill "
                    "streams the prompt straight into the slot's pages")
            if prefill_chunk <= 0 or prefill_chunk % page_size:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must be a positive "
                    f"multiple of page_size ({page_size}) so every chunk "
                    "writes whole pages")
            if self.use_mtp:
                raise ValueError(
                    "prefill_chunk is incompatible with use_mtp: chunked "
                    "prefill does not populate the MTP draft ring")
        if paged:
            # block-pool cache: pool_pages defaults to the dense engine's
            # token capacity (slots * max_len worth of pages) — same
            # capacity, roughly half the bytes at fp8 storage; size it
            # smaller to oversubscribe slots against memory
            self.page_size = page_size
            self.pages_per_slot = max_len // page_size
            self.pool_pages = (pool_pages if pool_pages is not None
                               else slots * self.pages_per_slot)
            self.page_storage = page_storage
            self.cache = self.model.init_paged_cache(
                slots, max_len, page_size, self.pool_pages, page_storage)
            # refcounted page accounting + copy-on-write prefix index
            # (host-side; prefix sharing only activates under chunked
            # prefill, whose fixed chunk grid makes page contents a
            # bitwise-pure function of the token prefix)
            self._alloc = paged_mod.PrefixPageAllocator(self.pool_pages)
            self._slot_pages: List[List[int]] = [[] for _ in range(slots)]
            self._aux_axes = self.model.paged_aux_axes()
        else:
            self.cache = self.model.init_cache(slots, max_len)
        # host-memory KV page tier (ROADMAP 4): the device pool becomes a
        # cache over `host_tier_pages` of host capacity — suspended slots
        # spill whole page sets, warm refcount-0 prefix pages spill ahead
        # of reuse, and everything rides the staged §4.5 host hop on the
        # tick-clocked transfer model in serve/tier.py
        self.tier: Optional[paged_mod.HostPageTier] = None
        if host_tier_pages is not None:
            if not paged:
                raise ValueError("host_tier_pages requires paged=True: the "
                                 "tier spills page sets, dense rings have "
                                 "none")
            self.tier = paged_mod.HostPageTier(host_tier_pages)
        elif tier_faults is not None:
            raise ValueError("tier_faults without host_tier_pages: there "
                             "is no tier transfer path to inject into")
        self.tier_cfg = (tier_config if tier_config is not None
                         else tier_mod.TierConfig())
        self.tier_faults = (tier_faults if tier_faults is not None
                            else tier_mod.NullFaultHook())
        self._xfers = tier_mod.TransferClock(self.tier_cfg)
        # rid -> suspension entry; insertion order is the resume order
        self._suspended: "collections.OrderedDict[int, Dict[str, Any]]" = \
            collections.OrderedDict()
        self._spilling_slots: Dict[int, int] = {}   # slot -> rid
        self._slot_tick0 = np.zeros((slots,), np.int64)
        self._tick = 0
        self.tstats = {"suspensions": 0, "resumes": 0, "spilled_pages": 0,
                       "fetched_pages": 0, "spill_bytes": 0,
                       "fetch_bytes": 0, "prefetch_stalls": 0,
                       "degraded": 0, "crc_failures": 0, "spill_aborts": 0,
                       "tier_full_refusals": 0, "peak_resident_pages": 0,
                       "prefix_spilled": 0, "prefix_fetched": 0}
        self._cache_shardings = None
        self._state_shardings = None
        self._tok_sharding = None
        if self.meshed:
            self._install_mesh()
        # host mirrors of the on-device per-slot state (int32: jnp.asarray
        # would silently downcast int64 under x64-disabled jax)
        self.positions = np.zeros((slots,), np.int32)   # next position
        self._tokens = np.zeros((slots,), np.int32)     # last emitted token
        self._left = np.zeros((slots,), np.int32)       # decode budget
        self._eos = np.full((slots,), -1, np.int32)
        # per-slot sampling PRNG: base key + next stream index. Sampling
        # key for a token is fold_in(rngs[i], tix[i]) — a pure function of
        # (request seed, stream position), so retried requests reproduce
        self._rngs = np.zeros((slots, 2), np.uint32)
        self._tix = np.zeros((slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self.pending: Deque[Tuple[Request, Optional[Dict]]] = \
            collections.deque()
        self.max_pending = max_pending
        # scheduler state: slots mid-chunked-prefill, continuations of
        # preempted residents still holding their indexed prefix pages,
        # per-slot extras for eviction re-admission, and the admission
        # skip-ahead counter for the starvation guard
        self._prefilling: Dict[int, Dict[str, Any]] = {}
        self._evicted: Dict[int, List[int]] = {}
        self._slot_extras: List[Optional[Dict]] = [None] * slots
        self._hol_skips = 0
        self._rng = jax.random.PRNGKey(seed + 1)
        self.stats = {"steps": 0, "tokens": 0, "accepted_drafts": 0,
                      "drafts": 0, "dispatches": 0, "prefills": 0,
                      "splices": 0, "first_tokens": 0, "page_admits": 0,
                      "page_releases": 0, "peak_pages_used": 0,
                      "chunk_prefills": 0, "evictions": 0,
                      # prompt positions prefilled, and positions computed
                      # (bucket or chunk padding included)
                      "prefill_tokens": 0, "prefill_padded_tokens": 0,
                      # fused decode dispatches, their lanes (slots x
                      # chunk each) and the tokens those lanes emitted
                      "decode_dispatches": 0, "decode_lane_steps": 0,
                      "decode_tokens": 0,
                      # paged_mla_decode's pages: those it computes on
                      # (each lane's live pages, every step of a chunk)
                      # and its whole grid (slots x pages_per_slot x
                      # chunk); 0 unless that kernel runs the decode
                      "decode_live_pages": 0, "decode_grid_pages": 0,
                      # bytes of the fp8 GEMM weights held quantized
                      "fp8_prequantized_bytes": self._fp8_weight_bytes}
        # jit caches + trace counters (tests assert retrace bounds)
        self._prefill_fns: Dict[int, Any] = {}
        self._prefill_traces = 0
        self._splice_traces = 0
        self._decode_traces = 0
        self._quant_traces = 0
        self._scatter_traces = 0
        self._release_traces = 0
        self._chunk_traces = 0
        self._table_traces = 0
        self._tier_gather_traces = 0
        self._tier_scatter_traces = 0
        self._tier_resume_traces = 0
        # meshed engines pin the cache/state out-shardings to the input
        # shardings: without the pin, GSPMD could hand back a re-sharded
        # cache and the next dispatch would see new input shardings and
        # retrace — breaking the compile-once trace-count contract
        cache_out = self._cache_shardings if self.meshed else None
        if paged:
            def quant(cache1):
                self._quant_traces += 1
                return self.model.prefill_to_pages(cache1, self.page_size,
                                                   self.page_storage)

            # the bucket-shaped prefill cache is dead once quantized into
            # the page wire payload, so donate it; the payload is a fresh
            # structure whose pool shardings the out-pinned scatter jit
            # imposes at admission — nothing to pin here
            # repro-lint: disable=R2-jit-contract -- donated; output is
            # the wire payload, not the pool cache
            self._quant_fn = jax.jit(
                quant, donate_argnums=(0,))

            def scatter(cache, pages, aux, ids, row, slot):
                self._scatter_traces += 1
                cache = self.model.admit_pages(cache, pages, ids, row, slot)
                if aux:
                    big = {k: cache[k] for k in aux}
                    cache.update(_splice(big, aux, slot, self._aux_axes))
                return cache

            self._scatter_fn = jax.jit(
                scatter, donate_argnums=(0,),
                out_shardings=cache_out)

            def release(cache, slot):
                self._release_traces += 1
                return self.model.release_slot_pages(cache, slot)

            self._release_fn = jax.jit(
                release, donate_argnums=(0,),
                out_shardings=cache_out)

            if self.prefill_chunk is not None:
                def chunk_prefill(params, cache, tokens, pos, lengths,
                                  row, slot):
                    self._chunk_traces += 1
                    return self.model.prefill_chunk(
                        params, cache, tokens, pos, lengths, row, slot,
                        pctx=self.ctx)

                # logits are a fresh (1,1,V) payload; the cache carries
                self._chunk_fn = jax.jit(
                    chunk_prefill, donate_argnums=(1,),
                    out_shardings=(None, cache_out) if self.meshed else None)

                def table_install(cache, row, slot):
                    self._table_traces += 1
                    table = cache["page_table"]
                    out = dict(cache)
                    out["page_table"] = jax.lax.dynamic_update_slice(
                        table, row[None].astype(table.dtype), (slot, 0))
                    return out

                self._table_fn = jax.jit(
                    table_install, donate_argnums=(0,),
                    out_shardings=cache_out)

            if self.tier is not None:
                # the three tier entry points, each compile-once: gather
                # reads a fixed pages_per_slot-wide id vector (trash-padded)
                # plus the slot's aux leaves; scatter installs a payload of
                # the same static width (no page-table change — FETCHING
                # pages hold bytes before any row references them); resume
                # installs the table row + aux splice when a slot frees up
                def tier_gather(cache, ids, slot):
                    self._tier_gather_traces += 1
                    pages = self.model.gather_pages(cache, ids)
                    aux = {}
                    if self._aux_axes:
                        aux = _slot_slice(
                            {k: cache[k] for k in self._aux_axes}, slot,
                            self._aux_axes)
                    return pages, aux

                gather_out = None
                if self.meshed:
                    from jax.sharding import NamedSharding, PartitionSpec
                    from repro.parallel import sharding as sh_mod
                    zids = np.zeros((self.pages_per_slot,), np.int32)
                    pay_s, aux_s = jax.eval_shape(tier_gather, self.cache,
                                                  zids, 0)
                    self._tier_gather_traces = 0   # eval_shape traced once
                    rep = NamedSharding(self.ctx.mesh, PartitionSpec())
                    gather_out = (
                        sh_mod.tier_payload_pspecs(
                            pay_s, self.ctx.mesh,
                            self.ctx.tp_axis or "model"),
                        jax.tree.map(lambda _: rep, aux_s))
                # repro-lint: disable=R2-jit-contract -- the cache is
                # only read, never donated: the suspended slot's gather
                # must keep decoding peers' pool buffer alive
                self._tier_gather_fn = jax.jit(tier_gather,
                                               out_shardings=gather_out)

                def tier_scatter(cache, pages, ids):
                    self._tier_scatter_traces += 1
                    return self.model.install_pages(cache, pages, ids)

                self._tier_scatter_fn = jax.jit(
                    tier_scatter, donate_argnums=(0,),
                    out_shardings=cache_out)

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
                    tier_resume, donate_argnums=(0,),
                    out_shardings=cache_out)
        else:
            axes = self.model.cache_batch_axes(slots, max_len)

            def splice(big, small, slot):
                self._splice_traces += 1
                return _splice(big, small, slot, axes)

            self._splice_fn = jax.jit(
                splice, donate_argnums=(0,),
                out_shardings=cache_out)

        def decode_chunk(params, cache, state):
            self._decode_traces += 1
            return self.model.decode_loop(
                params, cache, state, self.chunk,
                temperature=self.temperature, top_k=self.top_k,
                use_mtp=self.use_mtp, overlap=self.decode_overlap,
                pctx=self.ctx)

        decode_out = None
        if self.meshed:
            decode_out = (self._tok_sharding, self._tok_sharding,
                          self._cache_shardings, self._state_shardings)
        self._decode_fn = jax.jit(
            decode_chunk, donate_argnums=(1, 2),
            out_shardings=decode_out)

    # -- params and mesh install ---------------------------------------------
    def _install_params(self):
        """Place the params and quantize the fp8 GEMM weights once.

        Meshed engines shard the params per ``sharding.serve_rules`` —
        heads / dense matmuls / vocab TP over the model axis, experts EP
        on the model axis (the paper's decode deployment: no cross-node
        TP, attention data-parallel across the EP group). Each weight
        stack that ``linear`` sends to the fp8 GEMM
        (``layers.fp8_weights``) is then held as its E4M3 blocks and
        block scales (``fp8.BlockQuantized``), quantized here under one
        jit instead of inside every prefill and decode step; ``q`` keeps
        the weight's sharding. Pairs handed in by another engine pass
        through.
        """
        from repro.parallel import sharding
        is_pair = fp8_mod.is_quantized

        def pair(s, shape):
            return fp8_mod.BlockQuantized(
                s, sharding.block_scale_sharding(s, shape))

        if self.meshed:
            placed = jax.tree.map(
                lambda s, w: pair(s, w.scale.shape) if is_pair(w) else s,
                self._param_shardings, self.params)
            # caller-supplied params may live anywhere; engine-drawn
            # ones are already in place and this is a no-op
            # repro-lint: disable=R1-host-sync -- one-time mesh install
            # at engine construction, not a decode-loop transfer
            self.params = jax.device_put(self.params, placed)
        weights = Lyr.fp8_weights(self.params, self.cfg)
        if weights:
            def quantize(ws):
                return {k: fp8_mod.quantize_weight(w) for k, w in ws.items()}

            out = None
            if self.meshed:
                shapes = jax.eval_shape(quantize, weights)
                out = {k: pair(w.sharding, shapes[k].scale.shape)
                       for k, w in weights.items()}
            # the bf16 stacks stay the caller's (or are dropped): there is
            # no buffer to donate
            # repro-lint: disable=R2-jit-contract -- one-time build step
            done = jax.jit(quantize, out_shardings=out)(weights)
            self.params = jax.tree_util.tree_map_with_path(
                lambda path, w: done.get(jax.tree_util.keystr(path), w),
                self.params, is_leaf=is_pair)
        self._fp8_weight_bytes = sum(
            w.q.nbytes + w.scale.nbytes
            for w in jax.tree.leaves(self.params, is_leaf=is_pair)
            if is_pair(w))

    def _install_mesh(self):
        """Shard the engine's working set over ``ctx.mesh`` (the params
        were placed by :meth:`_install_params`):

        * dense cache per ``sharding.cache_pspecs`` (slot axis over dp,
          cache length over model), or the paged pools per
          ``sharding.paged_cache_pspecs`` (KV-head axes over model,
          scale sidebands + MLA latent pools + page table replicated —
          the page *allocator* stays host-side either way);
        * per-slot decode state per ``sharding.decode_state_shardings``
          (slot vectors + per-slot sampling keys over dp, chunk counters
          replicated).
        """
        from jax.sharding import NamedSharding

        from repro.parallel import sharding
        ctx = self.ctx
        mesh = ctx.mesh
        model_axis = ctx.tp_axis or "model"
        if self.paged:
            self._cache_shardings = sharding.paged_cache_pspecs(
                self.cache, mesh, ctx.dp_axes, model_axis)
        else:
            self._cache_shardings = sharding.cache_pspecs(
                self.cache, mesh, ctx.dp_axes, model_axis)
        # repro-lint: disable=R1-host-sync -- one-time mesh install at
        # engine construction, not a decode-loop transfer
        self.cache = jax.device_put(self.cache, self._cache_shardings)
        self._state_shardings = sharding.decode_state_shardings(
            mesh, self.slots, ctx.dp_axes)
        self._tok_sharding = NamedSharding(
            mesh, sharding.batch_pspec(mesh, self.slots, ctx.dp_axes,
                                       ndim=2))

    # -- introspection ------------------------------------------------------
    @property
    def compiled_prefill_buckets(self) -> List[int]:
        """Sorted bucket lengths with a compiled prefill program."""
        return sorted(self._prefill_fns)

    @property
    def trace_counts(self) -> Dict[str, int]:
        """How many times each jitted entry point has (re)traced — the
        compile-count contract: prefill ≤ #buckets, splice = 1,
        decode = 1 (paged engines: quant/scatter ≤ #buckets — page counts
        follow the bucket — and release = 1; chunked-prefill engines:
        chunk = 1 and table = 1, every chunk of every prompt shares one
        static (1, prefill_chunk) shape). Benchmarks/tests assert against
        this, not internals."""
        return {"prefill": self._prefill_traces,
                "splice": self._splice_traces,
                "decode": self._decode_traces,
                "quant": self._quant_traces,
                "scatter": self._scatter_traces,
                "release": self._release_traces,
                "chunk": self._chunk_traces,
                "table": self._table_traces,
                # tier engines: gather/scatter/resume each ≤ 1 — every
                # transfer pads to the static pages_per_slot width
                "tier_gather": self._tier_gather_traces,
                "tier_scatter": self._tier_scatter_traces,
                "tier_resume": self._tier_resume_traces}

    def decode_lowered_text(self) -> str:
        """StableHLO text of the fused decode chunk at this engine's
        shapes/shardings (``parallel/overlap.lowered_text``). Traces an
        inspection copy — the decode trace counter is restored so the
        compile-once contract stays assertable."""
        from repro.parallel import overlap
        n = self._decode_traces
        try:
            return overlap.lowered_text(self._decode_fn, self.params,
                                        self.cache, self._device_state())
        finally:
            self._decode_traces = n

    def decode_alltoall_bytes(self) -> int:
        """All-to-all bytes per layer-scan iteration of one decode step,
        read off the compiled lowering via
        ``parallel/overlap.collective_bytes`` — the paper's §4.3
        wire-byte accounting applied to the serving hot path (0 for
        unmeshed/local-MoE engines). serve_bench records this per EP impl
        so the ep_dedup < ep_flat claim is checkable from
        BENCH_serve.json."""
        from repro.parallel import overlap
        return overlap.collective_bytes(self.decode_lowered_text())

    # -- prefill ------------------------------------------------------------
    def _get_prefill(self, bucket: int):
        """Jitted prefill for one static (bucket, extra_slots) shape."""
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            # paged admission quantizes the bucket-shaped cache into pages,
            # so it needs no extra context slots; dense admission splices a
            # full max_len ring
            extra = 0 if self.paged else self.max_len - bucket

            def prefill(params, tokens, lengths, extras):
                self._prefill_traces += 1
                batch = {"tokens": tokens}
                batch.update(extras)
                return self.model.prefill(params, batch, extra_slots=extra,
                                          lengths=lengths, pctx=self.ctx)

            # params are shared by every bucket jit and the next request,
            # and tokens/lengths arrive as fresh host arrays: prefill has
            # no donatable buffer; the batch-1 payload's shardings are
            # imposed by the donated admission jits downstream
            # repro-lint: disable=R2-jit-contract -- nothing round-trips
            fn = jax.jit(prefill)
            self._prefill_fns[bucket] = fn
        return fn

    def prefill_request(self, req: Request, extras: Optional[Dict] = None):
        """Run bucketed prefill for one request; returns (first_token,
        payload). Dense engines: payload is a batch-1 cache that already
        has ``max_len`` context slots (extra_slots derived from the static
        bucket), so admission is a pure splice. Paged engines: payload is
        the quantized page pytree from ``Model.prefill_to_pages`` —
        the disaggregation wire format (fp8 pages + per-token scales).
        Used by admission here and by the disaggregated prefill pool.
        Requests with delivered tokens (scheduler continuations) prefill
        prompt+delivered and sample at the advanced stream offset."""
        prompt, _, offset = self._effective(req)
        L = len(prompt)
        bucket = bucket_length(L, self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :L] = prompt
        lengths = np.asarray([L], np.int32)
        self.stats["dispatches"] += 1
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += L
        self.stats["prefill_padded_tokens"] += bucket
        with _span("engine.prefill", rid=req.rid):
            logits, cache1 = self._get_prefill(bucket)(
                self.params, jnp.asarray(toks), jnp.asarray(lengths),
                extras or {})
            if self.paged:
                self.stats["dispatches"] += 1
                cache1 = self._quant_fn(cache1)
        # first token follows the same sampling policy as the fused loop:
        # stream index ``sample_offset`` of the request's seeded stream
        # (engine-rng split for seedless requests)
        from repro.models.api import sample_logits
        with _span("engine.sample_first", rid=req.rid):
            if req.seed is not None:
                sub = jax.random.fold_in(jax.random.PRNGKey(req.seed),
                                         offset)
            else:
                self._rng, sub = jax.random.split(self._rng)
            first = int(sample_logits(logits[0, -1], sub, self.temperature,
                                      self.top_k))
        return first, cache1

    # -- admission ----------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.active) if r is None]

    def free_pages(self) -> int:
        """Allocatable pages in the pool (0 for dense engines). Counts
        both plain free pages and refcount-0 pages parked in the prefix
        cache — the latter are reclaimed (LRU) when the plain pool runs
        dry, so both are real capacity."""
        return self._alloc.free_pages() if self.paged else 0

    def _effective(self, req: Request) -> Tuple[np.ndarray, int, int]:
        """Continuation-aware view of a request: ``(prompt, max_new,
        sample_offset)``. A request with delivered tokens (a preempted
        resident re-queued by the scheduler, or a gateway retry that kept
        ``out``) resumes as prompt+delivered with the remaining budget and
        an advanced stream offset — the seeded per-token sampling stream
        makes the resumed tail bitwise-identical."""
        prompt = np.asarray(req.prompt, np.int32)
        if req.out:
            prompt = np.concatenate(
                [prompt, np.asarray(req.out, np.int32)])
            return (prompt, req.max_new - len(req.out),
                    req.sample_offset + len(req.out))
        return prompt, req.max_new, req.sample_offset

    def pages_needed(self, req: Request) -> int:
        """Page budget a request reserves at admission: every position it
        can touch — prompt plus decode budget — rounded up to pages. The
        paged cache never ring-wraps, so this is also a hard bound."""
        return paged_mod.pages_for(len(req.prompt) + req.max_new,
                                   self.page_size)

    def _prefix_keys(self, prompt: np.ndarray) -> List[bytes]:
        """Index keys for a prompt's full pages (chunked-prefill engines)."""
        return paged_mod.prefix_keys(prompt, self.page_size,
                                     len(prompt) // self.page_size)

    def can_admit(self, req: Request) -> bool:
        """A slot is free and (paged engines) enough pool pages are too.
        Chunked-prefill engines probe the prefix index: a request whose
        leading pages are already resident needs fresh pages only from
        the divergence point."""
        if not self.free_slots():
            return False
        if not self.paged:
            return True
        if self.prefill_chunk is None:
            return self.pages_needed(req) <= self.free_pages()
        prompt, max_new, _ = self._effective(req)
        n = paged_mod.pages_for(len(prompt) + max_new, self.page_size)
        return self._alloc.can_admit(self._prefix_keys(prompt), n,
                                     self.prefill_chunk // self.page_size)

    def _validate_paged(self, req: Request):
        if not self.paged:
            return
        if len(req.prompt) + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + max_new "
                f"({req.max_new}) exceeds max_len ({self.max_len}); the "
                "paged cache never ring-wraps, so a request must fit its "
                "page-table capacity")
        if self.pages_needed(req) > self.pool_pages:
            raise ValueError(
                f"request {req.rid}: needs {self.pages_needed(req)} pages "
                f"but the pool only has {self.pool_pages}; it could never "
                "admit — grow pool_pages or shrink the request")

    def submit(self, req: Request, extras: Optional[Dict] = None):
        """Queue a request; ``step()`` admits it when a slot — and, for
        paged engines, enough pool pages — free up. With ``max_pending``
        set, a full queue raises ``AdmissionError`` (explicit
        backpressure) instead of growing without bound; rejection never
        reorders what was already queued."""
        self._validate_paged(req)
        if (self.max_pending is not None
                and len(self.pending) >= self.max_pending):
            raise AdmissionError(
                f"pending queue full: request {req.rid} rejected; "
                f"{len(self.pending)} queued >= max_pending "
                f"({self.max_pending}) — drive step() or route elsewhere")
        self.pending.append((req, extras))

    def add_request(self, req: Request, extras: Optional[Dict] = None):
        """Prefill + admit immediately. Raises when no slot is free."""
        self._validate_paged(req)
        free = self.free_slots()
        if not free:
            raise AdmissionError(
                f"no free slots: all {self.slots} slots are occupied; "
                "call step() until a request completes before admitting "
                "more, or use submit() to queue (see free_slots())")
        first, cache1 = self.prefill_request(req, extras)
        self.admit_prefilled(req, first, cache1, free[0])
        return first

    def admit_prefilled(self, req: Request, first: int, cache1,
                        slot: int, extras: Optional[Dict] = None):
        """Admit an already-prefilled request into ``slot``: one donated
        jitted splice of the prefill cache (dense), or a page reservation
        + quantized-page scatter + page-table install (paged), plus
        host-mirror bookkeeping. ``max_new`` counts new tokens after the
        prompt, so the first token (or an immediate EOS) can complete the
        request with zero decode steps — in that case the cache write is
        skipped entirely and no pages are reserved."""
        prompt, max_new, offset = self._effective(req)
        finishes = (max_new <= 1
                    or (req.eos is not None and first == req.eos))
        if self.paged and not finishes:
            # capacity check BEFORE any bookkeeping mutates, so a raise
            # leaves the request/stats re-admittable as-is
            n = paged_mod.pages_for(len(prompt) + max_new, self.page_size)
            if n > self.free_pages():
                raise AdmissionError(
                    f"no free pages: request {req.rid} needs {n}, pool has "
                    f"{self.free_pages()} of {self.pool_pages}; drive "
                    "step() until a request completes, or submit() to "
                    "queue (see free_pages())")
        req.out.append(first)
        self.stats["tokens"] += 1
        self.stats["first_tokens"] += 1
        if finishes:
            req.done = True
            return
        self.stats["dispatches"] += 1
        with _span("engine.scatter", rid=req.rid):
            if self.paged:
                alloc = self._alloc.alloc(n)
                self._slot_pages[slot] = alloc
                trash = self.pool_pages
                row = np.full((self.pages_per_slot,), trash, np.int32)
                row[:n] = alloc
                # prefill pages beyond the reserved range (bucket > request
                # budget) land in the trash page
                n_p = jax.tree.leaves(cache1["pages"])[0].shape[1]
                ids = np.asarray([alloc[i] if i < n else trash
                                  for i in range(n_p)], np.int32)
                self.stats["page_admits"] += 1
                used = self.pool_pages - self.free_pages()
                self.stats["peak_pages_used"] = max(
                    self.stats["peak_pages_used"], used)
                self.cache = self._scatter_fn(
                    self.cache, cache1["pages"], cache1["aux"],
                    jnp.asarray(ids), jnp.asarray(row), slot)
            else:
                self.stats["splices"] += 1
                self.cache = self._splice_fn(self.cache, cache1, slot)
        self.positions[slot] = len(prompt)
        self._tokens[slot] = first
        self._left[slot] = max_new - 1
        self._eos[slot] = -1 if req.eos is None else req.eos
        if req.seed is not None:
            base = jax.random.PRNGKey(req.seed)
        else:
            self._rng, base = jax.random.split(self._rng)
        self._rngs[slot] = np.asarray(base, np.uint32)
        self._tix[slot] = offset + 1   # prefill consumed
                                       # stream index offset
        self._slot_extras[slot] = extras
        self.active[slot] = req
        self._slot_tick0[slot] = self._tick

    # -- scheduler ----------------------------------------------------------
    def _admit_now(self, req: Request, extras: Optional[Dict]):
        slot = self.free_slots()[0]
        if self.prefill_chunk is not None:
            self._admit_chunked(req, extras, slot)
        else:
            first, cache1 = self.prefill_request(req, extras)
            self.admit_prefilled(req, first, cache1, slot, extras=extras)

    def _admit_chunked(self, req: Request, extras: Optional[Dict],
                       slot: int):
        """Reserve pages (claiming any indexed prefix run) and install the
        slot's page-table row; the prompt itself streams through
        ``_run_prefill_chunk`` one chunk per ``step()``. Pages claimed
        from the prefix index are shared and immutable — the chunks that
        would have computed them are skipped, and fresh pages take over
        from the divergence point (the copy-on-write fork)."""
        if extras:
            raise ValueError(
                "prefill_chunk admission does not support extras "
                "(encoder/vision payloads need whole-prompt prefill)")
        prompt, max_new, offset = self._effective(req)
        L, p, C = len(prompt), self.page_size, self.prefill_chunk
        n = paged_mod.pages_for(L + max_new, p)
        keys = self._prefix_keys(prompt)
        held = self._evicted.pop(req.rid, None)
        if held is not None:
            # a resuming continuation re-claims its retained prefix pages
            # through the index below (they stay indexed, so the admit()
            # hit run picks them straight back up)
            self._alloc.release(held)
        try:
            hits, fresh = self._alloc.admit(keys, n, C // p)
        except RuntimeError as e:
            raise AdmissionError(
                f"no free pages: request {req.rid} needs up to {n}, pool "
                f"has {self.free_pages()} of {self.pool_pages}; drive "
                "step() until a request completes, or submit() to "
                "queue (see free_pages())") from e
        pages = hits + fresh
        self._slot_pages[slot] = pages
        self._slot_extras[slot] = extras
        trash = self.pool_pages
        row = np.full((self.pages_per_slot,), trash, np.int32)
        row[:n] = pages
        self.stats["page_admits"] += 1
        used = self.pool_pages - self.free_pages()
        self.stats["peak_pages_used"] = max(
            self.stats["peak_pages_used"], used)
        # the row travels as a chunk operand; the cache's own row keeps
        # pointing at the trash page until graduation, so this slot's
        # masked lane in the decode dispatches interleaved with the
        # remaining chunks cannot write into the pages being filled
        # shared pages cover whole chunks, so prefill resumes at the
        # divergence chunk; never skip past the chunk holding the last
        # prompt token — its logits seed the first sampled token (a full
        # re-run of that chunk writes bitwise-identical bytes back into
        # any shared pages it overlaps)
        skip = min(len(hits) * p, (L - 1) // C * C)
        self._prefilling[slot] = dict(req=req, keys=keys, next=skip,
                                      prompt=prompt, max_new=max_new,
                                      offset=offset, row=row)
        self.active[slot] = req
        self._slot_tick0[slot] = self._tick
        if self.tier is not None:
            self._probe_tier_prefix(slot, hits, fresh, keys, L, skip)

    def _probe_tier_prefix(self, slot: int, hits: List[int],
                           fresh: List[int], keys: List[bytes], L: int,
                           skip: int):
        """Extend a chunked admission's shared-prefix run with host-tier
        prefix pages: pages past the device hit run that the tier holds
        are fetched into the slot's fresh pages instead of recomputed.
        The prefill cursor only advances when the fetch lands CRC-clean
        (``_finish_prefix_fetch``); until then the slot waits — decode
        never reads a page before its bytes are installed."""
        p, C = self.page_size, self.prefill_chunk
        ppc = C // p
        h = len(hits)
        if skip != h * p:
            return   # device hits already reach the final-chunk bound
        bound_pages = ((L - 1) // C * C) // p
        run = min(self.tier.prefix_run(keys[h:], ppc),
                  bound_pages - h) // ppc * ppc
        if run <= 0:
            return
        tkeys = keys[h:h + run]
        stored = self.tier.take_prefix(tkeys)
        ps = self._prefilling[slot]
        ps["tier_xfer"] = True
        self._xfers.submit(
            tier_mod.PREFIX_FETCH, ps["req"].rid, None,
            sum(paged_mod.payload_nbytes(pg) for pg, _ in stored),
            slow=self.tier_faults.slow(), slot=slot, req=ps["req"],
            keys=tkeys, stored=stored, pages=fresh[:run],
            end=(h + run) * p)

    def _run_prefill_chunk(self, slot: int):
        """Advance one prefilling slot by one chunk; the final chunk
        samples the first token and graduates the slot to decoding."""
        ps = self._prefilling[slot]
        req, prompt = ps["req"], ps["prompt"]
        C, p, L = self.prefill_chunk, self.page_size, len(prompt)
        start = ps["next"]
        toks = np.zeros((1, C), np.int32)
        end = min(L, start + C)
        toks[0, :end - start] = prompt[start:end]
        pos = np.arange(start, start + C, dtype=np.int32)[None]
        self.stats["dispatches"] += 1
        self.stats["chunk_prefills"] += 1
        self.stats["prefill_tokens"] += end - start
        self.stats["prefill_padded_tokens"] += C
        with _span("engine.prefill", rid=req.rid):
            logits, self.cache = self._chunk_fn(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos), jnp.asarray([L], jnp.int32),
                jnp.asarray(ps["row"][None]), slot)
        # index the chunk's freshly-written full prompt pages: their
        # content is a bitwise-pure function of the token prefix under the
        # fixed chunk grid, and the write is already dispatched, so device
        # ordering guarantees write-before-any-sharer-read
        for j in range(start // p, min((start + C) // p, len(ps["keys"]))):
            self._alloc.register(ps["keys"][j], self._slot_pages[slot][j])
        ps["next"] = start + C
        if ps["next"] < L:
            return
        del self._prefilling[slot]
        # graduation: the slot decodes from the next dispatch on, so its
        # real page-table row replaces the trash row now
        self.stats["dispatches"] += 1
        with _span("engine.scatter", rid=req.rid):
            self.cache = self._table_fn(self.cache, jnp.asarray(ps["row"]),
                                        slot)
        from repro.models.api import sample_logits
        with _span("engine.sample_first", rid=req.rid):
            if req.seed is not None:
                sub = jax.random.fold_in(jax.random.PRNGKey(req.seed),
                                         ps["offset"])
                base = jax.random.PRNGKey(req.seed)
            else:
                self._rng, sub = jax.random.split(self._rng)
                self._rng, base = jax.random.split(self._rng)
            first = int(sample_logits(logits[0, -1], sub, self.temperature,
                                      self.top_k))
        req.out.append(first)
        self.stats["tokens"] += 1
        self.stats["first_tokens"] += 1
        if ps["max_new"] <= 1 or (req.eos is not None and first == req.eos):
            # zero decode steps: the whole reservation (including the
            # never-touched budget tail) goes straight back to the pool
            req.done = True
            self._release_slot(slot)
            return
        self.positions[slot] = L
        self._tokens[slot] = first
        self._left[slot] = ps["max_new"] - 1
        self._eos[slot] = -1 if req.eos is None else req.eos
        self._rngs[slot] = np.asarray(base, np.uint32)
        self._tix[slot] = ps["offset"] + 1
        self._slot_tick0[slot] = self._tick   # quantum clock: decode start

    def _pick_admission(self) -> Optional[int]:
        """Index of the pending entry to admit next: highest priority
        first, FIFO within a class, with page-aware skip-ahead — a
        page-blocked request lets smaller ones jump it until the
        starvation guard trips, after which only the head may admit."""
        order = sorted(range(len(self.pending)),
                       key=lambda i: (-self.pending[i][0].priority, i))
        for rank, i in enumerate(order):
            if self.can_admit(self.pending[i][0]):
                if rank == 0:
                    self._hol_skips = 0
                elif self._hol_skips >= STARVATION_LIMIT:
                    return None   # head starved: next pages are its
                else:
                    self._hol_skips += 1
                return i
        return None

    def _try_evict(self, inc: int) -> bool:
        """Free capacity for an incoming priority-``inc`` request: evict
        the lowest-priority resident whose priority is strictly lower,
        or — when no resident qualifies — abort the fetch of a
        strictly-lower-priority suspended entry (its host copy survives;
        the fetch restarts later), or reclaim the retained prefix pages
        of a strictly-lower-priority evicted continuation (it will
        re-prefill; its token stream stays bitwise-identical either way).
        Tiered engines prefer *spilling* the victim over evicting it —
        its KV moves to the host instead of being recomputed — in which
        case this returns False: the capacity arrives asynchronously when
        the spill lands, and the caller must not keep preempting for the
        same arrival this tick."""
        victims = [(self.active[s].priority, s) for s in range(self.slots)
                   if self.active[s] is not None
                   and s not in self._prefilling
                   and s not in self._spilling_slots
                   and self.active[s].priority < inc]
        if victims:
            slot = min(victims)[1]
            if self.tier is not None and self._begin_suspend(slot):
                return False
            self._evict_slot(slot)
            return True
        if self.tier is not None:
            fetching = [(e["req"].priority, rid)
                        for rid, e in self._suspended.items()
                        if e["state"] == "fetching"
                        and e["req"].priority < inc]
            if fetching:
                rid = min(fetching)[1]
                e = self._suspended[rid]
                self._xfers.cancel(lambda t: t.rid == rid
                                   and t.kind == tier_mod.FETCH)
                self.tier.abort_fetch(e["eid"])
                self._alloc.release(e["fetch_pages"])
                e["fetch_pages"], e["tier_entry"] = None, None
                e["state"] = "host"
                return True
        held = [(req.priority, i) for i, (req, _) in
                enumerate(self.pending)
                if req.priority < inc and req.rid in self._evicted]
        if held:
            rid = self.pending[min(held)[1]][0].rid
            self._alloc.release(self._evicted.pop(rid))
            return True
        return False

    def _evict_slot(self, slot: int):
        """Preempt a resident: free its slot and pages and push it back
        to pending as a continuation (prompt+delivered, remaining budget,
        advanced stream offset — the seeded sampling stream makes the
        resumed tail bitwise-identical). Under chunked prefill the
        continuation's full written pages are indexed first and their
        refcounts retained in ``_evicted``, so resume re-claims the KV it
        already computed instead of recomputing it."""
        req = self.active[slot]
        extras = self._slot_extras[slot]
        held: List[int] = []
        if self.paged and self.prefill_chunk is not None:
            pages = self._slot_pages[slot]
            prompt, _, _ = self._effective(req)
            # KV coverage stops at the *written* prefix: the last emitted
            # token's KV lands only when it is fed, so positions[slot]
            # (== len(prompt+out) - 1) bounds the indexable pages
            n_keys = min(int(self.positions[slot]) // self.page_size,
                         len(pages))
            keys = paged_mod.prefix_keys(prompt, self.page_size, n_keys)
            for j, key in enumerate(keys):
                self._alloc.register(key, pages[j])
                if self._alloc.lookup(key) != pages[j]:
                    break   # another slot owns this prefix from here on
                held.append(pages[j])
            if held:
                self._evicted[req.rid] = held
                self._slot_pages[slot] = pages[len(held):]
        self.stats["evictions"] += 1
        self._release_slot(slot)
        self.pending.appendleft((req, extras))

    def _admit_pending(self) -> int:
        admitted = 0
        while self.pending:
            i = self._pick_admission()
            if i is not None:
                req, extras = self.pending[i]
                del self.pending[i]
                self._admit_now(req, extras)
                admitted += 1
                continue
            # Everything admissible is in; preempt for the
            # highest-priority blocked entry. Capacity freed here is
            # reserved for that entry alone — letting a lower-priority
            # request (often the just-evicted victim, cheap to resume
            # via its retained prefix) grab it would thrash.
            head_i = max(range(len(self.pending)),
                         key=lambda j: (self.pending[j][0].priority, -j))
            head = self.pending[head_i][0]
            if not self._try_evict(head.priority):
                break
            while not self.can_admit(head) and self._try_evict(head.priority):
                pass
            if not self.can_admit(head):
                break
            # indices shifted (eviction re-queues at the left): relocate
            # the head by identity before admitting it
            head_i = next(j for j, (q, _) in enumerate(self.pending)
                          if q is head)
            req, extras = self.pending[head_i]
            del self.pending[head_i]
            self._admit_now(req, extras)
            admitted += 1
        return admitted

    # -- host page tier (ISSUE 9 / §4.5 memory hierarchy) --------------------
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
        PR 8 evict-and-requeue rung)."""
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
        trash = self.pool_pages
        ids = np.asarray(pages + [trash] * (self.pages_per_slot - n),
                         np.int32)
        self.stats["dispatches"] += 1
        pay_dev, aux_dev = self._tier_gather_fn(self.cache,
                                                jnp.asarray(ids), slot)
        payload = tier_mod.trim_pages(tier_mod.staged_get(pay_dev), n)
        aux = tier_mod.staged_get(aux_dev)
        crcs = paged_mod.payload_page_crcs(payload, n)
        aux_crc = paged_mod.payload_crc(aux)
        nbytes = (paged_mod.payload_nbytes(payload)
                  + paged_mod.payload_nbytes(aux))
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
        self._xfers.submit(tier_mod.SPILL, req.rid, eid, nbytes,
                           slow=self.tier_faults.slow())
        self._suspended[req.rid] = dict(
            req=req, extras=self._slot_extras[slot], state="spilling",
            eid=eid, n=n, slot=slot, pages=None, fetch_pages=None,
            tier_entry=None, payload=payload, aux=aux, crcs=crcs,
            aux_crc=aux_crc, mirrors=mirrors)
        self._spilling_slots[slot] = req.rid
        self.tstats["suspensions"] += 1
        return True

    def _finish_spill(self, t: tier_mod.TierTransfer):
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

    def _fail_spill(self, t: tier_mod.TierTransfer):
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
        pages = self._slot_pages[slot]
        trash = self.pool_pages
        row = np.full((self.pages_per_slot,), trash, np.int32)
        row[:len(pages)] = pages
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
        if self.tier is None:
            return
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
            nbytes = (paged_mod.payload_nbytes(ent.payload)
                      + paged_mod.payload_nbytes(ent.aux))
            self._xfers.submit(tier_mod.FETCH, rid, e["eid"], nbytes,
                               slow=self.tier_faults.slow())

    def _finish_fetch(self, t: tier_mod.TierTransfer):
        """A fetch landed: CRC-check the host bytes, scatter them into the
        reserved device pages, and mark the entry ready to resume the
        moment a slot frees. CRC mismatch walks the degradation ladder."""
        e = self._suspended.get(t.rid)
        if e is None or e["state"] != "fetching":
            return
        ent, n = e["tier_entry"], e["n"]
        if (paged_mod.payload_page_crcs(ent.payload, n) != ent.crcs
                or paged_mod.payload_crc(ent.aux) != ent.aux_crc):
            self.tstats["crc_failures"] += 1
            self._degrade(t.rid)
            return
        pages = e["fetch_pages"]
        trash = self.pool_pages
        ids = np.asarray(pages + [trash] * (self.pages_per_slot - n),
                         np.int32)
        payload = tier_mod.pad_pages(ent.payload, self.pages_per_slot)
        self.stats["dispatches"] += 1
        self.cache = self._tier_scatter_fn(
            self.cache, tier_mod.staged_put(payload), jnp.asarray(ids))
        e["aux"] = ent.aux
        e["pages"], e["fetch_pages"] = pages, None
        e["tier_entry"] = None
        e["state"] = "ready"
        self.tier.free(e["eid"])
        self.tstats["fetched_pages"] += n
        self.tstats["fetch_bytes"] += t.nbytes

    def _degrade(self, rid: int):
        """Unrecoverable fetch (retries exhausted / timeout / CRC): drop
        the tiered copy and re-queue the request as a PR 7-style
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
            trash = self.pool_pages
            row = np.full((self.pages_per_slot,), trash, np.int32)
            row[:len(pages)] = pages
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
        re-prefilling (PR 8's evict) or starving the queue."""
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
        trash = self.pool_pages
        ids = np.asarray([pid for pid, _ in harvested]
                         + [trash] * (self.pages_per_slot - len(harvested)),
                         np.int32)
        self.stats["dispatches"] += 1
        pay_dev, _ = self._tier_gather_fn(self.cache, jnp.asarray(ids), 0)
        payload = tier_mod.trim_pages(tier_mod.staged_get(pay_dev),
                                      len(harvested))
        self._xfers.submit(tier_mod.PREFIX_SPILL, None, None,
                           paged_mod.payload_nbytes(payload),
                           slow=self.tier_faults.slow(),
                           harvest=harvested, payload=payload)

    def _finish_prefix_spill(self, t: tier_mod.TierTransfer):
        for j, (pid, key) in enumerate(t.meta["harvest"]):
            pg = tier_mod.slice_page(t.meta["payload"], j)
            self.tier.put_prefix(key, pg, paged_mod.payload_crc(pg))
        self._alloc.release([pid for pid, _ in t.meta["harvest"]])
        self.tstats["prefix_spilled"] += len(t.meta["harvest"])
        self.tstats["spill_bytes"] += t.nbytes

    def _fail_prefix_spill(self, t: tier_mod.TierTransfer):
        # the device copy never left: re-index the pages (release parks
        # them back in the warm cache) and count the abort
        for pid, key in t.meta["harvest"]:
            self._alloc.register(key, pid)
        self._alloc.release([pid for pid, _ in t.meta["harvest"]])
        self.tstats["spill_aborts"] += 1

    def _finish_prefix_fetch(self, t: tier_mod.TierTransfer):
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
               if paged_mod.payload_crc(pg) != crc]
        if bad:
            self.tstats["crc_failures"] += 1
            for j in bad:
                self.tier.drop_prefix(m["keys"][j])
            return
        trash = self.pool_pages
        pages = m["pages"]
        ids = np.asarray(pages + [trash] * (self.pages_per_slot
                                            - len(pages)), np.int32)
        payload = tier_mod.pad_pages(
            tier_mod.concat_pages([pg for pg, _ in m["stored"]]),
            self.pages_per_slot)
        self.stats["dispatches"] += 1
        self.cache = self._tier_scatter_fn(
            self.cache, tier_mod.staged_put(payload), jnp.asarray(ids))
        for j, key in enumerate(m["keys"]):
            self._alloc.register(key, pages[j])
        ps["next"] = m["end"]
        self.tstats["prefix_fetched"] += len(pages)
        self.tstats["fetch_bytes"] += t.nbytes

    def _fail_prefix_fetch(self, t: tier_mod.TierTransfer):
        ps = self._prefilling.get(t.meta["slot"])
        if ps is not None and ps.get("req") is t.meta["req"]:
            ps["tier_xfer"] = False   # cursor untouched: chunks recompute

    def _advance_transfers(self):
        done, failed = self._xfers.advance(self.tier_faults)
        for t in done:
            if t.kind == tier_mod.SPILL:
                self._finish_spill(t)
            elif t.kind == tier_mod.FETCH:
                self._finish_fetch(t)
            elif t.kind == tier_mod.PREFIX_SPILL:
                self._finish_prefix_spill(t)
            elif t.kind == tier_mod.PREFIX_FETCH:
                self._finish_prefix_fetch(t)
        for t in failed:
            if t.kind == tier_mod.SPILL:
                self._fail_spill(t)
            elif t.kind == tier_mod.FETCH:
                self._degrade(t.rid)
            elif t.kind == tier_mod.PREFIX_SPILL:
                self._fail_prefix_spill(t)
            elif t.kind == tier_mod.PREFIX_FETCH:
                self._fail_prefix_fetch(t)

    # -- decode -------------------------------------------------------------
    def _device_state(self) -> Dict[str, Any]:
        # built field-for-field like Model.init_decode_state (the canonical
        # structure; pinned by a test) without paying its allocations —
        # donation invalidates reused buffers, so the chunk counters must
        # be fresh scalars each step anyway
        st = dict(
            tokens=jnp.asarray(self._tokens),
            positions=jnp.asarray(self.positions),
            # slots mid-chunked-prefill are occupied but not yet decoding,
            # and mid-spill slots hold captured-in-flight pages: both are
            # masked out of the fused loop
            active=jnp.asarray(np.array(
                [r is not None and i not in self._prefilling
                 and i not in self._spilling_slots
                 for i, r in enumerate(self.active)])),
            left=jnp.asarray(self._left),
            eos=jnp.asarray(self._eos),
            rngs=jnp.asarray(self._rngs),
            tix=jnp.asarray(self._tix),
            drafts=jnp.zeros((), jnp.int32),
            accepted=jnp.zeros((), jnp.int32),
        )
        if self.meshed:
            # repro-lint: disable=R1-host-sync -- per-chunk dispatch
            # point: tiny per-slot scalars committed onto their mesh
            # shardings so every dispatch sees identical input shardings
            st = jax.device_put(st, self._state_shardings)
        return st

    def step(self):
        """One scheduler tick: admit from the pending queue (priority
        order, page-aware, preempting lower-priority residents when a
        higher-priority arrival is blocked), advance one chunked-prefill
        slot by one chunk, then run one fused ``chunk``-step decode
        dispatch over the decoding slots.

        Tiered engines prepend the tier phases: advance the transfer
        clock (landing spills frees slots/pages, landing fetches readies
        resumes), admit, resume fetched entries into leftover slots,
        rotate a quantum-expired resident out for waiters, start
        prefetches with leftover pages, and harvest cold prefix pages —
        then decode as usual with spilling slots masked out. Fetches are
        restarted once more after decode so pages freed by completions
        this tick are already in flight by the next.

        The tick runs under the profiler span ``engine.step``, its phases
        under ``engine.admit`` (holding ``engine.prefill``,
        ``engine.sample_first`` and ``engine.scatter`` per refill, each
        with the request's ``rid``), ``engine.decode``, ``engine.sync``
        and ``engine.harvest`` (docs/serving.md, Tracing)."""
        with _span("engine.step"):
            self._step()

    def _step(self):
        if self.tier is not None:
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
        else:
            with _span("engine.admit"):
                self._admit_pending()
        if self._prefilling:
            # one chunk for one long-prompt admission per tick, so
            # resident decode streams keep flowing between chunks (no
            # TTFT cliff for requests queued behind a long prompt);
            # slots whose prefix pages are inbound from the tier wait
            runnable = [s for s in self._prefilling
                        if not self._prefilling[s].get("tier_xfer")]
            if runnable:
                self._run_prefill_chunk(min(runnable))
        if not any(r is not None and i not in self._prefilling
                   and i not in self._spilling_slots
                   for i, r in enumerate(self.active)):
            return
        self.stats["dispatches"] += 1
        self.stats["decode_dispatches"] += 1
        self.stats["decode_lane_steps"] += self.slots * self.chunk
        with _span("engine.decode"):
            toks, emitted, self.cache, st = self._decode_fn(
                self.params, self.cache, self._device_state())
        with _span("engine.sync"):
            # single host sync per chunk: emitted tokens + updated slot
            # state — THE allowlisted dispatch point (1/chunk dispatches
            # per token, asserted by tests/test_serve_fused.py and
            # BENCH_serve.json)
            # repro-lint: disable=R1-host-sync -- the one sync per chunk
            toks, emitted, host = jax.device_get(
                (toks, emitted, {k: st[k] for k in
                                 ("tokens", "positions", "active", "left",
                                  "tix", "drafts", "accepted")}))
        with _span("engine.harvest"):
            self._harvest(toks, emitted, host)

    def _harvest(self, toks, emitted, host):
        """Fold one decode chunk's host copy into the engine: counters,
        the per-slot mirrors, each request's tokens, and the release of
        finished slots."""
        self.stats["steps"] += int(emitted.any(axis=0).sum())
        self.stats["decode_tokens"] += int(emitted.sum())
        if self._mla_kernel_decode:
            # each step's qpos: the dispatched positions, advanced by the
            # lane's active steps before it; the kernel's rule computes
            # pages 0..max(qpos, 0) // page of every lane
            qpos = (self.positions[:, None] + np.cumsum(emitted, axis=1)
                    - emitted)
            last = np.minimum(np.maximum(qpos, 0) // self.page_size,
                              self.pages_per_slot - 1)
            self.stats["decode_live_pages"] += int((last + 1).sum())
            self.stats["decode_grid_pages"] += (
                self.slots * self.pages_per_slot * self.chunk)
        self.stats["drafts"] += int(host["drafts"])
        self.stats["accepted_drafts"] += int(host["accepted"])
        # copy: device_get arrays are read-only, mirrors are written on
        # admit. Prefilling and mid-spill slots keep their host-written
        # mirrors — their masked decode lanes carry stale device state
        # (a spilling slot's authoritative mirrors ride its tier entry)
        keep = np.array([i in self._prefilling or i in self._spilling_slots
                         for i in range(self.slots)])
        self._tokens = np.where(keep, self._tokens,
                                host["tokens"]).astype(np.int32)
        self.positions = np.where(keep, self.positions,
                                  host["positions"]).astype(np.int32)
        self._left = np.where(keep, self._left,
                              host["left"]).astype(np.int32)
        self._tix = np.where(keep, self._tix,
                             host["tix"]).astype(np.int32)
        for i, r in enumerate(self.active):
            if r is None or keep[i]:
                continue
            new = toks[i, emitted[i]]
            r.out.extend(int(t) for t in new)
            self.stats["tokens"] += int(new.size)
            if not host["active"][i]:
                r.done = True
                self._release_slot(i)
        if self.tier is not None:
            # pages freed by completions this tick feed the prefetch
            # schedule immediately: the fetch lands on next tick's clock
            # advance, before the freed slot is rescheduled — the no-stall
            # overlap the serve_bench gate asserts
            self._start_fetches()

    def _release_slot(self, slot: int):
        """Free ``slot``: clear occupancy and (paged) drop one reference
        per reserved page — the whole reservation, including any
        never-written budget tail left by early EOS, returns to the pool
        at once. The slot's table row is re-pointed at the trash page so
        its masked decode lane can't write into a new owner's pages."""
        self.active[slot] = None
        self._slot_extras[slot] = None
        if self.paged and self._slot_pages[slot]:
            self._alloc.release(self._slot_pages[slot])
            self._slot_pages[slot] = []
            self.stats["dispatches"] += 1
            self.stats["page_releases"] += 1
            self.cache = self._release_fn(self.cache, slot)

    def cancel(self, rid: int) -> bool:
        """Abort a request by id: drop it from the pending queue (an
        evicted-but-not-resumed continuation also releases the prefix
        refcounts it retained), free its slot — mid-chunked-prefill or
        decoding alike (pages recycled; the lane is masked out of the
        next dispatch) — or, on tiered engines, unwind whichever tier
        state it is in (SPILLING/HOST/FETCHING/ready): device and host
        pages both free and any in-flight transfer is dropped from the
        clock. The Request object is left as-is — ``done`` stays False,
        ``out`` keeps whatever was delivered — so a gateway can
        re-dispatch it as a continuation. Returns False if unknown."""
        for i, (req, _) in enumerate(self.pending):
            if req.rid == rid:
                del self.pending[i]
                held = self._evicted.pop(rid, None)
                if held:
                    self._alloc.release(held)
                return True
        e = self._suspended.pop(rid, None)
        if e is not None:
            self._xfers.cancel(lambda t: t.rid == rid)
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
        for slot, req in enumerate(self.active):
            if req is not None and req.rid == rid:
                self._prefilling.pop(slot, None)
                if self.tier is not None:
                    self._xfers.cancel(lambda t: t.rid == rid)
                self._release_slot(slot)
                return True
        return False

    def pool_stats(self) -> Dict[str, Any]:
        """Page-pool occupancy (zeros for dense engines). Tiered engines
        add the host side so capacity dashboards see both levels of the
        hierarchy."""
        if not self.paged:
            return dict(pages_total=0, pages_free=0, pages_used=0,
                        occupancy=0.0)
        free = self.free_pages()
        used = self.pool_pages - free
        out = dict(pages_total=self.pool_pages,
                   pages_free=free, pages_used=used,
                   occupancy=used / self.pool_pages if self.pool_pages
                   else 0.0)
        if self.tier is not None:
            out.update(host_pages_total=self.tier.capacity_pages,
                       host_pages_free=self.tier.free_pages(),
                       host_occupancy=self.tier.occupancy())
        return out

    def tier_stats(self) -> Dict[str, Any]:
        """Host-tier residency and transfer counters (``tstats`` plus the
        live tier/clock occupancy). Meaningful only on tiered engines;
        returns the zeroed counters otherwise so callers can read it
        unconditionally."""
        out = dict(self.tstats)
        if self.tier is None:
            out.update(host_pages_total=0, host_pages_used=0,
                       host_pages_free=0, host_occupancy=0.0,
                       host_prefix_pages=0, suspended=0,
                       transfers_inflight=0, retries=0, timeouts=0)
            return out
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

    def prefix_stats(self) -> Dict[str, Any]:
        """Prefix-index effectiveness (zeros for dense / non-chunked
        engines): admission-time page lookups vs hits, plus how many
        pages currently back index entries. The gateway's cache-aware
        router reads this to weigh prefix affinity against load."""
        if not self.paged:
            return dict(lookups=0, hits=0, hit_rate=0.0, indexed_pages=0)
        lk = self._alloc.prefix_lookups
        out = dict(lookups=lk, hits=self._alloc.prefix_hits,
                   hit_rate=self._alloc.prefix_hits / lk if lk else 0.0,
                   indexed_pages=self._alloc.indexed_pages())
        if self.tier is not None:
            out.update(tier_prefix_pages=self.tier.prefix_pages(),
                       tier_prefix_evictions=self.tier.prefix_evictions,
                       tier_prefix_fetched=self.tstats["prefix_fetched"])
        return out

    def cache_bytes_per_token(self) -> float:
        """Attention-cache bytes per token of context capacity — the
        paper's Table 1 lever. Dense: ring buffers (values + pos) over
        ``slots * max_len`` tokens. Paged: pool pages (values + scales,
        trash page excluded) over ``pool_pages * page_size`` tokens, plus
        the page-table overhead (4/page_size bytes/token)."""
        segs = self.model.segments
        if self.paged:
            per_page = sum(
                leaf.nbytes / (self.pool_pages + 1)
                for seg in segs
                for leaf in jax.tree.leaves(self.cache[seg.name]))
            per_tok = per_page / self.page_size
            return per_tok + self.cache["page_table"].nbytes / (
                self.slots * self.max_len)
        total = sum(leaf.nbytes for seg in segs
                    for leaf in jax.tree.leaves(self.cache[seg.name]))
        return total / (self.slots * self.max_len)

    def has_work(self) -> bool:
        """Whether another ``step()`` can make progress: queued or
        resident requests, suspended entries parked in the host tier, or
        transfers still on the clock. Drivers (``run_until_done``, the
        gateway's idle check) must use this rather than pending/active
        alone — a tiered engine with every request suspended looks idle
        by the old test but still owes those requests their resumes."""
        return (bool(self.pending)
                or any(r is not None for r in self.active)
                or bool(self._suspended)
                or bool(self._xfers.inflight))

    def run_until_done(self, max_steps: int = 1000):
        """Drive chunks until every submitted/admitted request completes.
        ``max_steps`` bounds the number of fused chunks."""
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()

    def acceptance_rate(self) -> float:
        d = self.stats["drafts"]
        return self.stats["accepted_drafts"] / d if d else 0.0
