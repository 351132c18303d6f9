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
from repro.core import mla as mla_mod
from repro.core import paged as paged_mod
from repro.kernels.paged_attention.paged_attention import live_pages
from repro.models import layers as Lyr
from repro.models.api import Model, build_model
from repro.parallel import context as pctx_mod

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
        self._mla_kernel_decode = (paged and cfg.attention == "mla"
                                   and mla_mod.paged_kernel_decode(attn_impl))
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
                "table": self._table_traces}

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

    def _page_row(self, pages: List[int]) -> np.ndarray:
        """``pages`` padded with the trash page to a table row's width."""
        row = np.full((self.pages_per_slot,), self.pool_pages, np.int32)
        row[:len(pages)] = pages
        return row

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
                row = self._page_row(alloc)
                # prefill pages beyond the reserved range (bucket > request
                # budget) land in the trash page
                n_p = jax.tree.leaves(cache1["pages"])[0].shape[1]
                ids = np.asarray([alloc[i] if i < n else self.pool_pages
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
        self._on_decode_start(slot)

    # -- scheduler ----------------------------------------------------------
    def _admit_now(self, req: Request, extras: Optional[Dict]):
        slot = self.free_slots()[0]
        if self.prefill_chunk is not None:
            self._admit_chunked(req, extras, slot)
        else:
            first, cache1 = self.prefill_request(req, extras)
            self.admit_prefilled(req, first, cache1, slot, extras=extras)

    def _admit_chunked(self, req: Request, extras: Optional[Dict],
                       slot: int) -> int:
        """Reserve pages (claiming any indexed prefix run) and install the
        slot's page-table row; the prompt itself streams through
        ``_run_prefill_chunk`` one chunk per ``step()``. Pages claimed
        from the prefix index are shared and immutable — the chunks that
        would have computed them are skipped, and fresh pages take over
        from the divergence point (the copy-on-write fork). Returns how
        many leading pages of ``_slot_pages[slot]`` the index supplied."""
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
        row = self._page_row(pages)
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
        return len(hits)

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
        self._on_decode_start(slot)

    def _on_decode_start(self, slot: int):
        """``slot`` decodes from the next dispatch on (a subclass hook)."""

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

    def _masked_slots(self) -> set:
        """Occupied slots whose lanes the decode dispatch masks out: those
        mid-chunked-prefill, which are not decoding yet."""
        return set(self._prefilling)

    def _eviction_victim(self, inc: int) -> Optional[int]:
        """The lowest-priority decoding resident whose priority is
        strictly below ``inc``, or None."""
        masked = self._masked_slots()
        victims = [(r.priority, s) for s, r in enumerate(self.active)
                   if r is not None and s not in masked
                   and r.priority < inc]
        return min(victims)[1] if victims else None

    def _try_evict(self, inc: int) -> bool:
        """Free capacity for an incoming priority-``inc`` request: evict
        the lowest-priority resident whose priority is strictly lower,
        or — when no resident qualifies — reclaim the retained prefix
        pages of a strictly-lower-priority evicted continuation (it will
        re-prefill; its token stream stays bitwise-identical either way).
        Returns whether capacity was freed."""
        slot = self._eviction_victim(inc)
        if slot is not None:
            self._evict_slot(slot)
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

    # -- decode -------------------------------------------------------------
    def _device_state(self) -> Dict[str, Any]:
        # built field-for-field like Model.init_decode_state (the canonical
        # structure; pinned by a test) without paying its allocations —
        # donation invalidates reused buffers, so the chunk counters must
        # be fresh scalars each step anyway
        st = dict(
            tokens=jnp.asarray(self._tokens),
            positions=jnp.asarray(self.positions),
            active=jnp.asarray(self._decoding()),
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

    def _decoding(self) -> np.ndarray:
        """Per slot: occupied and not masked out of the decode dispatch."""
        masked = self._masked_slots()
        return np.array([r is not None and i not in masked
                         for i, r in enumerate(self.active)])

    def step(self):
        """One scheduler tick: admit from the pending queue (priority
        order, page-aware, preempting lower-priority residents when a
        higher-priority arrival is blocked), advance one chunked-prefill
        slot by one chunk, then run one fused ``chunk``-step decode
        dispatch over the decoding slots.

        The tick runs under the profiler span ``engine.step``, its phases
        under ``engine.admit`` (holding ``engine.prefill``,
        ``engine.sample_first`` and ``engine.scatter`` per refill, each
        with the request's ``rid``), ``engine.decode``, ``engine.sync``
        and ``engine.harvest`` (docs/serving.md, Tracing)."""
        with _span("engine.step"):
            self._step()

    def _step(self):
        with _span("engine.admit"):
            self._admit_pending()
        self._prefill_and_decode()

    def _runnable_prefills(self) -> List[int]:
        """Mid-chunked-prefill slots that may run a chunk this tick."""
        return list(self._prefilling)

    def _prefill_and_decode(self):
        """Run one prefill chunk, then one fused decode dispatch."""
        # one chunk for one long-prompt admission per tick, so resident
        # decode streams keep flowing between chunks (no TTFT cliff for
        # requests queued behind a long prompt)
        runnable = self._runnable_prefills()
        if runnable:
            self._run_prefill_chunk(min(runnable))
        if not self._decoding().any():
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
            # lane's active steps before it
            qpos = (self.positions[:, None] + np.cumsum(emitted, axis=1)
                    - emitted)
            self.stats["decode_live_pages"] += int(live_pages(
                qpos, self.page_size, self.pages_per_slot).sum())
            self.stats["decode_grid_pages"] += (
                self.slots * self.pages_per_slot * self.chunk)
        self.stats["drafts"] += int(host["drafts"])
        self.stats["accepted_drafts"] += int(host["accepted"])
        # copy: device_get arrays are read-only, mirrors are written on
        # admit. Masked slots keep their host-written mirrors — their
        # decode lanes carry stale device state
        masked = self._masked_slots()
        keep = np.array([i in masked for i in range(self.slots)])
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
        refcounts it retained), or free its slot — mid-chunked-prefill or
        decoding alike (pages recycled; the lane is masked out of the
        next dispatch). The Request object is left as-is — ``done`` stays
        False, ``out`` keeps whatever was delivered — so a gateway can
        re-dispatch it as a continuation. Returns False if unknown."""
        for i, (req, _) in enumerate(self.pending):
            if req.rid == rid:
                del self.pending[i]
                held = self._evicted.pop(rid, None)
                if held:
                    self._alloc.release(held)
                return True
        for slot, req in enumerate(self.active):
            if req is not None and req.rid == rid:
                self._prefilling.pop(slot, None)
                self._release_slot(slot)
                return True
        return False

    def pool_stats(self) -> Dict[str, Any]:
        """Page-pool occupancy (zeros for dense engines)."""
        if not self.paged:
            return dict(pages_total=0, pages_free=0, pages_used=0,
                        occupancy=0.0)
        free = self.free_pages()
        used = self.pool_pages - free
        return dict(pages_total=self.pool_pages,
                    pages_free=free, pages_used=used,
                    occupancy=used / self.pool_pages if self.pool_pages
                    else 0.0)

    def prefix_stats(self) -> Dict[str, Any]:
        """Prefix-index effectiveness (zeros for dense / non-chunked
        engines): admission-time page lookups vs hits, plus how many
        pages currently back index entries. The gateway's cache-aware
        router reads this to weigh prefix affinity against load."""
        if not self.paged:
            return dict(lookups=0, hits=0, hit_rate=0.0, indexed_pages=0)
        lk = self._alloc.prefix_lookups
        return dict(lookups=lk, hits=self._alloc.prefix_hits,
                    hit_rate=self._alloc.prefix_hits / lk if lk else 0.0,
                    indexed_pages=self._alloc.indexed_pages())

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
        resident requests. Drivers (``run_until_done``, the gateway's
        idle check) ask this rather than reading pending/active
        themselves, so an engine that holds requests elsewhere can say
        so."""
        return (bool(self.pending)
                or any(r is not None for r in self.active))

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
