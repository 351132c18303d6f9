"""Unified Model API.

``build_model(cfg)`` returns a ``Model`` whose methods are pure functions
suitable for jit/pjit:

    specs()                        ParamSpec pytree (source of truth)
    init(rng)                      materialized params
    loss(params, batch, rng)       (scalar, metrics) — teacher forcing (+MTP)
    prefill(params, batch)         (logits_last, cache); ``lengths=`` makes
                                   it bucket-friendly (pad-masked prompts)
    decode_step(params, cache, tokens, positions) (logits, cache)
    decode_loop(params, cache, state, k)  k fused decode steps under one
                                   lax.scan: on-device sampling, EOS/max-len
                                   masking, MTP drafting + acceptance stats
                                   (``prefill``/``decode_loop`` also take
                                   ``pctx=`` — a ParallelCtx scoped for the
                                   trace, used by the sharded serving path)
    init_cache(batch, max_len)     cache pytree (zeros)
    cache_batch_axes(batch, max_len)  declared batch-axis index per leaf
    init_paged_cache(batch, max_len, page, pool, storage)  block-pool
                                   decode cache (shared FP8/native page
                                   pools + per-slot page tables; see
                                   core/paged.py and serve docs)
    input_specs(shape_cfg)         ShapeDtypeStruct stand-ins per phase

Models are assembled from scanned **segments**; each segment is a stack of
identical blocks (dense / moe / pattern / ssd / rg-lru / decoder / ...)
whose params are stored stacked along the leading axis, so HLO size is
O(#segments), not O(depth) — required for 100-layer archs to compile fast.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCfg
from repro.core import mla as mla_mod
from repro.core import mtp as mtp_mod
from repro.models import layers as Lyr
from repro.models import rglru as rg_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.param import (ParamSpec, count, init_params, param_structs,
                                spec_axes)


def apply_remat(step, policy: str):
    """Wrap a scan step with the ctx's remat policy (none | full | dots).
    Single source for the single-batch backbone and the dual-microbatch
    scan (parallel/overlap), so the two paths can't diverge."""
    if policy == "full":
        return jax.checkpoint(step)
    if policy == "dots":
        return jax.checkpoint(
            step,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return step


def sample_logits(logits: jax.Array, key: jax.Array, temperature: float,
                  top_k: int = 0) -> jax.Array:
    """Greedy (temperature<=0) or temperature/top-k sampling over the last
    axis. Shared by the fused decode loop and the serving engine's
    first-token pick so both phases draw from the same policy."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    if top_k:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Segment table
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    kind: str        # dense | moe | dense_moe | vision_pattern | encoder |
                     # decoder | ssd | rg3 | rg_tail
    n: int           # scan length
    window: int = 0  # sliding window for attention blocks (0 = full)


def _segments(cfg: ModelConfig) -> List[Segment]:
    L = cfg.num_layers
    if cfg.family == "dense":
        return [Segment("blocks", "dense", L)]
    if cfg.family == "moe":
        lay = cfg.moe.layout
        if lay == "all":
            return [Segment("blocks", "moe", L)]
        if lay.startswith("dense_first:"):
            n0 = int(lay.split(":")[1])
            # a depth cut to the leading dense layers has no MoE segment
            segs = [Segment("dense0", "dense", min(n0, L))]
            if L > n0:
                segs.append(Segment("blocks", "moe", L - n0))
            return segs
        if lay.startswith("interleave:"):
            k = int(lay.split(":")[1])
            assert k == 2 and L % 2 == 0, (lay, L)
            return [Segment("pat", "dense_moe", L // 2)]
        raise ValueError(lay)
    if cfg.family == "vlm":
        assert L % cfg.cross_attn_every == 0
        return [Segment("pat", "vision_pattern", L // cfg.cross_attn_every)]
    if cfg.family == "encdec":
        return [Segment("dec", "decoder", L)]
    if cfg.family == "ssm":
        return [Segment("blocks", "ssd", L)]
    if cfg.family == "hybrid":
        plen = len(cfg.rglru.pattern)
        segs = [Segment("pat", "rg3", L // plen, window=cfg.rglru.window)]
        if L % plen:
            segs.append(Segment("tail", "rg_tail", 1))
        return segs
    raise ValueError(cfg.family)


def _rg_tail_len(cfg: ModelConfig) -> int:
    return cfg.num_layers % len(cfg.rglru.pattern)


# --- per-kind specs ---------------------------------------------------------


def _kind_specs(cfg: ModelConfig, seg: Segment) -> dict:
    n = seg.n
    if seg.kind == "dense":
        return tfm.dense_block_specs(cfg, (n,))
    if seg.kind == "moe":
        return tfm.moe_block_specs(cfg, (n,))
    if seg.kind == "dense_moe":
        return {"dense": tfm.dense_block_specs(cfg, (n,)),
                "moe": tfm.moe_block_specs(cfg, (n,))}
    if seg.kind == "vision_pattern":
        k = cfg.cross_attn_every - 1
        return {"cross": tfm.cross_block_specs(cfg, (n,)),
                "selfs": tfm.dense_block_specs(cfg, (n, k))}
    if seg.kind == "encoder":
        return tfm.dense_block_specs(cfg, (n,))
    if seg.kind == "decoder":
        return tfm.decoder_block_specs(cfg, (n,))
    if seg.kind == "ssd":
        return ssm_mod.ssd_block_specs(cfg, (n,))
    if seg.kind == "rg3":
        specs = {}
        for i, kind in enumerate(cfg.rglru.pattern):
            if kind == "recurrent":
                specs[f"r{i}"] = rg_mod.recurrent_block_specs(cfg, (n,))
            else:
                specs[f"a{i}"] = tfm.dense_block_specs(cfg, (n,))
        return specs
    if seg.kind == "rg_tail":
        t = _rg_tail_len(cfg)
        return {f"r{i}": rg_mod.recurrent_block_specs(cfg, (1,))
                for i in range(t)}
    raise ValueError(seg.kind)


# --- per-kind apply ---------------------------------------------------------


def _apply_kind(seg: Segment, p: dict, x: jax.Array, cfg: ModelConfig,
                ctx: dict, cache):
    """One scan step of segment ``seg``. cache: per-step slice or None."""
    ctx = dict(ctx, window=seg.window)
    if seg.kind in ("dense", "moe"):
        return tfm.block_apply(p, x, cfg, ctx, cache)
    if seg.kind == "dense_moe":
        c1 = cache["dense"] if cache else None
        c2 = cache["moe"] if cache else None
        x, nc1, _ = tfm.block_apply(p["dense"], x, cfg, ctx, c1)
        x, nc2, st = tfm.block_apply(p["moe"], x, cfg, ctx, c2)
        nc = None if nc1 is None and nc2 is None else {"dense": nc1, "moe": nc2}
        return x, nc, st
    if seg.kind == "vision_pattern":
        x, _, _ = tfm.cross_block_apply(p["cross"], x, cfg, ctx, None)

        def body(h, xs):
            ps, cs = xs
            h, nc, _ = tfm.block_apply(ps, h, cfg, ctx, cs)
            return h, nc

        inner_cache = cache["selfs"] if cache else None
        x, ncs = jax.lax.scan(body, x, (p["selfs"], inner_cache))
        return x, (None if ncs is None else {"selfs": ncs}), {}
    if seg.kind == "encoder":
        return tfm.encoder_block_apply(p, x, cfg, ctx, cache)
    if seg.kind == "decoder":
        return tfm.decoder_block_apply(p, x, cfg, ctx, cache)
    if seg.kind == "ssd":
        return ssm_mod.ssd_block_apply(p, x, cfg, ctx, cache)
    if seg.kind == "rg3":
        ncs = {}
        st: dict = {}
        for i, kind in enumerate(cfg.rglru.pattern):
            key = f"r{i}" if kind == "recurrent" else f"a{i}"
            sub_cache = cache[key] if cache else None
            if kind == "recurrent":
                x, nc, _ = rg_mod.recurrent_block_apply(p[key], x, cfg, ctx,
                                                        sub_cache)
            else:
                x, nc, _ = tfm.block_apply(p[key], x, cfg, ctx, sub_cache)
            ncs[key] = nc
        if all(v is None for v in ncs.values()):
            return x, None, st
        return x, ncs, st
    if seg.kind == "rg_tail":
        ncs = {}
        for i in range(_rg_tail_len(cfg)):
            key = f"r{i}"
            sub_cache = cache[key] if cache else None
            x, nc, _ = rg_mod.recurrent_block_apply(p[key], x, cfg, ctx,
                                                    sub_cache)
            ncs[key] = nc
        if all(v is None for v in ncs.values()):
            return x, None, {}
        return x, ncs, {}
    raise ValueError(seg.kind)


# ---------------------------------------------------------------------------
# Cache init per kind
# ---------------------------------------------------------------------------


def _kind_paged_cache(cfg: ModelConfig, seg: Segment, pool_pages: int,
                      page_size: int, storage: str):
    """Paged pool for one segment (attention caches only — see
    core/paged.py). Recurrent state and windowed rings have no paged
    layout; asking for one is a config error, not a silent fallback."""
    if seg.kind == "dense_moe":
        return {"dense": Lyr.init_paged_gqa_cache(cfg, seg.n, pool_pages,
                                                  page_size, storage),
                "moe": Lyr.init_paged_gqa_cache(cfg, seg.n, pool_pages,
                                                page_size, storage)}
    if seg.kind in ("dense", "moe", "decoder") and not seg.window:
        if cfg.attention == "mla":
            return mla_mod.init_paged_mla_cache(cfg, seg.n, pool_pages,
                                                page_size, storage)
        return Lyr.init_paged_gqa_cache(cfg, seg.n, pool_pages, page_size,
                                        storage)
    raise ValueError(
        f"segment {seg.name!r} (kind={seg.kind!r}, window={seg.window}) has "
        "no paged layout: only non-windowed attention caches page — "
        "recurrent SSM/RG-LRU state stays slot-resident at full precision "
        "and windowed rings are dense-only. Use the dense-cache engine for "
        "this arch.")


def _kind_cache(cfg: ModelConfig, seg: Segment, batch: int, max_len: int):
    n = seg.n
    T = max_len
    if seg.kind in ("dense", "encoder"):
        if cfg.attention == "mla":
            return mla_mod.init_mla_cache(cfg, n, batch, T)
        return Lyr.init_gqa_cache(cfg, n, batch, T, window=seg.window)
    if seg.kind == "moe":
        if cfg.attention == "mla":
            return mla_mod.init_mla_cache(cfg, n, batch, T)
        return Lyr.init_gqa_cache(cfg, n, batch, T, window=seg.window)
    if seg.kind == "dense_moe":
        return {"dense": Lyr.init_gqa_cache(cfg, n, batch, T),
                "moe": Lyr.init_gqa_cache(cfg, n, batch, T)}
    if seg.kind == "vision_pattern":
        k = cfg.cross_attn_every - 1
        inner = Lyr.init_gqa_cache(cfg, k, batch, T)
        return {"selfs": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape), inner)}
    if seg.kind == "decoder":
        return Lyr.init_gqa_cache(cfg, n, batch, T)
    if seg.kind == "ssd":
        return ssm_mod.init_ssd_cache(cfg, n, batch)
    if seg.kind == "rg3":
        out = {}
        for i, kind in enumerate(cfg.rglru.pattern):
            if kind == "recurrent":
                out[f"r{i}"] = rg_mod.init_rglru_cache(cfg, n, batch)
            else:
                out[f"a{i}"] = Lyr.init_gqa_cache(cfg, n, batch, T,
                                                  window=seg.window)
        return out
    if seg.kind == "rg_tail":
        return {f"r{i}": rg_mod.init_rglru_cache(cfg, 1, batch)
                for i in range(_rg_tail_len(cfg))}
    raise ValueError(seg.kind)


# ---------------------------------------------------------------------------
# The Model
# ---------------------------------------------------------------------------


def _embed_specs(cfg: ModelConfig) -> dict:
    d, V, pd = cfg.d_model, cfg.vocab_size, cfg.param_dtype
    specs = {
        "emb": ParamSpec((V, d), pd, ("vocab", "embed"), "normal"),
        "final_norm": ParamSpec((d,), pd, (None,), "ones"),
    }
    if not cfg.tie_embeddings:
        specs["unemb"] = ParamSpec((d, V), pd, ("embed", "vocab"), "fan_in")
    return specs


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.segments = _segments(cfg)
        # Attention-impl overrides merged into the serving-path ctx dicts
        # (prefill / prefill_chunk / decode_step). E.g. {"gqa_impl":
        # "pallas"} routes GQA decode through the paged scalar-prefetch
        # kernel and prefill through the flash bucketed kernel. Empty ->
        # default XLA path everywhere; training paths never read it.
        self.impl_ctx: Dict[str, Any] = {}

    # -- specs / init ------------------------------------------------------
    def specs(self) -> dict:
        cfg = self.cfg
        s: Dict[str, Any] = {"embed": _embed_specs(cfg)}
        for seg in self.segments:
            s[seg.name] = _kind_specs(cfg, seg)
        if cfg.encoder_layers:
            enc = Segment("enc", "encoder", cfg.encoder_layers)
            s["enc"] = _kind_specs(cfg, enc)
            s["enc_norm"] = ParamSpec((cfg.d_model,), cfg.param_dtype,
                                      (None,), "ones")
        if cfg.mtp:
            s["mtp"] = mtp_mod.mtp_specs(
                cfg, lambda n: tfm.dense_block_specs(
                    cfg, (n,), d_ff=cfg.d_ff))
        return s

    def init(self, rng: jax.Array, shardings=None):
        """Random parameters from ``rng``, drawn on the device in one jit.

        ``shardings`` (a pytree of ``NamedSharding`` matching the params)
        makes each device draw only its own shard, so a model larger than
        one chip never exists whole anywhere. Threefry is partitionable in
        the installed JAX, so a sharded draw gives the same bits as an
        unsharded one."""
        return jax.jit(functools.partial(init_params, self.specs()),
                       out_shardings=shardings)(rng)

    def param_structs(self):
        return param_structs(self.specs())

    # -- shared pieces -------------------------------------------------------
    def _embed(self, params, tokens):
        from repro.parallel.context import shard_act
        e = params["embed"]["emb"][tokens]
        return shard_act(e.astype(self.cfg.dtype))

    def _unembed(self, params, h):
        from repro.parallel.context import shard_act
        emb = params["embed"]
        with jax.named_scope("unembed"):
            h = Lyr.rmsnorm(shard_act(h), emb["final_norm"],
                            self.cfg.rms_eps)
            w = emb.get("unemb")
            if w is None:
                w = emb["emb"].T
            logits = jnp.einsum("...d,dv->...v", h, w.astype(h.dtype))
            return shard_act(logits, vocab_axis=True)

    def _encode(self, params, src_embeds):
        """Run the encoder stack (encdec family) over frame embeddings."""
        cfg = self.cfg
        B, S, _ = src_embeds.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        ctx = dict(positions=pos, causal=False)
        seg = Segment("enc", "encoder", cfg.encoder_layers)
        x = src_embeds.astype(cfg.dtype)
        x = self._run_segment(seg, params["enc"], x, ctx, None)[0]
        return Lyr.rmsnorm(x, params["enc_norm"], cfg.rms_eps)

    def _run_segment(self, seg: Segment, p, x, ctx, cache):
        """lax.scan over the segment's stacked layers."""
        cfg = self.cfg
        from repro.parallel import context as pctx
        remat = pctx.get().remat

        from repro.parallel.context import shard_act

        def step(h, xs):
            ps, cs = xs
            # barrier the per-layer slices: stops XLA from hoisting dtype
            # converts of sliced operands out of the loop, which would
            # materialize f32 copies of entire (L, ...) weight/cache stacks
            # (the barrier's transpose rule barriers the cotangents too, so
            # the backward scan keeps the same property)
            ps = jax.lax.optimization_barrier(ps)
            if cs is not None:
                cs = jax.lax.optimization_barrier(cs)
            h, nc, st = _apply_kind(seg, ps, h, cfg, ctx, cs)
            return shard_act(h), (nc, st)

        step = apply_remat(step, remat)

        if cache is None:
            xs = (p, None)
        else:
            xs = (p, cache)
        # the scope names the scan's own work too: slicing each layer's
        # weights and cache out of the stacks and writing the cache back
        with jax.named_scope("layers"):
            x, (new_cache, stats) = jax.lax.scan(step, x, xs)
        return x, new_cache, stats

    # -- phases --------------------------------------------------------------
    def _backbone(self, params, tokens, ctx, cache, extras):
        """Embed + all segments. Returns (h, new_cache_by_segment, stats)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if cfg.family == "encdec":
            mem = (extras["memory"] if "memory" in extras
                   else self._encode(params, extras["src_embeds"]))
            mp = jnp.broadcast_to(
                jnp.arange(mem.shape[1], dtype=jnp.int32), mem.shape[:2])
            ctx = dict(ctx, memory=mem, mem_positions=mp)
        if cfg.family == "vlm":
            mem = extras["patch_embeds"].astype(cfg.dtype)
            mp = jnp.broadcast_to(
                jnp.arange(mem.shape[1], dtype=jnp.int32), mem.shape[:2])
            ctx = dict(ctx, memory=mem, mem_positions=mp)
        new_caches = {}
        all_stats = {}
        for seg in self.segments:
            c = cache.get(seg.name) if cache else None
            x, nc, st = self._run_segment(seg, params[seg.name], x, ctx, c)
            if nc is not None:
                new_caches[seg.name] = nc
            if st:
                all_stats[seg.name] = st
        return x, new_caches, all_stats, ctx

    def _ce(self, params, h, labels):
        """Mean CE of hidden states vs labels (-1 = pad). Returns
        (loss, ntokens). Shared by ``loss`` and the dual-microbatch path
        (parallel/overlap) so both optimize the identical objective."""
        logits = self._unembed(params, h)
        valid = labels >= 0
        lab = jnp.where(valid, labels, 0)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logits.astype(jnp.float32),
                                 lab[..., None], axis=-1)[..., 0]
        ce = jnp.where(valid, lse - ll, 0.0)
        ntok = jnp.maximum(valid.sum(), 1)
        return ce.sum() / ntok, ntok

    def _mtp_loss(self, params, h, tokens, pos, ctx):
        """MTP auxiliary loss given the backbone's final hidden states."""
        cfg = self.cfg
        return mtp_mod.mtp_losses(
            params["mtp"], h, tokens,
            emb_fn=lambda t: self._embed(params, t),
            unemb_fn=lambda hh: self._unembed(params, hh),
            cfg=cfg, positions=pos,
            block_apply=lambda p, x, positions: tfm.block_apply(
                p, x, cfg, dict(ctx, positions=positions), None)[0])

    def loss(self, params, batch, rng=None, pctx=None):
        """Teacher-forcing loss. ``pctx``: optional ``ParallelCtx`` scoped
        for the duration of the trace (the ctx-threaded variant the meshed
        train step uses, instead of relying on the ambient global ctx)."""
        if pctx is not None:
            from repro.parallel import context as pctx_mod
            with pctx_mod.use(pctx):
                return self._loss_inner(params, batch)
        return self._loss_inner(params, batch)

    def _loss_inner(self, params, batch):
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        ctx = dict(positions=pos, causal=True)
        h, _, stats, ctx = self._backbone(params, tokens, ctx, None, batch)
        loss, ntok = self._ce(params, h, labels)
        metrics = {"ce": loss, "ntokens": ntok}
        # MoE diagnostics
        aux = 0.0
        for segname, st in stats.items():
            if "aux_loss" in st:
                aux = aux + jnp.mean(st["aux_loss"])
                metrics[f"{segname}/drop_frac"] = jnp.mean(st["drop"])
                metrics[f"{segname}/load_layers"] = st["load"]   # (n, E)
        metrics["aux_loss"] = aux
        if cfg.mtp:
            mtp_l = self._mtp_loss(params, h, tokens, pos, ctx)
            metrics["mtp_loss"] = mtp_l
            loss = loss + mtp_l
        return loss, metrics

    def loss_dual(self, params, batchA, batchB, rng=None, pctx=None):
        """Dual anti-phase microbatch loss (paper §2.3.1 overlap).

        Runs both microbatches through one scanned layer step so each
        microbatch's MoE all-to-alls can overlap the other's compute (see
        ``parallel/overlap.py``). Returns ``(loss, metrics)`` with the
        same metrics schema as ``loss`` (microbatch-averaged), so the
        trainer's router-bias balancing consumes it unchanged.
        """
        from repro.parallel import context as pctx_mod
        from repro.parallel import overlap
        if pctx is not None:
            with pctx_mod.use(pctx):
                return overlap.dual_loss_and_metrics(
                    self, params, batchA, batchB)
        return overlap.dual_loss_and_metrics(self, params, batchA, batchB)

    def prefill(self, params, batch, extra_slots: int = 0, lengths=None,
                pctx=None):
        """Process the prompt; returns (last-position logits, decode cache).

        ``pctx``: optional ``ParallelCtx`` scoped for the duration of the
        trace (mirrors ``loss(pctx=)``) — the sharded serving engine's
        meshed prefill threads its ctx here so MoE layers dispatch through
        the EP shard_map instead of relying on the ambient global context.

        ``lengths`` (B,) enables the bucketed path: ``tokens`` is padded on
        the right to a static bucket length S and only the first
        ``lengths[b]`` positions of row b are real. Pad positions are
        harmless under causal attention (real queries never attend to
        later keys), are masked out of recurrent-state updates and the MoE
        capacity contest (``ctx['valid']``: pads rank below every real
        token and the keep threshold is the exact-length capacity), never
        enter the decode cache (cache ``pos`` is -1 on pad slots), and the
        returned logits are taken at position ``lengths-1`` per row. One compile then serves every prompt length
        in the bucket.
        """
        if pctx is not None:
            from repro.parallel import context as pctx_mod
            with pctx_mod.use(pctx):
                return self._prefill_inner(params, batch, extra_slots,
                                           lengths)
        return self._prefill_inner(params, batch, extra_slots, lengths)

    def _prefill_inner(self, params, batch, extra_slots, lengths):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        ctx = dict(positions=pos, causal=True, collect_cache=True,
                   **self.impl_ctx)
        if lengths is not None:
            lengths = jnp.asarray(lengths, jnp.int32)
            ctx["valid"] = pos < lengths[:, None]
            ctx["prompt_lengths"] = lengths
        h, entries, _, ctx = self._backbone(params, tokens, ctx, None, batch)
        if lengths is None:
            h_last = h[:, -1:]
        else:
            idx = jnp.clip(lengths - 1, 0, S - 1)[:, None, None]
            h_last = jnp.take_along_axis(h, idx, axis=1)
        logits = self._unembed(params, h_last)
        cache = self._assemble_cache(entries, B, S, extra_slots, ctx, batch,
                                     lengths)
        if cfg.mtp:
            cache["mtp_h"] = h_last
            cache["mtp"] = self._mtp_prefill_ring(
                params, h, tokens, pos, S + extra_slots, lengths)
        return logits, cache

    def _mtp_prefill_ring(self, params, h, tokens, pos, T, lengths):
        """Populate MTP module 1's KV ring over the prompt.

        Training feeds the module the pair ``(h_k, Emb(t_{k+1}))`` at every
        position; decode must present the same context or the draft
        distribution has nothing to do with what the module learned (the
        acceptance-rate-0 bug). This runs the module over the prompt's
        ``L-1`` pairs (positions ``0..L-2``) collecting its block's cache
        entries into a length-``T`` ring — position ``L-1``'s pair needs
        the first generated token and is processed by the first fused
        decode step, which continues the ring with no gap.
        """
        cfg = self.cfg
        B, S = tokens.shape
        cdt = jnp.dtype(cfg.cache_dtype_())
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        if S == 1:     # single-token prompt: no pairs, empty ring
            return self._init_mtp_ring(B, T)
        Sm = S - 1
        pair_pos = pos[:, :Sm]
        # pair k exists iff t_{k+1} is a real prompt token: k < L-1
        pair_valid = pair_pos < (lengths[:, None] - 1)
        entries = {}

        def bapply(pb, x, p_):
            bctx = dict(positions=p_, causal=True, collect_cache=True,
                        valid=pair_valid)
            out, e, _ = tfm.block_apply(pb, x, cfg, bctx, None)
            entries["e"] = e
            return out

        pm = jax.tree.map(lambda x: x[0], params["mtp"])
        mtp_mod.mtp_hidden(pm, h[:, :Sm],
                           self._embed(params, tokens[:, 1:]),
                           cfg=cfg, positions=pair_pos, block_apply=bapply)

        def ring(x):
            m = pair_valid.reshape((B, Sm) + (1,) * (x.ndim - 2))
            buf = jnp.zeros((B, T) + x.shape[2:], cdt)
            return buf.at[:, :Sm].set(
                jnp.where(m, x, 0).astype(cdt))[None]

        rpos = jnp.where(pair_valid, pair_pos, -1)
        rpos = jnp.pad(rpos, ((0, 0), (0, T - Sm)), constant_values=-1)[None]
        a, b = entries["e"]
        if cfg.attention == "mla":
            return dict(ckv=ring(a), kr=ring(b), pos=rpos)
        return dict(k=ring(a), v=ring(b), pos=rpos)

    def prefill_chunk(self, params, cache, tokens, positions, lengths,
                      row, slot, pctx=None):
        """Process one chunk of one slot's prompt against the paged cache.

        The incremental-prefill entry point for the continuous-batching
        scheduler: instead of one whole-bucket ``prefill`` + page scatter,
        the prompt streams through in page-aligned chunks between fused
        decode dispatches. Each chunk writes its K/V (or MLA latents) into
        the slot's pages *first*, then attends over the gathered pages
        with per-query positional validity (``l <= qpos_i``), which covers
        both the already-resident prefix and intra-chunk causality in one
        path — no separate first-chunk/continuation trace shapes, so one
        compile serves every chunk of every prompt of every slot.

        tokens: (1, C) with C a multiple of the page size; positions:
        (1, C) absolute positions, page-aligned start; lengths: (1,) full
        prompt length — positions past ``lengths-1`` are pad (their writes
        land beyond the live prefix and are either overwritten by decode
        or masked by validity; MoE demotes them from the capacity contest
        via ``ctx['valid']``); ``row`` (1, pages_per_slot) is the slot's
        page-table row, passed as an operand rather than read from the
        cache — the cache's own row stays pointed at the trash page until
        the final chunk, so the slot's masked lane in any interleaved
        decode dispatch cannot scribble on pages the prompt is still
        streaming into; ``slot`` (traced scalar) picks the batch cache
        row. Returns ``(logits (1, 1, V) at the chunk's last real
        position, new_cache)`` — only the final chunk's logits (position
        ``lengths-1``) are meaningful to sample from.
        """
        if pctx is not None:
            from repro.parallel import context as pctx_mod
            with pctx_mod.use(pctx):
                return self._prefill_chunk_inner(params, cache, tokens,
                                                 positions, lengths, row,
                                                 slot)
        return self._prefill_chunk_inner(params, cache, tokens, positions,
                                         lengths, row, slot)

    def _prefill_chunk_inner(self, params, cache, tokens, positions, lengths,
                             row, slot):
        cfg = self.cfg
        B, C = tokens.shape
        lengths = jnp.asarray(lengths, jnp.int32)
        table = jnp.asarray(row, jnp.int32)
        ctx = dict(positions=positions, causal=True, page_table=table,
                   valid=positions < lengths[:, None],
                   prompt_lengths=lengths, **self.impl_ctx)
        h, new_caches, _, ctx = self._backbone(params, tokens, ctx, cache, {})
        out_cache = dict(cache)
        out_cache.update(new_caches)
        idx = jnp.clip(lengths - 1 - positions[:, 0], 0, C - 1)[:, None, None]
        h_last = jnp.take_along_axis(h, idx, axis=1)
        if cfg.mtp:
            # final chunk's value is h at lengths-1 (chunked prefill does
            # not populate the MTP ring — the engine forbids combining it
            # with use_mtp)
            out_cache["mtp_h"] = jax.lax.dynamic_update_slice(
                cache["mtp_h"], h_last.astype(cache["mtp_h"].dtype),
                (slot, 0, 0))
        return self._unembed(params, h_last), out_cache

    def _assemble_cache(self, entries, B, S, extra, ctx, batch, lengths=None):
        """Turn per-layer prefill entries into decode cache buffers."""
        cfg = self.cfg
        T = S + extra
        cache: Dict[str, Any] = {}
        for seg in self.segments:
            if seg.name not in entries:
                continue
            e = entries[seg.name]
            cache[seg.name] = self._entries_to_cache(seg, e, B, S, T, lengths)
        if cfg.family in ("encdec", "vlm"):
            cache["memory"] = ctx["memory"]
        return cache

    def _entries_to_cache(self, seg: Segment, e, B, S, T, lengths=None):
        cfg = self.cfg

        if seg.kind in ("dense", "moe", "decoder", "encoder"):
            Tc = min(T, seg.window) if seg.window else T
            cdt = jnp.dtype(cfg.cache_dtype_())
            if lengths is None:
                lengths = jnp.full((B,), S, jnp.int32)
            # Ring layout: cache slot t holds the newest prompt token whose
            # position p satisfies p ≡ t (mod Tc). Solving for p gives a
            # per-slot gather that works for both the full (Tc >= len) and
            # windowed (Tc < len) cases and for traced per-row lengths.
            t = jnp.arange(Tc, dtype=jnp.int32)
            n_t = (lengths[:, None] - 1 - t[None, :]) // Tc
            src = t[None, :] + n_t * Tc                       # (B, Tc)
            valid = (src >= 0) & (src < lengths[:, None])
            srcc = jnp.clip(src, 0, S - 1)

            def prep(x):
                """(n,B,S,...) entries -> (n,B,Tc,...) ring buffers."""
                idx = srcc.reshape((1, B, Tc) + (1,) * (x.ndim - 3))
                g = jnp.take_along_axis(x, idx, axis=2)
                m = valid.reshape((1, B, Tc) + (1,) * (x.ndim - 3))
                return jnp.where(m, g, 0).astype(cdt)

            pos = jnp.where(valid, src, -1)
            pos = jnp.broadcast_to(pos[None], (seg.n, B, Tc))
            if cfg.attention == "mla":
                ckv, kr = e
                return dict(ckv=prep(ckv), kr=prep(kr), pos=pos)
            k, v = e
            return dict(k=prep(k), v=prep(v), pos=pos)
        if seg.kind == "dense_moe":
            return {"dense": self._entries_to_cache(
                        Segment(seg.name, "dense", seg.n), e["dense"], B, S,
                        T, lengths),
                    "moe": self._entries_to_cache(
                        Segment(seg.name, "dense", seg.n), e["moe"], B, S,
                        T, lengths)}
        if seg.kind == "vision_pattern":
            return {"selfs": self._vision_cache(e["selfs"], B, S, T, lengths)}
        if seg.kind == "ssd":
            # conv tail / final state are already length-exact: the apply fn
            # gates pad positions out of the recurrence (ctx['valid']).
            conv, state = e
            return dict(conv=conv, state=state)
        if seg.kind in ("rg3", "rg_tail"):
            out = {}
            for key, ee in e.items():
                if key.startswith("r"):
                    conv, hlast = ee
                    out[key] = dict(conv=conv, h=hlast)
                else:
                    sub = Segment(seg.name, "dense", seg.n, window=seg.window)
                    out[key] = self._entries_to_cache(sub, ee, B, S, T,
                                                      lengths)
            return out
        raise ValueError(seg.kind)

    def _vision_cache(self, sub, B, S, T, lengths=None):
        k, v = sub
        # (n, k, B, S, KV, hd) -> buffers (n, k, B, T, KV, hd)
        def pad(x):
            return jnp.pad(x, [(0, 0), (0, 0), (0, 0), (0, T - x.shape[3]),
                               (0, 0), (0, 0)])
        n, kk = k.shape[0], k.shape[1]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (n, kk, B, S))
        if lengths is not None:
            pos = jnp.where(pos < lengths[None, None, :, None], pos, -1)
        pos = jnp.pad(pos, [(0, 0), (0, 0), (0, 0), (0, T - S)],
                      constant_values=-1)
        return dict(k=pad(k), v=pad(v), pos=pos)

    def decode_step(self, params, cache, tokens, positions):
        """One decode step. tokens: (B,1) int32; positions: (B,1) int32.
        A paged cache (``init_paged_cache``) carries its ``page_table``
        as a top-level leaf; it is threaded to every layer via ctx (one
        (B, pages) array shared by the whole stack, not scanned)."""
        cfg = self.cfg
        ctx = dict(positions=positions, causal=True, **self.impl_ctx)
        if "page_table" in cache:
            ctx["page_table"] = cache["page_table"]
        extras = {"memory": cache["memory"]} if "memory" in cache else {}
        if cfg.family == "vlm":
            extras = {"patch_embeds": cache["memory"]}
        h, new_caches, _, ctx = self._backbone(params, tokens, ctx, cache,
                                               extras)
        logits = self._unembed(params, h)
        out_cache = dict(cache)
        out_cache.update(new_caches)
        if cfg.mtp:
            out_cache["mtp_h"] = h
        return logits, out_cache

    def init_decode_state(self, batch: int, seed: int = 0) -> Dict[str, Any]:
        """Per-slot on-device decode state consumed by ``decode_loop``.

        tokens/positions: last emitted token and its next position per slot.
        active: slot occupancy mask. left: decode-token budget (max-len
        masking). eos: per-slot EOS id (-1 = none). rngs: per-slot PRNG
        *base* key (the request's sampling identity — retries re-derive the
        same stream); tix: per-slot sample index, folded into the base key
        each step so token t of a request is always sampled with
        ``fold_in(base, t)`` regardless of which slot/replica/chunk runs
        it. drafts/accepted: on-device speculative-decoding counters for
        this chunk (the MTP draft itself is same-step — drafted from the
        carried ``(mtp_h, tokens)`` pair at the top of each fused step and
        verified against that step's sample, so no draft token needs to
        live in the state).
        """
        B = batch
        return dict(
            tokens=jnp.zeros((B,), jnp.int32),
            positions=jnp.zeros((B,), jnp.int32),
            active=jnp.zeros((B,), bool),
            left=jnp.zeros((B,), jnp.int32),
            eos=-jnp.ones((B,), jnp.int32),
            rngs=jax.random.split(jax.random.PRNGKey(seed), B),
            tix=jnp.zeros((B,), jnp.int32),
            drafts=jnp.zeros((), jnp.int32),
            accepted=jnp.zeros((), jnp.int32),
        )

    def decode_loop(self, params, cache, state, k: int, *,
                    temperature: float = 0.0, top_k: int = 0,
                    use_mtp: bool = False, overlap: bool = False,
                    pctx=None):
        """Run ``k`` fused decode steps under one ``lax.scan``.

        Everything the per-token host loop used to do round-trips for
        happens on device: sampling (greedy, or temperature/top-k via
        per-slot request-seeded PRNG keys — see ``init_decode_state``),
        per-slot EOS + budget masking, and — when ``use_mtp`` — the
        same-step MTP draft (drawn against the module's KV ring before the
        main step, verified against the step's own sample) plus
        draft-acceptance counting. One dispatch emits up to ``B*k`` tokens.

        state: see ``init_decode_state``. Returns ``(tokens (B,k),
        emitted (B,k) bool, cache, state)`` — tokens are -1 where the slot
        was inactive at that step.

        ``pctx``: optional ``ParallelCtx`` scoped for the trace (mirrors
        ``loss(pctx=)``): the sharded serving engine threads its ctx here
        so every scanned decode step's MoE routes through the EP
        shard_map — the paper's decode-side large-EP deployment.

        ``overlap=True`` runs the batch as two anti-phase half-batches
        through one scanned layer step (``parallel/overlap.
        dual_decode_step``) so each half's MoE all-to-alls can fly under
        the other half's dense compute — the paper's §2.3.1 dual
        microbatch applied to decode. Dense caches only (paged pools are
        shared across slots and cannot be split), no MTP, even batch.
        """
        if overlap:
            if use_mtp:
                raise ValueError("decode overlap is incompatible with "
                                 "use_mtp: the draft ring is not split")
            inner = functools.partial(self._decode_loop_dual,
                                      temperature=temperature, top_k=top_k)
        else:
            inner = functools.partial(self._decode_loop_inner,
                                      temperature=temperature, top_k=top_k,
                                      use_mtp=use_mtp)
        if pctx is not None:
            from repro.parallel import context as pctx_mod
            with pctx_mod.use(pctx):
                return inner(params, cache, state, k)
        return inner(params, cache, state, k)

    def _decode_loop_inner(self, params, cache, state, k: int, *,
                           temperature: float, top_k: int, use_mtp: bool):
        cfg = self.cfg
        assert not use_mtp or cfg.mtp is not None

        def sample(logits, key):
            return sample_logits(logits, key, temperature, top_k)

        def body(carry, _):
            cache, st = carry
            tok, pos = st["tokens"], st["positions"]
            active, left = st["active"], st["left"]
            eos = st["eos"]
            if use_mtp:
                # same-step speculation: draft from the carried pair
                # (h_{p-1}, Emb(t_p)) against the MTP module's own KV ring
                # *before* the main step, then verify against the token
                # this step samples. Every active step drafts — the
                # prompt's pairs were rung in at prefill, so the pair
                # always exists.
                d, ring = mtp_mod.mtp_draft_tokens(
                    params, cache, cfg, tok, pos,
                    embed_fn=lambda t: self._embed(params, t),
                    unembed_fn=lambda hh: self._unembed(params, hh))
                cache = dict(cache)
                cache["mtp"] = ring
            logits, cache = self.decode_step(params, cache, tok[:, None],
                                             pos[:, None])
            with jax.named_scope("sample"):
                # per-slot sampling keys: fold the slot's sample index into
                # its request-scoped base key, so the token at stream index
                # t is a pure function of (request seed, t) — a retried
                # request re-dispatched on another replica reproduces its
                # stream
                keys = jax.vmap(jax.random.fold_in)(st["rngs"], st["tix"])
                nxt = jax.vmap(sample)(logits[:, 0], keys)
                if use_mtp:
                    drafts = st["drafts"] + active.sum(dtype=jnp.int32)
                    accepted = st["accepted"] + (
                        active & (d == nxt)).sum(dtype=jnp.int32)
                else:
                    drafts, accepted = st["drafts"], st["accepted"]
                emitted = jnp.where(active, nxt, -1)
                pos2 = pos + active
                left2 = left - active
                done = active & (((eos >= 0) & (nxt == eos)) | (left2 <= 0))
                active2 = active & ~done
                tok2 = jnp.where(active, nxt, tok)
                st2 = dict(tokens=tok2, positions=pos2, active=active2,
                           left=left2, eos=eos, rngs=st["rngs"],
                           tix=st["tix"] + active, drafts=drafts,
                           accepted=accepted)
            return (cache, st2), (emitted, active)

        (cache, state), (toks, was_active) = jax.lax.scan(
            body, (cache, state), None, length=k)
        return toks.T, was_active.T, cache, state

    def _dense_cache_axes(self, cache) -> Dict[str, Any]:
        """Batch-axis per leaf of an *actual* dense decode cache pytree
        (``cache_batch_axes`` keyed off the cache in hand instead of a
        rebuilt struct — chunked decode carries exactly these leaves)."""
        kinds = {seg.name: seg.kind for seg in self.segments}
        axes: Dict[str, Any] = {}
        for key, sub in cache.items():
            if key in ("memory", "mtp_h"):
                axes[key] = 0
            elif key == "mtp":
                axes[key] = jax.tree.map(lambda _: 1, sub)
            else:
                ax = 2 if kinds[key] == "vision_pattern" else 1
                axes[key] = jax.tree.map(lambda _: ax, sub)
        return axes

    def _decode_loop_dual(self, params, cache, state, k: int, *,
                          temperature: float, top_k: int):
        """``_decode_loop_inner`` over two anti-phase half-batches.

        Splits cache + state at the batch axis, runs each fused step
        through ``overlap.dual_decode_step`` (both halves' layer ops in
        ONE scan body, so their MoE all-to-alls are schedulable under the
        neighbor's compute), and concatenates the halves back — slot ``i``
        keeps index ``i``, token streams are bitwise those of the single
        path when routing is deterministic per token.
        """
        from repro.parallel import overlap
        B = state["tokens"].shape[0]
        if B % 2:
            raise ValueError(f"decode overlap needs an even batch, got {B}")
        if "page_table" in cache:
            raise ValueError(
                "decode overlap requires a dense cache: paged page pools "
                "are shared across slots and have no batch axis to split")
        if "memory" in cache:
            raise ValueError("decode overlap supports decoder-only "
                             "caches (enc/vlm memory is not threaded "
                             "through the dual step)")
        b = B // 2
        axes = self._dense_cache_axes(cache)

        def csplit(i):
            return jax.tree.map(
                lambda x, ax: jax.lax.slice_in_dim(x, i * b, (i + 1) * b,
                                                   axis=ax), cache, axes)

        def ssplit(st, i):
            return {kk: (v[i * b:(i + 1) * b] if v.ndim else v)
                    for kk, v in st.items()}

        cacheA, cacheB = csplit(0), csplit(1)
        stA, stB = ssplit(state, 0), ssplit(state, 1)

        def sample(logits, key):
            return sample_logits(logits, key, temperature, top_k)

        def substep(logits, st):
            with jax.named_scope("sample"):
                keys = jax.vmap(jax.random.fold_in)(st["rngs"], st["tix"])
                nxt = jax.vmap(sample)(logits[:, 0], keys)
                active, left, eos = st["active"], st["left"], st["eos"]
                emitted = jnp.where(active, nxt, -1)
                left2 = left - active
                done = active & (((eos >= 0) & (nxt == eos)) | (left2 <= 0))
                st2 = dict(tokens=jnp.where(active, nxt, st["tokens"]),
                           positions=st["positions"] + active,
                           active=active & ~done, left=left2, eos=eos,
                           rngs=st["rngs"], tix=st["tix"] + active,
                           drafts=st["drafts"], accepted=st["accepted"])
                return emitted, active, st2

        def body(carry, _):
            cA, cB, sA, sB = carry
            la, lb, cA, cB = overlap.dual_decode_step(
                self, params, cA, cB,
                sA["tokens"][:, None], sB["tokens"][:, None],
                sA["positions"][:, None], sB["positions"][:, None])
            eA, aA, sA = substep(la, sA)
            eB, aB, sB = substep(lb, sB)
            return (cA, cB, sA, sB), (eA, eB, aA, aB)

        (cacheA, cacheB, stA, stB), (tA, tB, aA, aB) = jax.lax.scan(
            body, (cacheA, cacheB, stA, stB), None, length=k)
        cache = jax.tree.map(
            lambda a, bb, ax: jnp.concatenate([a, bb], axis=ax),
            cacheA, cacheB, axes)
        state = {kk: (jnp.concatenate([stA[kk], stB[kk]], axis=0)
                      if stA[kk].ndim else stA[kk]) for kk in stA}
        toks = jnp.concatenate([tA, tB], axis=1)        # (k, B)
        emitted = jnp.concatenate([aA, aB], axis=1)
        return toks.T, emitted.T, cache, state

    # -- cache/init specs ----------------------------------------------------
    def _init_mtp_ring(self, batch: int, max_len: int):
        """MTP module 1's own KV ring: a 1-layer dense ring cache (the
        module's block attends over its *pair* sequence, which pages would
        buy nothing for — one layer, and evicted with the slot)."""
        if self.cfg.attention == "mla":
            return mla_mod.init_mla_cache(self.cfg, 1, batch, max_len)
        return Lyr.init_gqa_cache(self.cfg, 1, batch, max_len)

    def init_cache(self, batch: int, max_len: int):
        cache: Dict[str, Any] = {}
        for seg in self.segments:
            cache[seg.name] = _kind_cache(self.cfg, seg, batch, max_len)
        cfg = self.cfg
        if cfg.family in ("encdec", "vlm"):
            n = (int(max_len * cfg.src_len_ratio) if cfg.family == "encdec"
                 else cfg.num_patches)
            cache["memory"] = jnp.zeros((batch, n, cfg.d_model), cfg.dtype)
        if cfg.mtp:
            cache["mtp_h"] = jnp.zeros((batch, 1, cfg.d_model), cfg.dtype)
            cache["mtp"] = self._init_mtp_ring(batch, max_len)
        return cache

    def cache_structs(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def cache_batch_axes(self, batch: int, max_len: int):
        """Pytree (matching ``init_cache``) of each leaf's batch-axis index.

        Declared per cache family rather than inferred from shapes: every
        layer-stacked family (MLA latent, GQA ring K/V, SSM conv+state,
        rg-lru conv+h) carries batch at axis 1 behind the stacked-layers
        axis; the vision self-attn cache nests one more scan axis (axis 2);
        encoder memory and the MTP hidden are unstacked (axis 0). Used by
        the serving engine's jitted slot-admission splice.
        """
        structs = self.cache_structs(batch, max_len)
        axes: Dict[str, Any] = {}
        for seg in self.segments:
            ax = 2 if seg.kind == "vision_pattern" else 1
            axes[seg.name] = jax.tree.map(lambda _: ax, structs[seg.name])
        if "memory" in structs:
            axes["memory"] = 0
        if "mtp_h" in structs:
            axes["mtp_h"] = 0
        if "mtp" in structs:   # layer-stacked (1, B, T, ...) ring
            axes["mtp"] = jax.tree.map(lambda _: 1, structs["mtp"])
        return axes

    # -- paged cache family (block pool + page tables; core/paged.py) -------
    def supports_paged(self) -> bool:
        """True iff every cached segment has a paged layout (non-windowed
        attention). Recurrent/windowed families are dense-cache only."""
        try:
            for seg in self.segments:
                _kind_paged_cache(self.cfg, seg, 0, 1, "bf16")
        except ValueError:
            return False
        return True

    def init_paged_cache(self, batch: int, max_len: int, page_size: int,
                         pool_pages: int, storage: str = "fp8"):
        """Paged decode cache: shared page pools + per-slot page tables.

        Attention segments become pools of ``pool_pages`` fixed-size token
        blocks (+1 trash page) with no batch axis; ``page_table`` (B,
        max_len//page_size) maps each slot's logical pages to physical
        ones (trash where unmapped). ``storage="fp8"`` stores E4M3 values
        with per-token scales; ``"bf16"`` stores the native cache dtype.
        Aux leaves (encoder memory, MTP hidden) stay slot-resident.
        """
        from repro.core import paged as paged_mod
        paged_mod.validate_storage(storage)
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} not a multiple of "
                             f"page_size {page_size}")
        cfg = self.cfg
        pp = max_len // page_size
        cache: Dict[str, Any] = {
            "page_table": jnp.full((batch, pp),
                                   paged_mod.trash_page(pool_pages),
                                   jnp.int32)}
        for seg in self.segments:
            cache[seg.name] = _kind_paged_cache(cfg, seg, pool_pages,
                                                page_size, storage)
        if cfg.family in ("encdec", "vlm"):
            n = (int(max_len * cfg.src_len_ratio) if cfg.family == "encdec"
                 else cfg.num_patches)
            cache["memory"] = jnp.zeros((batch, n, cfg.d_model), cfg.dtype)
        if cfg.mtp:
            cache["mtp_h"] = jnp.zeros((batch, 1, cfg.d_model), cfg.dtype)
            cache["mtp"] = self._init_mtp_ring(batch, max_len)
        return cache

    def paged_aux_axes(self) -> Dict[str, Any]:
        """Batch-axis declarations for the slot-resident leaves of a paged
        cache (the ones admission still splices densely)."""
        axes: Dict[str, Any] = {}
        if self.cfg.family in ("encdec", "vlm"):
            axes["memory"] = 0
        if self.cfg.mtp:
            axes["mtp_h"] = 0
            axes["mtp"] = jax.tree.map(
                lambda _: 1, jax.eval_shape(lambda: self._init_mtp_ring(1, 8)))
        return axes

    def prefill_to_pages(self, cache1, page_size: int, storage: str):
        """Quantize a batch-1 prefill cache (``extra_slots=0``, so the
        length axis is the static bucket) into page-granular payload:
        ``{"pages": {segment: {leaf: (n, bucket//page, page, ...)}},
        "aux": {...}}``. This is the disaggregation wire format — fp8
        pages + scales are what `Handoff` ships, ~2x fewer bytes than the
        bf16 rows at equal token count.
        """
        from repro.core import paged as paged_mod
        store = jnp.dtype(self.cfg.cache_dtype_())

        def seg_pages(sub):
            out = {}
            for name in ("ckv", "kr", "k", "v"):
                if name not in sub:
                    continue
                leaf = sub[name]
                # GQA entries (n, 1, T, KV, hd) page as flat rows
                leaf = leaf.reshape(leaf.shape[:3] + (-1,))
                d = paged_mod.entries_to_pages(leaf, page_size, storage,
                                               store)
                out[name] = d["q"]
                if "scale" in d:
                    out[name + "_scale"] = d["scale"]
            return out

        pages: Dict[str, Any] = {}
        for seg in self.segments:
            sub = cache1[seg.name]
            if seg.kind == "dense_moe":
                pages[seg.name] = {k: seg_pages(sub[k])
                                   for k in ("dense", "moe")}
            else:
                pages[seg.name] = seg_pages(sub)
        aux = {k: cache1[k] for k in ("memory", "mtp_h", "mtp")
               if k in cache1}
        return {"pages": pages, "aux": aux}

    def install_pages(self, cache, payload_pages, ids):
        """Scatter page payload into the pools at physical ``ids`` — no
        page-table change (jit-friendly). The shared core of prefill
        admission and the KV tier's fetch path: trash-padded ``ids``
        entries land in the scratch page, so one static payload width
        serves every transfer size."""
        from repro.core import paged as paged_mod

        def seg_scatter(pool, pages):
            return {k: paged_mod.scatter_pages(pool[k], pages[k], ids)
                    for k in pool}

        out = dict(cache)
        for seg in self.segments:
            sub = payload_pages[seg.name]
            if seg.kind == "dense_moe":
                out[seg.name] = {k: seg_scatter(cache[seg.name][k], sub[k])
                                 for k in ("dense", "moe")}
            else:
                out[seg.name] = seg_scatter(cache[seg.name], sub)
        return out

    def gather_pages(self, cache, ids):
        """Read physical pages ``ids`` out of every pool — the inverse of
        :meth:`install_pages`, shaped ``(layers, len(ids), page, ...)``
        per leaf (jit-friendly; ``ids`` may be a traced int32 vector).
        This is the device side of a tier spill: the caller stages the
        result to host memory between ticks."""
        def seg_gather(pool):
            return {k: pool[k][:, ids] for k in pool}

        out = {}
        for seg in self.segments:
            sub = cache[seg.name]
            if seg.kind == "dense_moe":
                out[seg.name] = {k: seg_gather(sub[k])
                                 for k in ("dense", "moe")}
            else:
                out[seg.name] = seg_gather(sub)
        return out

    def admit_pages(self, cache, payload_pages, ids, table_row, slot):
        """Scatter a request's quantized prefill pages into the pools and
        install its page-table row (jit-friendly; ``slot`` traced).
        ``ids``: (bucket_pages,) physical page ids (trash-padded beyond
        the reserved range); ``table_row``: (pages_per_slot,) int32."""
        out = self.install_pages(cache, payload_pages, ids)
        table = cache["page_table"]
        out["page_table"] = jax.lax.dynamic_update_slice(
            table, table_row[None].astype(table.dtype), (slot, 0))
        return out

    def release_slot_pages(self, cache, slot):
        """Point a freed slot's page-table row at the trash page so its
        (still-running, masked) decode lane can never write into pages
        recycled to a new owner (jit-friendly; ``slot`` traced)."""
        table = cache["page_table"]
        # trash id = pool_pages = (P+1) - 1, recovered from any pool leaf
        leaf = jax.tree.leaves(cache[self.segments[0].name])[0]
        trash = jnp.full((1, table.shape[1]), leaf.shape[1] - 1, table.dtype)
        out = dict(cache)
        out["page_table"] = jax.lax.dynamic_update_slice(
            table, trash, (slot, 0))
        return out

    # -- dry-run inputs --------------------------------------------------------
    def input_specs(self, shape: ShapeCfg) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if shape.phase in ("train", "prefill"):
            d: Dict[str, Any] = {"tokens": sds((B, S), i32)}
            if shape.phase == "train":
                d["labels"] = sds((B, S), i32)
            if cfg.family == "encdec":
                d["src_embeds"] = sds((B, int(S * cfg.src_len_ratio),
                                       cfg.d_model), jnp.dtype(cfg.dtype))
            if cfg.family == "vlm":
                d["patch_embeds"] = sds((B, cfg.num_patches, cfg.d_model),
                                        jnp.dtype(cfg.dtype))
            return d
        # decode: tokens + positions + cache with S context slots
        cache = self.cache_structs(B, S)
        return {"tokens": sds((B, 1), i32), "positions": sds((B, 1), i32),
                "cache": cache}


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


# ---------------------------------------------------------------------------
# Param counting (DESIGN.md convention; used for MODEL_FLOPS = 6·N·D)
# ---------------------------------------------------------------------------


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    m = Model(cfg)
    specs = m.specs()
    leaves = jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    total = 0
    for path, s in leaves:
        sz = math.prod(s.shape)
        keys = "/".join(str(getattr(k, "key", k)) for k in path)
        if active_only and "experts" in s.axes:
            sz = int(sz * cfg.moe.top_k / cfg.moe.num_experts)
        total += sz
    return total
