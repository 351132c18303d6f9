"""Logical-axis -> mesh-axis sharding rules.

Param specs carry logical axes ("embed", "heads", "mlp", "vocab",
"experts", "layers"); a rule table maps them to mesh axes, with a
divisibility fallback (axes that don't divide evenly are replicated).

Two built-in strategies:

* ``tp_rules``   — Megatron-style TP on the model axis (dense archs; also
  a reasonable MoE baseline on TPU, where ICI is not the paper's weak
  NVLink — see DESIGN.md §2 hardware adaptation).
* ``dp_ep_rules`` — the paper-faithful MoE layout (§4.2 "TP avoided"):
  attention weights FSDP-sharded, experts EP on the model axis.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.param import ParamSpec

Rule = Union[None, str, Tuple[str, ...]]


def tp_rules(multi_pod: bool) -> Dict[str, Rule]:
    return {
        "embed": None,
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "expert_ff": "data",   # decode: expert-FF TP over data (ep_ftp)
        "layers": None,
    }


def dp_ep_rules(multi_pod: bool) -> Dict[str, Rule]:
    """Paper §4.2: no TP; experts EP-sharded; big dense weights FSDP over
    the data axis (ZeRO-3-style, all-gathered by GSPMD at use)."""
    return {
        "embed": None,
        "heads": "data",
        "kv_heads": "data",
        "mlp": "data",
        "vocab": "model",
        "experts": "model",
        "layers": None,
    }


def _mesh_size(mesh: Mesh, rule: Rule) -> int:
    if rule is None:
        return 1
    if isinstance(rule, str):
        return mesh.shape[rule]
    return int(np.prod([mesh.shape[r] for r in rule]))


def spec_to_pspec(spec: ParamSpec, mesh: Mesh, rules: Dict[str, Rule]) -> P:
    entries = []
    used: set = set()
    for dim, ax in zip(spec.shape, spec.axes):
        rule = rules.get(ax) if ax is not None else None
        if rule is None:
            entries.append(None)
            continue
        names = (rule,) if isinstance(rule, str) else tuple(rule)
        if any(n in used for n in names) or dim % _mesh_size(mesh, rule) != 0:
            entries.append(None)   # replicate: non-divisible or axis reuse
            continue
        used.update(names)
        entries.append(rule)
    return P(*entries)


def param_shardings(mesh: Mesh, spec_tree, rules: Dict[str, Rule]):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, spec_to_pspec(s, mesh, rules)),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def block_scale_sharding(s: NamedSharding, shape) -> NamedSharding:
    """Sharding of a weight's per-block scales (``core/fp8``): the
    weight's own spec, replicated along a dim whose block count its mesh
    axes do not divide."""
    spec = tuple(s.spec) + (None,) * (len(shape) - len(s.spec))
    return NamedSharding(s.mesh, P(*(
        ax if dim % _mesh_size(s.mesh, ax) == 0 else None
        for dim, ax in zip(shape, spec))))


def batch_pspec(mesh: Mesh, batch_size: int, dp_axes: Tuple[str, ...],
                ndim: int = 2, seq_axis: Optional[str] = None) -> P:
    """Shard the batch dim over dp axes when divisible; optionally shard the
    sequence dim (SP for prefill of tiny-batch long-context cells)."""
    total = int(np.prod([mesh.shape[a] for a in dp_axes]))
    entries: list = [None] * ndim
    if batch_size % total == 0:
        entries[0] = tuple(dp_axes) if len(dp_axes) > 1 else dp_axes[0]
    elif seq_axis and ndim >= 2:
        entries[1] = seq_axis
    return P(*entries)


def like_tree(shardings_leaf, tree):
    """Broadcast one sharding to a whole pytree (e.g. replicated scalars)."""
    return jax.tree.map(lambda _: shardings_leaf, tree)


def param_pspecs(mesh: Mesh, spec_tree, rules: Dict[str, Rule]):
    """PartitionSpec pytree (same structure as the ParamSpec tree). Used
    both to build NamedShardings and as shard_map in_specs for explicit
    cross-replica collectives over the gradient tree (grad-norm psum)."""
    return jax.tree.map(
        lambda s: spec_to_pspec(s, mesh, rules),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def train_state_shardings(mesh: Mesh, spec_tree, rules: Dict[str, Rule]):
    """Shardings for the full train state (paper §2.4 memory recipe).

    Returns ``(param_shardings, opt_shardings, pspecs)``:

    * params: per ``rules`` (fsdp_tp_rules: every big tensor sharded over
      model x data, so params+opt fit the 10-byte/param budget),
    * opt state (``optimizer.AdamWState``): fp32 master and bf16 m/v
      mirror the param layout exactly; the step counter is replicated,
    * pspecs: the PartitionSpec tree for explicit-collective helpers.
    """
    from repro.train.optimizer import AdamWState   # lazy: avoid cycle
    pspecs = param_pspecs(mesh, spec_tree, rules)
    pshard = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    # m/v mirror optimizer.init: non-float params carry None moments, so
    # their sharding leaves must be None too or device_put's treedefs
    # mismatch at meshed init/restore
    import jax.numpy as jnp
    mvshard = jax.tree.map(
        lambda s, sh: sh if jnp.issubdtype(jnp.dtype(s.dtype), jnp.floating)
        else None,
        spec_tree, pshard, is_leaf=lambda x: isinstance(x, ParamSpec))
    oshard = AdamWState(step=NamedSharding(mesh, P()),
                        master=pshard, m=mvshard, v=mvshard)
    return pshard, oshard, pspecs


def fsdp_tp_rules(multi_pod: bool) -> Dict[str, Rule]:
    """Training rules: TP on the model axis + ZeRO-3/FSDP over the data
    axis for the big replicated dims. Every large tensor is sharded on
    both axes -> params+opt fit the 10-byte/param budget (DESIGN.md §5);
    GSPMD all-gathers weights per layer (amortized by the scan)."""
    return {
        # multi-pod: ZeRO-3 spans the pod axis too — 10 B/param / |mesh|;
        # the cross-pod gathers land in the collective roofline term and
        # are a §Perf iteration target (PP would remove them; no pipe axis
        # in the assignment mesh)
        "embed": ("pod", "data") if multi_pod else "data",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "expert_ff": None,     # train/prefill: FSDP via embed->data instead
        "layers": None,
    }


def rules_for(cfg, phase: str, multi_pod: bool) -> Dict[str, Rule]:
    if phase in ("train", "prefill"):
        # prefill also FSDP-shards weights: gathers amortize over the huge
        # token count, and big-MoE expert tensors would not fit otherwise
        return fsdp_tp_rules(multi_pod)
    return tp_rules(multi_pod)


def serve_rules(multi_pod: bool, ep_ftp: bool = False) -> Dict[str, Rule]:
    """Inference (decode) rules for the sharded serving engine: attention
    heads + dense matmuls TP over the model axis, experts EP on the model
    axis — the paper's decode deployment (large-EP, no cross-node TP).

    ``expert_ff`` engages its data-axis TP only when the ctx opts into
    ``ep_ftp``; otherwise each model column keeps its experts' FF weights
    whole, matching ``parallel/ep.py``'s shard_map in_specs so the decode
    loop never re-gathers expert weights per layer.
    """
    r = tp_rules(multi_pod)
    if not ep_ftp:
        r["expert_ff"] = None
    return r


# ---------------------------------------------------------------------------
# Decode-cache sharding: leaf-name-driven (see models/api cache layouts)
# ---------------------------------------------------------------------------

_CACHE_AXES = {
    # name: (batch_axis_from_end, model_axis_from_end)
    "k": (-4, -3), "v": (-4, -3),          # (..., B, T, KV, hd): shard T
    "ckv": (-3, -2), "kr": (-3, -2),       # (..., B, T, R): shard T
    "pos": (-2, -1),                        # (..., B, T)
    "state": (-4, -3),                      # (..., B, H, P, N): shard heads
    "h": (-2, -1),                          # (..., B, w): shard width
    "conv": (-3, None),
    "memory": (0, None),
    "mtp_h": (0, None),
}


def cache_pspecs(cache_structs, mesh: Mesh, dp_axes: Tuple[str, ...],
                 model_axis: str = "model"):
    """Shard decode caches: batch over dp axes (when divisible), the long
    axis (cache length / state heads) over the model axis. GSPMD handles
    the cross-shard softmax/contraction reductions exactly."""
    dp_total = int(np.prod([mesh.shape[a] for a in dp_axes]))
    msize = mesh.shape[model_axis]

    def one(path, leaf):
        name = None
        for p in reversed(path):
            k = getattr(p, "key", None)
            if isinstance(k, str):
                name = k
                break
        entries = [None] * leaf.ndim
        rule = _CACHE_AXES.get(name)
        if rule is None:
            return NamedSharding(mesh, P(*entries))
        baxis, maxis = rule
        baxis = baxis % leaf.ndim
        if leaf.shape[baxis] % dp_total == 0:
            entries[baxis] = tuple(dp_axes) if len(dp_axes) > 1 else dp_axes[0]
        if maxis is not None:
            maxis = maxis % leaf.ndim
            if maxis != baxis and leaf.shape[maxis] % msize == 0 and \
                    leaf.shape[maxis] >= msize:
                entries[maxis] = model_axis
        return NamedSharding(mesh, P(*entries))

    paths = jax.tree_util.tree_flatten_with_path(cache_structs)[0]
    treedef = jax.tree.structure(cache_structs)
    return jax.tree.unflatten(treedef, [one(p, l) for p, l in paths])


def paged_cache_pspecs(cache_structs, mesh: Mesh, dp_axes: Tuple[str, ...],
                       model_axis: str = "model"):
    """Shard a paged decode cache (``Model.init_paged_cache`` layout).

    Pool leaves carry **no batch axis** (pages are shared across slots), so
    the dp axes never apply to them; instead each pool leaf is
    replicated-or-model-sharded per the leaf-name declaration in
    ``core/paged.pool_model_axes`` (GQA K/V pools shard their KV-head
    axis; scale sidebands and the MLA latent/rope pools replicate — same
    declared-per-family style as ``Model.paged_aux_axes``). The page
    table is replicated: it is tiny, host-authored, and every model
    column needs the full slot->page mapping. Aux slot-resident leaves
    (encoder memory, MTP hidden) shard their batch axis over dp.
    """
    from repro.core import paged as paged_mod
    dp_total = int(np.prod([mesh.shape[a] for a in dp_axes]))
    msize = mesh.shape[model_axis]

    def one(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        name = next((k for k in reversed(keys) if isinstance(k, str)), None)
        entries: list = [None] * leaf.ndim
        if name in ("memory", "mtp_h"):
            if leaf.shape[0] % dp_total == 0 and leaf.shape[0] > 0:
                entries[0] = (tuple(dp_axes) if len(dp_axes) > 1
                              else dp_axes[0])
            return NamedSharding(mesh, P(*entries))
        if name == "page_table":
            return NamedSharding(mesh, P())
        if "mtp" in keys:
            # the MTP module's KV ring is a dense (1, B, T, ...) subtree
            # riding in the paged cache: the dense name rules apply (batch
            # over dp, long axis over model), same as cache_pspecs
            rule = _CACHE_AXES.get(name)
            if rule is not None:
                baxis, maxis = rule
                baxis = baxis % leaf.ndim
                if leaf.shape[baxis] % dp_total == 0:
                    entries[baxis] = (tuple(dp_axes) if len(dp_axes) > 1
                                      else dp_axes[0])
                if maxis is not None:
                    maxis = maxis % leaf.ndim
                    if maxis != baxis and leaf.shape[maxis] % msize == 0 \
                            and leaf.shape[maxis] >= msize:
                        entries[maxis] = model_axis
            return NamedSharding(mesh, P(*entries))
        ax = paged_mod.pool_model_axes(name, leaf.ndim)
        if ax is not None and leaf.shape[ax] % msize == 0 and \
                leaf.shape[ax] >= msize:
            entries[ax] = model_axis
        return NamedSharding(mesh, P(*entries))

    paths = jax.tree_util.tree_flatten_with_path(cache_structs)[0]
    treedef = jax.tree.structure(cache_structs)
    out = jax.tree.unflatten(treedef, [one(p, l) for p, l in paths])
    if isinstance(out, dict) and "page_table" in out:
        # the scheduler's COW prefix sharing aliases page-table rows across
        # slots; every model column must see the identical full slot->page
        # mapping, so the table's spec is pinned fully replicated — any
        # future rule change that shards it should fail loudly here
        assert out["page_table"].spec == P(), out["page_table"]
    return out


def tier_payload_pspecs(payload_structs, mesh: Mesh,
                        model_axis: str = "model"):
    """Shardings for a KV-tier page payload (``Model.gather_pages``
    output: ``(layers, k, page, ...)`` per pool leaf).

    A payload leaf keeps its pool leaf's trailing axes — only the pool's
    ``P+1`` physical-page axis is swapped for the gathered ``k`` axis — so
    ``core/paged.pool_model_axes`` applies verbatim: GQA K/V payloads can
    stay sharded over their KV-head axis while staged, everything else
    replicates. Note the *tier itself* holds no device state: entry
    metadata, residency states, CRCs, and the host page store are plain
    host-side Python/numpy (no pspecs to declare) — only the in-flight
    gather/install payloads touched by the engine's tier jits are device
    arrays, and these are their specs.
    """
    from repro.core import paged as paged_mod
    msize = mesh.shape[model_axis]

    def one(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        name = next((k for k in reversed(keys) if isinstance(k, str)), None)
        entries: list = [None] * leaf.ndim
        ax = paged_mod.pool_model_axes(name, leaf.ndim)
        if ax is not None and leaf.shape[ax] % msize == 0 and \
                leaf.shape[ax] >= msize:
            entries[ax] = model_axis
        return NamedSharding(mesh, P(*entries))

    paths = jax.tree_util.tree_flatten_with_path(payload_structs)[0]
    treedef = jax.tree.structure(payload_structs)
    return jax.tree.unflatten(treedef, [one(p, l) for p, l in paths])


# per-slot decode-state leaves with a leading batch (slot) axis; the
# chunk counters replicate. Name-driven because scalar counters would
# otherwise be ambiguous against 1-d slot vectors.
_STATE_BATCH_KEYS = ("tokens", "positions", "active", "left", "eos",
                     "tix")


def decode_state_shardings(mesh: Mesh, batch: int,
                           dp_axes: Tuple[str, ...]) -> Dict[str, Any]:
    """Shardings for ``Model.init_decode_state``-shaped pytrees: the
    per-slot vectors — including the (B, 2) per-slot sampling keys and
    the (B,) stream indices — shard over the dp axes (when divisible);
    the on-device draft counters are replicated."""
    bshard = NamedSharding(mesh, batch_pspec(mesh, batch, dp_axes, ndim=1))
    rep = NamedSharding(mesh, P())
    out = {k: (bshard if k in _STATE_BATCH_KEYS else rep)
           for k in _STATE_BATCH_KEYS + ("drafts", "accepted")}
    out["rngs"] = NamedSharding(
        mesh, batch_pspec(mesh, batch, dp_axes, ndim=2, seq_axis=None))
    return out


def input_shardings(mesh: Mesh, input_structs, dp_axes: Tuple[str, ...],
                    model_axis: str = "model"):
    """Shardings for the model input dict (tokens/labels/embeds/cache)."""
    out = {}
    for k, v in input_structs.items():
        if k == "cache":
            out[k] = cache_pspecs(v, mesh, dp_axes, model_axis)
        else:
            pspec = batch_pspec(mesh, v.shape[0], dp_axes, v.ndim,
                                seq_axis=None)
            out[k] = NamedSharding(mesh, pspec)
    return out
