"""Multi-Token Prediction module (paper §2.3.3, T6; DeepSeek-V3).

Each MTP module m (depth starts at 1) is a single extra transformer block:

    h'_k = W_proj [ RMSNorm(h_k) ; RMSNorm(Emb(t_{k+m})) ]
    h_k  = Block_m(h'_k)           -> logits for t_{k+m+1} (shared unemb)

Training adds ``loss_weight``-scaled CE per module; at inference the module
drafts token t+2 which the next main-model step verifies in parallel
(serve/speculative.py) — the paper reports 80–90 % acceptance and ~1.8x TPS.

The block itself is supplied by the host model (``block_specs``/
``block_apply`` callables) so MTP composes with any of the zoo families.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import rmsnorm
from repro.models.param import ParamSpec


def mtp_specs(cfg: ModelConfig, block_specs: Callable[[int], dict]) -> dict:
    d, pd = cfg.d_model, cfg.param_dtype
    n = cfg.mtp.num_modules
    L, la = (n,), ("layers",)
    return {
        "norm_h": ParamSpec(L + (d,), pd, la + (None,), "ones"),
        "norm_e": ParamSpec(L + (d,), pd, la + (None,), "ones"),
        "w_proj": ParamSpec(L + (2 * d, d), pd, la + (None, "embed"), "fan_in"),
        "block": block_specs(n),
    }


def mtp_hidden(p_m: dict, h: jax.Array, emb_next: jax.Array, *,
               cfg: ModelConfig, positions: jax.Array,
               block_apply: Callable) -> jax.Array:
    """One MTP module. p_m: this module's param slice. h: (B,S,d) hidden
    from the previous depth; emb_next: (B,S,d) embeddings of tokens shifted
    by the module depth. Returns the module's output hidden (B,S,d)."""
    from repro.models.layers import linear
    x = jnp.concatenate([
        rmsnorm(h, p_m["norm_h"], cfg.rms_eps),
        rmsnorm(emb_next, p_m["norm_e"], cfg.rms_eps)], axis=-1)
    x = linear(x, p_m["w_proj"], cfg)
    return block_apply(p_m["block"], x, positions)


def mtp_losses(p: dict, h: jax.Array, tokens: jax.Array, emb_fn: Callable,
               unemb_fn: Callable, *, cfg: ModelConfig,
               positions: jax.Array, block_apply: Callable) -> jax.Array:
    """Summed weighted CE over MTP depths. tokens: (B,S) inputs; target of
    depth m at position k is tokens[k+m+1]. Returns scalar loss."""
    n = cfg.mtp.num_modules
    B, S = tokens.shape
    total = 0.0
    for m in range(1, n + 1):
        pm = jax.tree.map(lambda x: x[m - 1], p)
        # input tokens shifted by m: at position k we feed Emb(t_{k+m})
        shifted = jnp.roll(tokens, -m, axis=1)
        h = mtp_hidden(pm, h, emb_fn(shifted), cfg=cfg,
                       positions=positions, block_apply=block_apply)
        logits = unemb_fn(h)                            # (B,S,V)
        targets = jnp.roll(tokens, -(m + 1), axis=1)
        valid = jnp.arange(S) < S - (m + 1)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logits.astype(jnp.float32),
                                 targets[..., None], axis=-1)[..., 0]
        ce = jnp.where(valid[None, :], lse - ll, 0.0)
        total = total + cfg.mtp.loss_weight / n * (
            ce.sum() / jnp.maximum(valid.sum() * B, 1))
    return total


def mtp_draft(p: dict, h_last: jax.Array, emb_next: jax.Array, *,
              cfg: ModelConfig, positions: jax.Array,
              block_apply: Callable, unemb_fn: Callable) -> jax.Array:
    """Decode-time draft: given the main model's last hidden h_last (B,1,d)
    and the embedding of the token it just produced, return draft logits
    for the token after next. Uses module depth 1."""
    pm = jax.tree.map(lambda x: x[0], p)
    h = mtp_hidden(pm, h_last, emb_next, cfg=cfg, positions=positions,
                   block_apply=block_apply)
    return unemb_fn(h)


def mtp_draft_tokens(params: dict, cache: dict, cfg: ModelConfig,
                     last_tokens: jax.Array, positions: jax.Array,
                     embed_fn: Callable, unembed_fn: Callable
                     ) -> Tuple[jax.Array, dict]:
    """Greedy draft token per slot, traced inside the fused decode loop.

    Runs one step of MTP module 1 at position ``positions - 1`` — the pair
    ``(h_{p-1}, Emb(t_p))`` carried in ``cache['mtp_h']`` / the slot's
    current token — against the module's own KV ring ``cache['mtp']``
    (populated over the prompt at prefill), exactly the context the module
    saw in training. The old path ran the block with ``cache=None`` so
    every draft attended over a single token; with no context the draft
    never matched the verify stream and acceptance was stuck at 0.

    last_tokens/positions: (B,) — the token each slot emitted last step
    and its position. Returns ``(draft (B,) int32, new_ring)`` where the
    draft predicts the token the *current* step is about to emit and
    ``new_ring`` is the updated layer-stacked ``cache['mtp']`` subtree.
    """
    from repro.models import transformer as tfm
    ring = jax.tree.map(lambda x: x[0], cache["mtp"])
    new_ring: dict = {}

    def bapply(pb, x, pos):
        out, ring_out, _ = tfm.block_apply(
            pb, x, cfg, dict(positions=pos, causal=True), ring)
        new_ring.update(ring_out)
        return out

    logits = mtp_draft(
        params["mtp"], cache["mtp_h"], embed_fn(last_tokens[:, None]),
        cfg=cfg, positions=positions[:, None] - 1,
        block_apply=bapply, unemb_fn=unembed_fn)
    draft = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
    return draft, jax.tree.map(lambda x: x[None], new_ring)


def mtp_align_head(params: dict) -> dict:
    """Rewrite the MTP head so module 1's draft is exactly the main model's
    greedy argmax at the draft position (test/bench utility).

    Zeroes every MTP parameter (pre-norm residual blocks become identity,
    attention/FFN contribute nothing), then sets ``norm_h`` to ones and
    ``w_proj`` to ``[I; 0]`` so the module output is ``rmsnorm(h)``. The
    shared unembedding applies its own rmsnorm first and rmsnorm is
    scale-invariant and idempotent, so ``unemb(rmsnorm(h)) == unemb(h)``
    logit-for-logit: the draft equals the greedy token after ``h``. Under
    greedy sampling acceptance then counts exactly the consecutive-equal
    pairs of the emitted stream — deterministically positive on a
    repetitive workload, which the regression test pins.
    """
    m = dict(jax.tree.map(jnp.zeros_like, params["mtp"]))
    n, d2, d = params["mtp"]["w_proj"].shape
    proj = jnp.concatenate([jnp.eye(d), jnp.zeros((d2 - d, d))], axis=0)
    # in the param dtype: a serving engine's w_proj may be an fp8 pair
    m["w_proj"] = jnp.broadcast_to(proj, (n, d2, d)).astype(
        params["mtp"]["norm_h"].dtype)
    m["norm_h"] = jnp.ones_like(params["mtp"]["norm_h"])
    p = dict(params)
    p["mtp"] = m
    return p
