"""AdamW with the DeepSeek-V3 state-dtype recipe (paper §2.4 context):
fp32 master weights, **bf16 first/second moments** (the V3 technical
report's memory optimization), bf16 compute weights. Pure JAX.

Memory per param: 2 (bf16 w) + 4 (fp32 master) + 2 + 2 (bf16 m, v)
= 10 bytes — what makes 400B-scale training fit the mesh (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array
    master: Any     # fp32 copies of params
    m: Any          # bf16 first moment
    v: Any          # bf16 second moment


def _is_float(x):
    return jnp.issubdtype(x.dtype, jnp.floating)


def init(params) -> AdamWState:
    # a fresh buffer even for fp32 params: the train step donates params
    # and optimizer state together, and one buffer cannot be donated twice
    master = jax.tree.map(
        lambda p: jnp.array(p, jnp.float32, copy=True) if _is_float(p)
        else p, params)
    m = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.bfloat16) if _is_float(p) else None,
        params)
    v = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.bfloat16) if _is_float(p) else None,
        params)
    return AdamWState(jnp.zeros((), jnp.int32), master, m, v)


def global_norm(grads) -> jax.Array:
    leaves = [jnp.sum(g.astype(jnp.float32) ** 2)
              for g in jax.tree.leaves(grads) if g is not None]
    return jnp.sqrt(sum(leaves))


def update(grads, state: AdamWState, params, *, lr, b1: float = 0.9,
           b2: float = 0.95, eps: float = 1e-8, weight_decay: float = 0.1,
           clip_norm: Optional[float] = 1.0,
           grad_norm: Optional[jax.Array] = None
           ) -> Tuple[Any, AdamWState, dict]:
    """Returns (new_params in original dtypes, new_state, stats).

    ``grad_norm``: precomputed global norm (the meshed train step passes
    ``collectives.sharded_global_norm`` — an explicit cross-replica psum —
    so clipping is collective-exact rather than left to GSPMD placement).
    """
    step = state.step + 1
    gnorm = global_norm(grads) if grad_norm is None else grad_norm
    scale = 1.0
    if clip_norm is not None:
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))

    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(g, master, m, v, p):
        if g is None or not _is_float(p):
            return p, master, m, v
        g = g.astype(jnp.float32) * scale
        m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        mh = m32 / bc1
        vh = v32 / bc2
        wd = weight_decay if p.ndim >= 2 else 0.0   # no decay on norms/bias
        new_master = master - lr * (mh / (jnp.sqrt(vh) + eps) + wd * master)
        return (new_master.astype(p.dtype), new_master,
                m32.astype(jnp.bfloat16), v32.astype(jnp.bfloat16))

    flat_p, td = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_ma = jax.tree.leaves(state.master)
    flat_m = td.flatten_up_to(state.m)
    flat_v = td.flatten_up_to(state.v)
    out = [upd(g, ma, m, v, p) for g, ma, m, v, p in
           zip(flat_g, flat_ma, flat_m, flat_v, flat_p)]
    new_p = td.unflatten([o[0] for o in out])
    new_master = td.unflatten([o[1] for o in out])
    new_m = td.unflatten([o[2] for o in out])
    new_v = td.unflatten([o[3] for o in out])
    stats = {"grad_norm": gnorm, "lr": lr}
    return new_p, AdamWState(step, new_master, new_m, new_v), stats
