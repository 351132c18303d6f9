"""Pieces the float32 references share: norms, rotary embedding, causal
attention in query blocks, the SwiGLU FFN, and the quantizers of the
control (the reference computed one precision step below the program).

Nothing here imports the program. Matmuls run at ``highest`` precision,
so a TPU computes them in float32 and not in one bfloat16 pass.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256
TILE = 128


def rmsnorm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def rope(x, pos, theta):
    """Rotary embedding over the last axis, halves rotated against each
    other. x: (S, H, D); pos: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def causal_attention(q, k, v, scale):
    """q (S, H, Dq), k (S, H|1, Dq), v (S, H, Dv): causal softmax
    attention, computed QUERY_BLOCK queries at a time so the (H, block, S)
    score tile is all that lives at once. S must divide by the block."""
    S, H, _ = q.shape
    blocks = S // QUERY_BLOCK
    kpos = jnp.arange(S)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, jnp.broadcast_to(k, (S, H) +
                                                           k.shape[2:]))
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None],
                      s * scale, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(one, jnp.arange(blocks))
    return out.reshape(S, H, v.shape[-1])


def swiglu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def matmul(x, w):
    return jnp.matmul(x.astype(F32), w.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


# -- control: the same forward one precision step down --------------------

def _tiles(x, axis):
    """View ``axis`` of x as (n, TILE) tiles, padding with zeros."""
    n = x.shape[axis]
    pad = (-n) % TILE
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    shape = x.shape[:axis] + (x.shape[axis] // TILE, TILE) + \
        x.shape[axis + 1:]
    return x.reshape(shape), n


def _round(x, fmt):
    if fmt == "fp8":
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    if fmt == "int4":
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        s = jnp.where(amax > 0, amax / 7.0, 1.0)
        return jnp.clip(jnp.round(x / s), -8, 7) * s
    raise ValueError(f"unknown control format {fmt!r}")


def qdq_rows(x, fmt):
    """Quantize-dequantize in 1 x TILE tiles along the last axis (the
    contraction axis of an activation)."""
    t, n = _tiles(x.astype(F32), x.ndim - 1)
    return _round(t, fmt).reshape(t.shape[:-2] + (-1,))[..., :n]


def qdq_blocks(w, fmt):
    """Quantize-dequantize a (..., K, N) weight in TILE x TILE blocks."""
    w = w.astype(F32)
    t, k = _tiles(w, w.ndim - 2)                 # (..., K/T, T, N)
    t, n = _tiles(t, t.ndim - 1)                 # (..., K/T, T, N/T, T)
    t = jnp.moveaxis(t, -3, -2)                  # (..., K/T, N/T, T, T)
    sh = t.shape
    flat = t.reshape(sh[:-2] + (TILE * TILE,))
    q = _round(flat, fmt).reshape(sh)
    q = jnp.moveaxis(q, -2, -3).reshape(w.shape[:-2] + (sh[-4] * TILE,
                                                       sh[-3] * TILE))
    return q[..., :k, :n]


def control_matmul(fmt: Optional[str]) -> Callable:
    """The matmul of the reference (``fmt`` None) or of its control, whose
    operands are rounded to ``fmt`` in tiles of TILE along the contraction
    axis (activations) and TILE x TILE blocks (weights)."""
    if fmt is None:
        return matmul
    return lambda x, w: matmul(qdq_rows(x, fmt), qdq_blocks(w, fmt))


@functools.partial(jax.jit, static_argnames=("fmt", "block"))
def logit_stats(h, unemb, targets, fmt: Optional[str] = None,
                block: int = 8192):
    """Over vocabulary blocks of ``h @ unemb`` (h (P, d) final hidden
    states): the best logit, the argmax, and the logit of ``targets``.
    With ``fmt``, the control's logits: h and each block of the head
    rounded as ``control_matmul`` rounds them."""
    V = unemb.shape[1]
    mm = control_matmul(fmt)
    best = jnp.full(h.shape[:1], -jnp.inf, F32)
    arg = jnp.zeros(h.shape[:1], jnp.int32)
    at = jnp.zeros(h.shape[:1], F32)
    for s in range(0, V, block):
        lg = mm(h, unemb[:, s:s + block])
        m = lg.max(-1)
        a = lg.argmax(-1).astype(jnp.int32) + s
        arg = jnp.where(m > best, a, arg)
        best = jnp.maximum(best, m)
        inside = (targets >= s) & (targets < s + lg.shape[1])
        idx = jnp.clip(targets - s, 0, lg.shape[1] - 1)
        at = jnp.where(inside, jnp.take_along_axis(lg, idx[:, None], 1)[:, 0],
                       at)
    return best, arg, at


def pad_to(n: int, mult: int = 1024) -> int:
    return int(math.ceil(n / mult) * mult)


def draw(structs, key, norms):
    """Weights for ``structs`` (a pytree of ShapeDtypeStruct keyed like
    the served model), each leaf from its own fold of ``key``, drawn in
    float32 and stored in the leaf's dtype. Call under jit.

    Leaves named in ``norms`` are ones. Matrices take 1/sqrt(fan-in) (the
    second-to-last axis), so every sublayer's output and the logits are of
    order one; the embedding is unit normal."""
    flat = jax.tree_util.tree_flatten_with_path(structs)[0]
    out = []
    for i, (kp, s) in enumerate(flat):
        name = str(getattr(kp[-1], "key", kp[-1]))
        if name in norms:
            out.append(jnp.ones(s.shape, s.dtype))
            continue
        std = 1.0 if name == "emb" else 1.0 / math.sqrt(s.shape[-2])
        x = jax.random.normal(jax.random.fold_in(key, i), s.shape, F32)
        out.append((x * std).astype(s.dtype))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(structs), out)
