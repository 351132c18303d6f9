"""Pure-jnp oracle for the paged MLA absorbed-decode kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_mla_decode_ref(q_abs, q_rope, ckv, kr, ckv_s, kr_s, table,
                         qpos, *, scale: float):
    """Gather + full softmax reference.

    q_abs (B,H,R) / q_rope (B,H,Rr) fp32; ckv/kr (P+1, page, R/Rr) in the
    storage dtype with per-token scales ckv_s/kr_s (P+1, page); table
    (B, pp) physical page ids; qpos (B,) current decode positions.
    Returns (B, H, R) fp32.
    """
    B, pp = table.shape
    page = ckv.shape[1]
    ckv_f = ckv.astype(jnp.float32) * ckv_s[..., None]
    kr_f = kr.astype(jnp.float32) * kr_s[..., None]
    ckv_t = ckv_f[table].reshape(B, pp * page, -1)      # (B, T, R)
    kr_t = kr_f[table].reshape(B, pp * page, -1)        # (B, T, Rr)
    s = (jnp.einsum("bhr,btr->bht", q_abs.astype(jnp.float32), ckv_t)
         + jnp.einsum("bhr,btr->bht", q_rope.astype(jnp.float32), kr_t)
         ) * scale
    valid = jnp.arange(pp * page)[None, :] <= qpos[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", p, ckv_t)


def paged_gqa_decode_ref(q, k, v, k_s, v_s, table, qpos, *, scale: float):
    """Gather + full softmax reference for the paged GQA decode kernel.

    q (B,H,hd) fp32; k/v (P+1, page, KV*hd) in the storage dtype (one
    token's entry is a flat row of KV heads) with per-token scales
    k_s/v_s (P+1, page); table (B, pp) physical page ids; qpos (B,). The
    head axis factors as (KV, G) — one KV head per group of G = H // KV
    query heads. Returns (B, H, hd) fp32.
    """
    B, H, hd = q.shape
    page, KV = k.shape[1], k.shape[2] // hd
    G = H // KV
    pp = table.shape[1]
    kf = k.astype(jnp.float32) * k_s[..., None]
    vf = v.astype(jnp.float32) * v_s[..., None]
    kt = kf[table].reshape(B, pp * page, KV, hd)        # (B, T, KV, hd)
    vt = vf[table].reshape(B, pp * page, KV, hd)
    qg = q.astype(jnp.float32).reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,btkh->bkgt", qg, kt) * scale
    valid = jnp.arange(pp * page)[None, :] <= qpos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkh->bkgh", p, vt)
    return o.reshape(B, H, hd)
