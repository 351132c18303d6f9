"""Compressed collectives (paper §3.2 LogFMT + §6.5 in-network compression).

``compressed_psum`` — ring reduce-scatter + all-gather over a mesh axis
with LogFMT-compressed hops. Intended for the *scarce* fabric (the inter-
pod axis in our meshes; the paper's IB): gradients cross the slow links at
~n_bits/16 of their bf16 size. Quantization error accumulates once per
reduce hop; ``logfmt_bench`` quantifies it and tests bound it.

Also provides plain helpers the trainer uses (grad sync, cross-replica
checksum for SDC detection — paper §6.1).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import logfmt


def _ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def compressed_psum(x: jax.Array, axis: str, n_bits: int = 8) -> jax.Array:
    """Sum ``x`` across ``axis`` with LogFMT-compressed ring hops.

    Must run inside shard_map with ``axis`` in scope. x: any (..., d) with
    d padded to the LogFMT tile internally. Returns the summed array
    (same on every member, like psum).
    """
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    shape = x.shape
    d = shape[-1]
    pad = (-d) % logfmt.TILE
    xf = x.astype(jnp.float32).reshape(-1, d)
    if pad:
        xf = jnp.pad(xf, [(0, 0), (0, pad)])
    rows = xf.shape[0]
    # split rows into n chunks (pad rows)
    rpad = (-rows) % n
    if rpad:
        xf = jnp.pad(xf, [(0, rpad), (0, 0)])
    chunks = xf.reshape(n, -1, xf.shape[-1])

    me = jax.lax.axis_index(axis)

    def send(c):
        """One compressed ring hop i -> i+1."""
        codes, mn, step = logfmt.encode(c, n_bits)
        codes = jax.lax.ppermute(codes, axis, _ring_perm(n))
        mn = jax.lax.ppermute(mn, axis, _ring_perm(n))
        step = jax.lax.ppermute(step, axis, _ring_perm(n))
        return logfmt.decode(codes, mn, step, n_bits, dtype=jnp.float32)

    # --- reduce-scatter: at hop t device i forwards its running chunk and
    # accumulates chunk (i - t - 1); after n-1 hops it owns chunk (i+1) ----
    acc = jnp.take(chunks, me, axis=0)
    for t in range(n - 1):
        acc = send(acc) + jnp.take(chunks, (me - t - 1) % n, axis=0)
    # --- all-gather: rotate the reduced chunks around (compressed) -------
    out = jnp.zeros_like(chunks)
    out = out.at[(me + 1) % n].set(acc)
    cur = acc
    for t in range(1, n):
        cur = send(cur)
        out = out.at[(me + 1 - t) % n].set(cur)
    y = out.reshape(-1, xf.shape[-1])
    if rpad:
        y = y[:rows]
    if pad:
        y = y[:, :d]
    return y.reshape(shape).astype(x.dtype)


def _spec_axes(spec) -> set:
    """Mesh axes a PartitionSpec actually uses."""
    used: set = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        if isinstance(entry, str):
            used.add(entry)
        else:
            used.update(entry)
    return used


def sharded_global_norm(tree, mesh, pspecs) -> jax.Array:
    """Global L2 norm of a sharded gradient tree via an explicit psum.

    Unlike ``optimizer.global_norm`` under GSPMD (where XLA decides where
    the cross-shard reduction happens), this computes each device's local
    partial sum-of-squares inside ``shard_map`` and combines with a psum
    over every mesh axis — the trainer's grad-norm clipping is then a real
    cross-replica collective by construction. Leaves replicated over some
    axes contribute once (local partials are pre-divided by the
    replication factor; replicas are bit-identical so this is exact).

    ``pspecs``: PartitionSpec tree matching ``tree`` (see
    ``sharding.param_pspecs``).
    """
    from jax.sharding import PartitionSpec as P

    leaves, treedef = jax.tree.flatten(tree)
    spec_leaves = treedef.flatten_up_to(pspecs)
    total_size = 1
    for a in mesh.axis_names:
        total_size *= mesh.shape[a]
    repl = []
    for spec in spec_leaves:
        used = _spec_axes(spec)
        r = 1
        for a in mesh.axis_names:
            if a not in used:
                r *= mesh.shape[a]
        repl.append(float(r))

    def local(ls):
        s = jnp.zeros((), jnp.float32)
        for leaf, r in zip(ls, repl):
            if leaf is None:
                continue
            s = s + jnp.sum(leaf.astype(jnp.float32) ** 2) / r
        for a in mesh.axis_names:
            s = jax.lax.psum(s, a)
        return s

    sq = jax.shard_map(
        local, mesh=mesh, in_specs=(tuple(spec_leaves),), out_specs=P(),
        check_vma=False)(tuple(leaves))
    return jnp.sqrt(sq)


def _np_fletcher64(a) -> int:
    """Host-side mirror of ``fletcher64`` for per-shard checksumming."""
    import numpy as np
    b = np.ascontiguousarray(np.asarray(a, np.float32)).view(np.uint32)
    b = b.ravel().astype(np.uint64)
    i = np.arange(1, b.size + 1, dtype=np.uint64)
    s1 = int(b.sum()) & 0xFFFFFFFF
    s2 = int((b * i).sum()) & 0xFFFFFFFF
    return s1 ^ ((s2 << 1) & 0xFFFFFFFF)


def device_checksums(tree) -> dict:
    """Per-device checksums of a sharded pytree's *local shards*.

    Real per-replica measurement (paper §6.1): each device's resident
    bytes are read back and fletcher-summed on host, XOR-combined across
    leaves. Returns ``{device_id: checksum}``. The SDC guard compares two
    independent read-backs — corruption in device memory or on the
    readback path shows up as a mismatch between reads (the trainer's
    injector corrupts one read to exercise the alarm path).
    """
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        if not (hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            continue
        if not hasattr(leaf, "addressable_shards"):
            out[0] = out.get(0, 0) ^ _np_fletcher64(leaf)
            continue
        for sh in leaf.addressable_shards:
            c = _np_fletcher64(sh.data)
            out[sh.device.id] = out.get(sh.device.id, 0) ^ c
    return out


def fletcher64(x: jax.Array) -> jax.Array:
    """Cheap on-device checksum of a pytree leaf (SDC guard, paper §6.1).
    DP replicas must agree bit-for-bit; divergence flags silent corruption.
    (uint32 arithmetic — wrap-around is part of the hash.)"""
    b = jax.lax.bitcast_convert_type(x.reshape(-1).astype(jnp.float32),
                                     jnp.uint32)
    i = jnp.arange(1, b.shape[0] + 1, dtype=jnp.uint32)
    s1 = jnp.sum(b, dtype=jnp.uint32)
    s2 = jnp.sum(b * i, dtype=jnp.uint32)
    return s1 ^ (s2 << jnp.uint32(1))


def tree_checksum(tree) -> jax.Array:
    leaves = [fletcher64(l) for l in jax.tree.leaves(tree)
              if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating)]
    out = jnp.uint32(0)
    for l in leaves:
        out = out ^ l
    return out
