"""One call per registered kernel op at the widths of the models served.

The interpret-vs-ref sweep (``tests/test_kernel_properties.py``) runs at
toy shapes. These cases use real widths instead: GQA head_dim 128
(qwen1.5-4b: 20 KV heads; qwen3-14b: 8 KV heads for 40 query heads),
the MLA latent 512 + rope 64 with 128 heads (deepseek-v3), fp8 GEMM
contractions K = 2560 and 7168, expert GEMMs 2048 x 768 (qwen3-moe), and
LogFMT rows of d = 7168. ``tests/test_tpu_compile.py`` compiles each for
a described TPU v5e; ``chip_smoke.py`` runs each on the chip under the
``pallas`` and ``ref`` backends and compares them with :func:`compare`.

Every builder is pure ``jax.random`` so ``jax.eval_shape`` gives the
argument shapes without materializing anything.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import logfmt, paged


@dataclasses.dataclass(frozen=True)
class Case:
    op: str                       # registered kernel name
    build: Callable[[jax.Array], Tuple[tuple, dict]]
    # allclose bound |got - ref| <= atol + rtol * |ref|; the values are
    # the interpret-vs-ref sweep's for the same op and dtype
    rtol: float
    atol: float


def _normal(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _paged_table(key, B, pp, pool):
    ids = jax.random.permutation(key, pool)[:B * pp]
    return ids.reshape(B, pp).astype(jnp.int32)


def paged_mla_args(key, *, page: int, storage: str, B: int = 4,
                   T: int = 512, H: int = 128, R: int = 512, Rr: int = 64):
    pp = T // page
    pool = B * pp + 3
    k = jax.random.split(key, 6)
    ckv = _normal(k[0], (pool + 1, page, R))
    kr = _normal(k[1], (pool + 1, page, Rr))
    if storage == "fp8":
        ckv, cs = paged.quantize_vecs(ckv)
        kr, ks = paged.quantize_vecs(kr)
    else:
        ckv, kr = ckv.astype(jnp.bfloat16), kr.astype(jnp.bfloat16)
        cs = ks = jnp.ones((pool + 1, page), jnp.float32)
    qpos = jax.random.randint(k[2], (B,), T // 2, T).astype(jnp.int32)
    args = (_normal(k[3], (B, H, R)), _normal(k[4], (B, H, Rr)), ckv, kr,
            cs, ks, _paged_table(k[5], B, pp, pool), qpos)
    return args, dict(scale=1.0 / math.sqrt(R + Rr))


def paged_gqa_args(key, *, page: int, storage: str, B: int = 4,
                   T: int = 512, H: int = 20, KV: int = 20, hd: int = 128):
    pp = T // page
    pool = B * pp + 3
    k = jax.random.split(key, 5)
    kk = _normal(k[0], (pool + 1, page, KV * hd))
    vv = _normal(k[1], (pool + 1, page, KV * hd))
    if storage == "fp8":
        kk, ks = paged.quantize_vecs(kk)
        vv, vs = paged.quantize_vecs(vv)
    else:
        kk, vv = kk.astype(jnp.bfloat16), vv.astype(jnp.bfloat16)
        ks = vs = jnp.ones((pool + 1, page), jnp.float32)
    qpos = jax.random.randint(k[2], (B,), T // 2, T).astype(jnp.int32)
    args = (_normal(k[3], (B, H, hd)), kk, vv, ks, vs,
            _paged_table(k[4], B, pp, pool), qpos)
    return args, dict(scale=1.0 / math.sqrt(hd))


def _fp8_gemm(M, K, N):
    def build(key):
        k1, k2 = jax.random.split(key)
        return (_normal(k1, (M, K)), _normal(k2, (K, N)) / math.sqrt(K)), {}
    return build


def _mla_decode(key, B=4, T=512, H=128, R=512, Rr=64):
    k = jax.random.split(key, 5)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    npos = jax.random.randint(k[4], (B,), T // 2, T).astype(jnp.int32)
    pos = jnp.where(pos < npos[:, None], pos, -1)
    args = (_normal(k[0], (B, H, R)), _normal(k[1], (B, H, Rr)),
            _normal(k[2], (B, T, R), jnp.bfloat16),
            _normal(k[3], (B, T, Rr), jnp.bfloat16), pos, npos - 1)
    return args, dict(scale=1.0 / math.sqrt(R + Rr))


def _moe_gemm(D, F, E=8, C=64):
    def build(key):
        k1, k2 = jax.random.split(key)
        return (_normal(k1, (E, C, D), jnp.bfloat16),
                (_normal(k2, (E, D, F)) / math.sqrt(D)).astype(
                    jnp.bfloat16)), {}
    return build


def _flash_prefill(key, B=1, S=256, H=20, KV=20, hd=128):
    k = jax.random.split(key, 3)
    q = _normal(k[0], (B, S, H, hd), jnp.bfloat16)
    kk = _normal(k[1], (B, S, KV, hd), jnp.bfloat16)
    vv = _normal(k[2], (B, S, KV, hd), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.where(pos < S - 37, pos, -1)          # a padded tail
    return (q, kk, vv, pos, kpos), dict(causal=True,
                                        scale=1.0 / math.sqrt(hd))


def _logfmt_encode(key, N=64, D=7168):
    x = _normal(key, (N, D)) * jnp.exp(_normal(jax.random.fold_in(key, 1),
                                               (N, D)))
    return (x.at[0, :3].set(0.0),), dict(n_bits=8)


def _logfmt_decode(key, N=64, D=7168):
    c, mn, step = logfmt.encode(_normal(key, (N, D)) * 5, 8)
    return (c, mn, step), dict(n_bits=8, dtype=jnp.float32)


CASES: Dict[str, Case] = {
    "fp8_gemm/k2560": Case("fp8_gemm", _fp8_gemm(128, 2560, 6912),
                           2e-2, 2e-2),
    "fp8_gemm/k7168": Case("fp8_gemm", _fp8_gemm(128, 7168, 2048),
                           2e-2, 2e-2),
    "mla_decode": Case("mla_decode", _mla_decode, 2e-2, 2e-2),
    "moe_gemm/up": Case("moe_gemm", _moe_gemm(2048, 768), 3e-2, 3e-2),
    "moe_gemm/down": Case("moe_gemm", _moe_gemm(768, 2048), 3e-2, 3e-2),
    "paged_mla_decode": Case(
        "paged_mla_decode",
        lambda key: paged_mla_args(key, page=16, storage="fp8"),
        1e-4, 1e-4),
    "paged_gqa_decode": Case(
        "paged_gqa_decode",
        lambda key: paged_gqa_args(key, page=16, storage="fp8"),
        1e-4, 1e-4),
    "paged_gqa_decode/g5": Case(
        "paged_gqa_decode",
        lambda key: paged_gqa_args(key, page=16, storage="fp8", H=40, KV=8),
        1e-4, 1e-4),
    "flash_prefill": Case("flash_prefill", _flash_prefill, 2e-2, 2e-2),
    "logfmt_encode": Case("logfmt_encode", _logfmt_encode, 0.0, 0.0),
    "logfmt_decode": Case("logfmt_decode", _logfmt_decode, 1e-4, 1e-5),
}


def compare(case: Case, got, ref) -> Dict[str, float]:
    """Worst-case agreement of a ``pallas`` output with the ``ref`` one.

    Returns ``max_abs_err`` and ``bound_ratio`` (the largest
    ``|got - ref| / (atol + rtol*|ref|)``; at most 1 passes). LogFMT codes
    may differ by one level on under 0.1% of entries, as in the
    interpret-vs-ref sweep: ``bound_ratio`` there is the larger of the
    mismatch share over 1e-3 and the largest level step, and the sideband
    is held to rtol 1e-5."""
    if case.op == "logfmt_encode":
        (gc, gmn, gst), (rc, rmn, rst) = (jax.tree.map(np.asarray, got),
                                          jax.tree.map(np.asarray, ref))
        diff = gc.astype(np.int32) - rc.astype(np.int32)
        share = float((diff != 0).mean())
        side = max(float(np.abs(gmn - rmn).max()
                         / (1e-6 + 1e-5 * np.abs(rmn).max())),
                   float(np.abs(gst - rst).max()
                         / (1e-5 + 1e-5 * np.abs(rst).max())))
        return {"max_abs_err": float(np.abs(diff).max(initial=0)),
                "bound_ratio": max(share / 1e-3,
                                   float(np.abs(diff).max(initial=0)),
                                   side)}
    worst, ratio = 0.0, 0.0
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        err = np.abs(g - r)
        worst = max(worst, float(err.max()))
        ratio = max(ratio, float((err / (case.atol + case.rtol
                                         * np.abs(r))).max()))
    return {"max_abs_err": worst, "bound_ratio": ratio}
