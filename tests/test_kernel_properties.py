"""Seeded property sweep over every registered kernel.

Random shapes pinned to tiling boundaries (page_size±1 resident
tokens, single-page tables, a fully-allocated pool, lone slots),
interpret-vs-ref agreement within each kernel's documented tolerance,
fp8 quantize->dequantize round-trip error bounds, the exhaustive
256-code pin behind ``paged.e4m3_decode``, and golden-value fixtures
for the paged attention reference oracles.

Draws come from seeded numpy PCG64 generators so the sweep is
deterministic; a hypothesis fuzz pass widens shape coverage.
"""
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core import logfmt, paged
from repro.kernels.paged_attention import paged_attention as pa
from repro.kernels.paged_attention.ref import (paged_gqa_decode_ref,
                                               paged_mla_decode_ref)


def _gen(*salt):
    """Deterministic generator keyed on strings/ints (not Python hash)."""
    seed = [s if isinstance(s, int) else zlib.crc32(s.encode())
            for s in salt]
    return np.random.default_rng(seed)


def _normal(gen, shape, dtype=jnp.float32):
    return jnp.asarray(gen.standard_normal(shape), jnp.float32).astype(dtype)


def _allclose(rtol, atol):
    def cmp(got, ref):
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(r, np.float32),
                                       rtol=rtol, atol=atol)
    return cmp


def _codes_close(got, ref):
    """logfmt codes may differ by one level on <0.1% of entries."""
    (gc, gmn, gstep), (rc, rmn, rstep) = got, ref
    diff = np.asarray(gc).astype(np.int32) - np.asarray(rc).astype(np.int32)
    mismatch = diff != 0
    assert mismatch.mean() < 1e-3, mismatch.mean()
    assert np.abs(diff[mismatch]).max(initial=0) <= 1
    np.testing.assert_allclose(np.asarray(gmn), np.asarray(rmn),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gstep), np.asarray(rstep),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Paged-pool geometry at tiling boundaries
# ---------------------------------------------------------------------------

PAGED_BOUNDARIES = ("page_minus_1", "page_plus_1", "single_page",
                    "full_pool", "lone_slot")


def _paged_geometry(gen, boundary):
    """(B, pp, page, pool, qpos) hitting one named tiling boundary.

    ``page_minus_1`` / ``page_plus_1`` put ``page∓...±1`` resident tokens
    in the slot (the online-softmax loop ends one lane short of / one
    lane into a page); ``single_page`` shrinks the table to one entry;
    ``full_pool`` allocates every physical page (no spare beyond trash);
    ``lone_slot`` runs the grid with B=1.
    """
    page = int(gen.choice([4, 8, 16]))
    if boundary == "single_page":
        B, pp = int(gen.integers(1, 4)), 1
        qpos = gen.integers(0, page, size=B)
    elif boundary == "lone_slot":
        B, pp = 1, int(gen.integers(2, 5))
        qpos = gen.integers(0, pp * page, size=B)
    else:
        B, pp = int(gen.integers(2, 4)), int(gen.integers(2, 4))
        if boundary == "page_minus_1":
            qpos = np.full(B, page - 2)      # page-1 tokens resident
        elif boundary == "page_plus_1":
            qpos = np.full(B, page)          # page+1 tokens resident
        else:
            qpos = gen.integers(0, pp * page, size=B)
    spare = 0 if boundary == "full_pool" else int(gen.integers(1, 4))
    pool = B * pp + spare
    return B, pp, page, pool, np.asarray(qpos, np.int32)


def _paged_table(gen, B, pp, pool):
    ids = gen.permutation(pool)[:B * pp]     # trash page is index ``pool``
    return jnp.asarray(ids.reshape(B, pp), jnp.int32)


def _paged_mla_args(gen, boundary):
    B, pp, page, pool, qpos = _paged_geometry(gen, boundary)
    H = int(gen.choice([2, 4, 8]))
    R, Rr = int(gen.choice([16, 32])), int(gen.choice([4, 8]))
    qa = _normal(gen, (B, H, R))
    qr = _normal(gen, (B, H, Rr))
    ckv = _normal(gen, (pool + 1, page, R))
    kr = _normal(gen, (pool + 1, page, Rr))
    if gen.integers(2):                      # fp8 storage
        ckv, cs = paged.quantize_vecs(ckv)
        kr, ks = paged.quantize_vecs(kr)
    else:
        cs = jnp.ones((pool + 1, page), jnp.float32)
        ks = jnp.ones((pool + 1, page), jnp.float32)
    table = _paged_table(gen, B, pp, pool)
    args = (qa, qr, ckv, kr, cs, ks, table, jnp.asarray(qpos))
    return args, dict(scale=0.11)


def _paged_gqa_args(gen, boundary):
    B, pp, page, pool, qpos = _paged_geometry(gen, boundary)
    KV = int(gen.choice([1, 2, 4]))
    G = int(gen.choice([1, 2, 4]))
    hd = int(gen.choice([8, 16, 32]))
    q = _normal(gen, (B, KV * G, hd))
    k = _normal(gen, (pool + 1, page, KV, hd))
    v = _normal(gen, (pool + 1, page, KV, hd))
    if gen.integers(2):                      # fp8 storage
        k, k_s = paged.quantize_vecs(k, vec_ndim=2)
        v, v_s = paged.quantize_vecs(v, vec_ndim=2)
    else:
        k_s = jnp.ones((pool + 1, page), jnp.float32)
        v_s = jnp.ones((pool + 1, page), jnp.float32)
    # pools store one token's (KV, hd) entry as a flat row
    k = k.reshape(pool + 1, page, KV * hd)
    v = v.reshape(pool + 1, page, KV * hd)
    table = _paged_table(gen, B, pp, pool)
    args = (q, k, v, k_s, v_s, table, jnp.asarray(qpos))
    return args, dict(scale=0.13)


# ---------------------------------------------------------------------------
# Per-op shape samplers (one entry per registered kernel — coverage is
# asserted, like the registry parity sweep's PARITY_CASES contract)
# ---------------------------------------------------------------------------


def _sample_fp8_gemm(gen):
    M = int(gen.choice([64, 100, 128]))
    K = int(gen.choice([96, 128, 200]))
    N = int(gen.choice([24, 72, 128]))
    x = _normal(gen, (M, K))
    if gen.integers(2):
        x = x * jnp.exp(_normal(gen, (M, K)))
    w = _normal(gen, (K, N))
    return (x, w), {}, _allclose(2e-2, 2e-2)


def _sample_mla_decode(gen):
    B, H = int(gen.integers(1, 4)), int(gen.choice([4, 8]))
    R, Rr = int(gen.choice([32, 64])), int(gen.choice([8, 16]))
    T = int(gen.choice([16, 32, 48]))
    qa = _normal(gen, (B, H, R))
    qr = _normal(gen, (B, H, Rr))
    dtype = jnp.float32 if gen.integers(2) else jnp.bfloat16
    ckv = _normal(gen, (B, T, R), dtype)
    kr = _normal(gen, (B, T, Rr), dtype)
    npos = int(gen.integers(1, T + 1))
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    pos = jnp.where(pos < npos, pos, -1)
    qpos = jnp.full((B,), npos - 1)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    return (qa, qr, ckv, kr, pos, qpos), dict(scale=0.11), _allclose(tol, tol)


def _sample_moe_gemm(gen):
    E, C = int(gen.integers(1, 4)), int(gen.choice([8, 16, 40]))
    D, F = int(gen.choice([32, 72])), int(gen.choice([24, 64]))
    dtype = jnp.float32 if gen.integers(2) else jnp.bfloat16
    x = _normal(gen, (E, C, D), dtype)
    w = _normal(gen, (E, D, F), dtype)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    return (x, w), {}, _allclose(tol, tol)


def _sample_paged_mla_decode(gen):
    b = PAGED_BOUNDARIES[int(gen.integers(len(PAGED_BOUNDARIES)))]
    args, kwargs = _paged_mla_args(gen, b)
    return args, kwargs, _allclose(1e-4, 1e-4)


def _sample_paged_gqa_decode(gen):
    b = PAGED_BOUNDARIES[int(gen.integers(len(PAGED_BOUNDARIES)))]
    args, kwargs = _paged_gqa_args(gen, b)
    return args, kwargs, _allclose(1e-4, 1e-4)


def _sample_flash_prefill(gen):
    S = int(gen.choice([8, 16, 32]))         # power-of-two buckets
    B = int(gen.integers(1, 3))
    KV = int(gen.choice([1, 2]))
    G = int(gen.choice([1, 2]))
    hd = int(gen.choice([16, 32]))
    dtype = jnp.float32 if gen.integers(2) else jnp.bfloat16
    causal = bool(gen.integers(2))
    q = _normal(gen, (B, S, KV * G, hd), dtype)
    k = _normal(gen, (B, S, KV, hd), dtype)
    v = _normal(gen, (B, S, KV, hd), dtype)
    qp = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    lens = jnp.asarray(gen.integers(1, S + 1, size=B), jnp.int32)
    kp = jnp.where(jnp.arange(S)[None, :] < lens[:, None],
                   jnp.arange(S, dtype=jnp.int32)[None, :], -1)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    return ((q, k, v, qp, kp), dict(causal=causal, scale=0.13),
            _allclose(tol, tol))


def _sample_logfmt_encode(gen):
    shape = (int(gen.choice([8, 64, 100])), int(gen.choice([128, 256, 384])))
    n_bits = int(gen.choice([8, 10]))
    x = _normal(gen, shape) * jnp.exp(_normal(gen, shape))
    x = x.at[0, :3].set(0.0)
    return (x,), dict(n_bits=n_bits), _codes_close


def _sample_logfmt_decode(gen):
    shape = (int(gen.choice([8, 64, 100])), int(gen.choice([128, 256])))
    n_bits = int(gen.choice([8, 10]))
    x = _normal(gen, shape) * 5
    c, mn, step = logfmt.encode(x, n_bits)
    return ((c, mn, step), dict(n_bits=n_bits, dtype=jnp.float32),
            _allclose(1e-4, 1e-5))


SAMPLERS = {
    "fp8_gemm": _sample_fp8_gemm,
    "mla_decode": _sample_mla_decode,
    "moe_gemm": _sample_moe_gemm,
    "paged_mla_decode": _sample_paged_mla_decode,
    "paged_gqa_decode": _sample_paged_gqa_decode,
    "flash_prefill": _sample_flash_prefill,
    "logfmt_encode": _sample_logfmt_encode,
    "logfmt_decode": _sample_logfmt_decode,
}


def _run_case(name, args, kwargs, compare):
    op = kernels.get(name)
    with kernels.use_backend("interpret", clear_caches=False):
        got = op(*args, **kwargs)
    with kernels.use_backend("ref", clear_caches=False):
        ref = op(*args, **kwargs)
    compare(got, ref)


class TestPropertySweep:
    def test_covers_every_registered_kernel(self):
        assert set(kernels.names()) == set(SAMPLERS)

    @pytest.mark.parametrize(
        "name,seed",
        [(n, s) for n in sorted(SAMPLERS) for s in (0, 1)])
    def test_interpret_matches_ref(self, name, seed):
        args, kwargs, compare = SAMPLERS[name](_gen(name, seed))
        _run_case(name, args, kwargs, compare)

    @pytest.mark.parametrize("name", ["paged_mla_decode", "paged_gqa_decode"])
    @pytest.mark.parametrize("boundary", PAGED_BOUNDARIES)
    def test_paged_tiling_boundaries(self, name, boundary):
        """Every named boundary is exercised explicitly (the generic
        sweep draws boundaries at random, which need not cover all)."""
        gen = _gen(name, boundary)
        build = (_paged_mla_args if name == "paged_mla_decode"
                 else _paged_gqa_args)
        args, kwargs = build(gen, boundary)
        _run_case(name, args, kwargs, _allclose(1e-4, 1e-4))

    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_fuzz_paged_gqa_decode(self, seed):
        """Hypothesis widens the seed space beyond the fixed sweep."""
        args, kwargs, compare = _sample_paged_gqa_decode(_gen("fuzz", seed))
        _run_case("paged_gqa_decode", args, kwargs, compare)


# ---------------------------------------------------------------------------
# paged_mla_decode works on each slot's live pages only
# ---------------------------------------------------------------------------


def _full_walk_kernel(table_ref, qpos_ref, qa_ref, qr_ref, ckv_ref, kr_ref,
                      cs_ref, ks_ref, o_ref, m_ref, l_ref, acc_ref, *, page):
    """The MLA kernel's body without its live-page rule: every grid step
    fetches ``table[b, t]`` and folds it in, dead or not."""
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, pa.NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ckv = ckv_ref[0].astype(jnp.float32)
    kr = kr_ref[0].astype(jnp.float32)
    cs = cs_ref[0]
    s = (pa.dot_nt(qa_ref[0], ckv) * cs
         + pa.dot_nt(qr_ref[0], kr) * ks_ref[0])
    lpos = t * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    pa._online_softmax_step(s, lpos <= qpos_ref[b], lambda p: p * cs,
                            m_ref, l_ref, acc_ref, ckv)

    @pl.when(t == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _full_walk(qa, qr, ckv, kr, cs, ks, table, qpos, *, scale):
    """``paged_mla_decode`` as a walk over all ``pp`` pages of every slot
    (interpret mode): the numbers the live-page rule must keep."""
    (B, H, R), Rr, page = qa.shape, qr.shape[-1], ckv.shape[1]
    page_map = lambda b, t, tbl, qp: (tbl[b, t], 0, 0)  # noqa: E731
    slot_map = lambda b, t, tbl, qp: (b, 0, 0)          # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, table.shape[1]),
        in_specs=[pl.BlockSpec((1, H, R), slot_map),
                  pl.BlockSpec((1, H, Rr), slot_map),
                  pl.BlockSpec((1, page, R), page_map),
                  pl.BlockSpec((1, page, Rr), page_map),
                  pl.BlockSpec((1, 1, page), page_map),
                  pl.BlockSpec((1, 1, page), page_map)],
        out_specs=pl.BlockSpec((1, H, R), slot_map),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, R), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_full_walk_kernel, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        interpret=True,
    )(table, qpos, qa.astype(jnp.float32) * scale,
      qr.astype(jnp.float32) * scale, ckv, kr, pa._row_scales(cs),
      pa._row_scales(ks))


def _interpret_mla(args, kwargs):
    with kernels.use_backend("interpret", clear_caches=False):
        return np.asarray(kernels.get("paged_mla_decode")(*args, **kwargs))


def _assert_bitwise(got, want):
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


# the property samples: the sweep's two draws and each named boundary
MLA_SAMPLES = [("sweep", 0), ("sweep", 1)] + [(b, None)
                                              for b in PAGED_BOUNDARIES]


def _mla_sample(kind, seed):
    if kind == "sweep":
        args, kwargs, _ = _sample_paged_mla_decode(
            _gen("paged_mla_decode", seed))
        return args, kwargs
    return _paged_mla_args(_gen("paged_mla_decode", kind), kind)


class TestPagedMLALivePages:
    def test_host_rule_is_the_kernels(self):
        """``live_pages`` (the engine's counter) counts the pages up to
        the kernel's ``_last_live_page`` for every query position."""
        page, pp = 8, 4
        qpos = np.arange(-3, pp * page + 9, dtype=np.int32)
        last = jax.vmap(lambda q: pa._last_live_page(q, page, pp))(qpos)
        np.testing.assert_array_equal(pa.live_pages(qpos, page, pp),
                                      np.asarray(last) + 1)

    @pytest.mark.parametrize("kind,seed", MLA_SAMPLES)
    def test_bitwise_equal_to_full_walk(self, kind, seed):
        """Skipping dead pages changes no bit of a finite pool's output."""
        args, kwargs = _mla_sample(kind, seed)
        _assert_bitwise(_interpret_mla(args, kwargs),
                        np.asarray(_full_walk(*args, **kwargs)))

    @pytest.mark.parametrize("case", ["zero", "page_minus_1", "page",
                                      "last_row", "negative"])
    def test_dead_pages_never_reach_the_output(self, case):
        """Lane 0 sits at the case's ``qpos``; lanes 1 and 2 reserve a
        few pages past their last live one and point the rest of their
        row at the trash page. Every dead page (reserved above ``qpos``,
        or trash) holds e4m3 NaN bytes and NaN scales, and the output is
        bitwise that of zeros there, which is the full walk's."""
        gen = _gen("live_pages", case)
        B, pp, page, H, R, Rr = 3, 4, 8, 4, 32, 8
        pool = B * pp
        qpos = np.array([{"zero": 0, "page_minus_1": page - 1,
                          "page": page, "last_row": pp * page - 1,
                          "negative": -1}[case],
                         page + 3, 2 * page], np.int32)
        last = np.maximum(qpos, 0) // page          # all below pp
        ids = gen.permutation(pool).reshape(B, pp)
        table = ids.copy()
        for b in (1, 2):
            table[b, last[b] + 2:] = pool           # the trash page
        dead = np.arange(pp)[None, :] > last[:, None]
        dead_ids = np.unique(table[dead])
        assert pool in dead_ids and dead_ids.size > 1
        ckv, cs = paged.quantize_vecs(_normal(gen, (pool + 1, page, R)))
        kr, ks = paged.quantize_vecs(_normal(gen, (pool + 1, page, Rr)))
        nan8 = jax.lax.bitcast_convert_type(jnp.uint8(0x7F), paged.E4M3)

        def fill(x, v):
            return x.at[dead_ids].set(jnp.asarray(v, x.dtype))

        q = (_normal(gen, (B, H, R)), _normal(gen, (B, H, Rr)))
        rest = (jnp.asarray(table, jnp.int32), jnp.asarray(qpos))
        zeros = (fill(ckv, 0), fill(kr, 0), fill(cs, 0), fill(ks, 0))
        poison = (fill(ckv, nan8), fill(kr, nan8), fill(cs, jnp.nan),
                  fill(ks, jnp.nan))
        kwargs = dict(scale=0.11)
        out = _interpret_mla(q + poison + rest, kwargs)
        assert np.isfinite(out).all()
        _assert_bitwise(out, _interpret_mla(q + zeros + rest, kwargs))
        _assert_bitwise(out, np.asarray(_full_walk(*q, *zeros, *rest,
                                                   **kwargs)))
        if case == "negative":
            assert (out[0] == 0).all()


# ---------------------------------------------------------------------------
# FP8 quantize -> dequantize round-trip bounds
# ---------------------------------------------------------------------------


class TestFp8RoundTrip:
    @pytest.mark.parametrize("vec_ndim", [1, 2])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_round_trip_error_bound(self, vec_ndim, seed):
        """E4M3 carries 3 mantissa bits: normals round-trip within a
        relative half-ulp of 2^-4; values tiny relative to the token's
        amax land in the subnormal range, bounded in absolute terms by
        half the subnormal step (scale * 2^-10; we allow 2^-9)."""
        gen = _gen("fp8rt", vec_ndim, seed)
        shape = (5, 7) + ((4, 6) if vec_ndim == 2 else (16,))
        x = jnp.asarray(gen.standard_normal(shape) *
                        np.exp(gen.standard_normal(shape)), jnp.float32)
        q, scale = paged.quantize_vecs(x, vec_ndim=vec_ndim)
        rt = paged.dequantize_vecs(q, scale, vec_ndim=vec_ndim)
        err = np.abs(np.asarray(x) - np.asarray(rt))
        s = np.asarray(scale).reshape(scale.shape + (1,) * vec_ndim)
        bound = 2.0**-4 * np.abs(np.asarray(x)) + s * 2.0**-9
        assert (err <= bound + 1e-12).all(), float((err - bound).max())

    def test_zero_and_amax_round_trip_exactly(self):
        x = jnp.asarray([[0.0, -3.5, 7.0, 0.25]], jnp.float32)
        q, scale = paged.quantize_vecs(x)
        rt = np.asarray(paged.dequantize_vecs(q, scale))
        assert rt[0, 0] == 0.0
        # the token amax maps to E4M3_MAX exactly, so it survives verbatim
        np.testing.assert_allclose(rt[0, 2], 7.0, rtol=1e-6)

    def test_byte_pool_bitcast_is_lossless(self):
        """uint8 byte-pool storage (``_to_store``) is a bitcast, not a
        value convert: decode of the stored byte equals decode of the
        E4M3 value for every token."""
        gen = _gen("bytepool")
        x = jnp.asarray(gen.standard_normal((3, 8, 2, 4)), jnp.float32)
        q, _ = paged.quantize_vecs(x, vec_ndim=2)
        pool = jnp.zeros(q.shape, jnp.uint8)
        stored = paged._to_store(pool, q)
        assert stored.dtype == jnp.uint8
        np.testing.assert_array_equal(
            np.asarray(paged.e4m3_decode(stored)),
            np.asarray(q.astype(jnp.float32)))


class TestE4M3DecodeTable:
    def test_all_256_codes_match_astype(self):
        """paged.e4m3_decode's LUT vs XLA's f8->f32 convert, all codes:
        254 are bit-exact, the 2 NaN encodings decode to NaN both ways."""
        codes = jnp.arange(256, dtype=jnp.uint8)
        via_astype = np.asarray(jax.lax.bitcast_convert_type(
            codes, paged.E4M3).astype(jnp.float32))
        via_lut = np.asarray(paged.e4m3_decode(codes))
        nan = np.isnan(via_astype)
        assert nan.sum() == 2 and set(np.where(nan)[0]) == {0x7F, 0xFF}
        assert np.isnan(via_lut[nan]).all()
        assert (via_astype[~nan].view(np.uint32)
                == via_lut[~nan].view(np.uint32)).all()

    def test_accepts_e4m3_and_uint8_inputs(self):
        codes = jnp.arange(256, dtype=jnp.uint8)
        as_f8 = jax.lax.bitcast_convert_type(codes, paged.E4M3)
        a = np.asarray(paged.e4m3_decode(codes))
        b = np.asarray(paged.e4m3_decode(as_f8))
        np.testing.assert_array_equal(a[~np.isnan(a)], b[~np.isnan(b)])


# ---------------------------------------------------------------------------
# Golden-value fixtures for the paged reference oracles
# ---------------------------------------------------------------------------
#
# Inputs are built from an integer LCG (no libm, no jax.random) so they
# are bit-identical on every platform and jax version; the expected
# outputs below were computed from the checked-in reference oracles and
# pin their numerics — a refactor that changes the math fails here even
# if interpret and ref drift together.


def _det(shape, salt):
    n = int(np.prod(shape))
    u = (np.arange(n, dtype=np.uint64) * np.uint64(2654435761)
         + np.uint64(salt) * np.uint64(97003)) % np.uint64(100003)
    vals = (u.astype(np.float64) / 100003.0 - 0.5) * 2.0
    return jnp.asarray(vals.astype(np.float32).reshape(shape))


GOLDEN_MLA = np.array([[[-0.06367323,  0.05030647,  0.18153943, -0.19049442],
  [-0.14528413,  0.12947385,  0.09992852, -0.09282090]],

 [[ 0.07593291,  0.04443243, -0.07943555, -0.02055797],
  [ 0.11260585,  0.02728340, -0.04398158, -0.01707391]]], np.float32)

GOLDEN_GQA = np.array([[[ 0.00127724, -0.42957005,  0.24648991],
  [-0.06496401, -0.42423594,  0.18024865],
  [-0.19455341,  0.38098139,  0.05065925],
  [-0.10705849,  0.36845201,  0.13815413]],

 [[ 0.09921712, -0.24979752,  0.04561076],
  [-0.03357625, -0.07226399, -0.10721944],
  [ 0.15499693, -0.12559164, -0.10464408],
  [ 0.13293037, -0.22111936,  0.08159731]]], np.float32)

GOLDEN_GQA_FP8 = np.array([[[ 0.00459046, -0.42940792,  0.23031308],
  [-0.06422430, -0.42380810,  0.16422766],
  [-0.18074800,  0.38204637,  0.05733209],
  [-0.09607048,  0.36845085,  0.14243031]],

 [[ 0.10612536, -0.25163218,  0.04176489],
  [-0.02394619, -0.07416371, -0.10983831],
  [ 0.15415637, -0.12603141, -0.09800611],
  [ 0.12940963, -0.21883059,  0.08834893]]], np.float32)


def _golden_mla_inputs():
    B, H, R, Rr, pool, page, pp = 2, 2, 4, 2, 5, 4, 2
    qa = _det((B, H, R), 1)
    qr = _det((B, H, Rr), 2)
    ckv = _det((pool + 1, page, R), 3)
    kr = _det((pool + 1, page, Rr), 4)
    cs = jnp.ones((pool + 1, page), jnp.float32)
    ks = jnp.ones((pool + 1, page), jnp.float32)
    table = jnp.asarray([[3, 0], [1, 4]], jnp.int32)
    qpos = jnp.asarray([3, 5], jnp.int32)
    return qa, qr, ckv, kr, cs, ks, table, qpos


def _golden_gqa_inputs(fp8):
    B, H, KV, hd, pool, page, pp = 2, 4, 2, 3, 5, 4, 2
    q = _det((B, H, hd), 5)
    k = _det((pool + 1, page, KV, hd), 6)
    v = _det((pool + 1, page, KV, hd), 7)
    if fp8:
        k, k_s = paged.quantize_vecs(k, vec_ndim=2)
        v, v_s = paged.quantize_vecs(v, vec_ndim=2)
    else:
        k_s = jnp.ones((pool + 1, page), jnp.float32)
        v_s = jnp.ones((pool + 1, page), jnp.float32)
    k = k.reshape(pool + 1, page, KV * hd)
    v = v.reshape(pool + 1, page, KV * hd)
    table = jnp.asarray([[2, 4], [0, 3]], jnp.int32)
    qpos = jnp.asarray([2, 5], jnp.int32)
    return q, k, v, k_s, v_s, table, qpos


class TestGoldenFixtures:
    def test_paged_mla_decode_ref_golden(self):
        out = paged_mla_decode_ref(*_golden_mla_inputs(), scale=0.25)
        np.testing.assert_allclose(np.asarray(out), GOLDEN_MLA,
                                   rtol=1e-5, atol=1e-6)

    def test_paged_gqa_decode_ref_golden(self):
        out = paged_gqa_decode_ref(*_golden_gqa_inputs(False), scale=0.3)
        np.testing.assert_allclose(np.asarray(out), GOLDEN_GQA,
                                   rtol=1e-5, atol=1e-6)

    def test_paged_gqa_decode_ref_golden_fp8(self):
        """Pins the quantize -> byte-store -> LUT-dequant chain end to
        end, not just the attention math."""
        out = paged_gqa_decode_ref(*_golden_gqa_inputs(True), scale=0.3)
        np.testing.assert_allclose(np.asarray(out), GOLDEN_GQA_FP8,
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("name", ["paged_mla_decode",
                                      "paged_gqa_decode"])
    def test_interpret_backend_matches_golden(self, name):
        """The kernel itself (interpret backend) reproduces the golden
        values, tying the Pallas implementation to the pinned numerics
        rather than only to a co-evolving oracle."""
        if name == "paged_mla_decode":
            args, gold, scale = _golden_mla_inputs(), GOLDEN_MLA, 0.25
        else:
            args, gold, scale = _golden_gqa_inputs(False), GOLDEN_GQA, 0.3
        op = kernels.get(name)
        with kernels.use_backend("interpret", clear_caches=False):
            out = op(*args, scale=scale)
        np.testing.assert_allclose(np.asarray(out, np.float32), gold,
                                   rtol=1e-4, atol=1e-4)
