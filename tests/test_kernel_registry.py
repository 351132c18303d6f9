"""Kernel dispatch registry: backend policy, jit composition, and the
backend-parity sweep that replaces the per-kernel copy-pasted parity
tests (every registered kernel runs ref vs interpret over a shape/dtype
grid through the one public entry point)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.kernels import registry
from repro.kernels.registry import BlockTable, pad_to_multiple


# ---------------------------------------------------------------------------
# Shared tiling layer
# ---------------------------------------------------------------------------


class TestTiling:
    def test_pad_to_multiple(self):
        x = jnp.ones((3, 5))
        y = pad_to_multiple(x, 0, 4)
        assert y.shape == (4, 5) and float(y[3].sum()) == 0.0
        assert pad_to_multiple(x, 1, 5) is x          # already aligned
        z = pad_to_multiple(jnp.zeros((2,), jnp.int32), 0, 4, value=-1)
        assert z.tolist() == [0, 0, -1, -1]

    def test_block_table_buckets(self):
        t = BlockTable({1: dict(b=8), 32: dict(b=32), 128: dict(b=128)})
        assert t.block(4, "b") == 8          # below all floors -> smallest
        assert t.block(32, "b") == 32
        assert t.block(100, "b") == 32
        assert t.block(4096, "b") == 128
        assert t.lookup(64) == {"b": 32}

    def test_block_table_validates(self):
        with pytest.raises(ValueError):
            BlockTable({})
        with pytest.raises(ValueError):
            BlockTable({0: dict(b=8)})


# ---------------------------------------------------------------------------
# Backend selection policy
# ---------------------------------------------------------------------------


@pytest.fixture
def probe_op():
    op = registry.kernel("_test_probe")
    try:
        yield op
    finally:
        registry._REGISTRY.pop("_test_probe", None)


def _attach_probe_backends(op):
    @op.backend("ref")
    @jax.jit
    def _ref(x):
        return x + 1.0

    @op.backend("pallas", "interpret")
    @functools.partial(jax.jit, static_argnames=("interpret",))
    def _kern(x, *, interpret):
        return x + (2.0 if interpret else 3.0)


class TestBackendPolicy:
    def test_platform_default(self):
        # no override, no env: TPU -> pallas, anything else -> interpret
        expect = "pallas" if jax.default_backend() == "tpu" else "interpret"
        assert kernels.active_backend() == expect

    def test_env_var_override(self, monkeypatch):
        monkeypatch.setenv(registry.ENV_VAR, "ref")
        assert kernels.active_backend() == "ref"
        monkeypatch.setenv(registry.ENV_VAR, "bogus")
        with pytest.raises(ValueError, match="bogus"):
            kernels.active_backend()

    def test_context_beats_env(self, monkeypatch):
        monkeypatch.setenv(registry.ENV_VAR, "ref")
        with kernels.use_backend("interpret", clear_caches=False):
            assert kernels.active_backend() == "interpret"
        assert kernels.active_backend() == "ref"

    def test_use_backend_nests_and_restores(self):
        base = kernels.active_backend()
        with kernels.use_backend("ref", clear_caches=False):
            with kernels.use_backend("interpret", clear_caches=False):
                assert kernels.active_backend() == "interpret"
            assert kernels.active_backend() == "ref"
        assert kernels.active_backend() == base

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError):
            with kernels.use_backend("cuda"):
                pass

    def test_dispatch_threads_interpret_flag(self, probe_op):
        _attach_probe_backends(probe_op)
        x = jnp.zeros(())
        with kernels.use_backend("interpret", clear_caches=False):
            assert float(probe_op(x)) == 2.0
        with kernels.use_backend("pallas", clear_caches=False):
            assert float(probe_op(x)) == 3.0   # probe's "pallas" is fake
        with kernels.use_backend("ref", clear_caches=False):
            assert float(probe_op(x)) == 1.0

    def test_missing_backend_is_loud(self, probe_op):
        @probe_op.backend("ref")
        def _ref(x):
            return x

        with kernels.use_backend("interpret", clear_caches=False):
            with pytest.raises(NotImplementedError, match="_test_probe"):
                probe_op(jnp.zeros(()))

    def test_duplicate_registration_rejected(self, probe_op):
        with pytest.raises(ValueError, match="already registered"):
            registry.kernel("_test_probe")

        @probe_op.backend("ref")
        def _ref(x):
            return x

        with pytest.raises(ValueError, match="already registered"):
            probe_op.backend("ref")(lambda x: x)

    def test_use_backend_changes_path_under_jit(self, probe_op):
        """The acceptance-criterion property: a caller that wrapped the op
        in its own jax.jit still follows ``use_backend`` — the backend is
        static at the kernels' jit boundary and the context drops jit
        caches on a real switch, so the outer jit retraces."""
        _attach_probe_backends(probe_op)
        outer = jax.jit(lambda x: probe_op(x) * 10.0)
        x = jnp.zeros(())
        base = {"interpret": 20.0, "pallas": 30.0}[kernels.active_backend()]
        assert float(outer(x)) == base        # traced once, cached
        with kernels.use_backend("ref"):
            assert float(outer(x)) == 10.0    # retraced onto the ref path
        assert float(outer(x)) == base        # restored (and retraced back)


# ---------------------------------------------------------------------------
# Backend-parity sweep: ref vs interpret for every registered kernel
# ---------------------------------------------------------------------------


def _allclose(rtol, atol):
    def cmp(got, ref):
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(r, np.float32),
                                       rtol=rtol, atol=atol)
    return cmp


def _logfmt_codes_close(got, ref):
    """Codes may differ by one level on <0.1% of entries (fp tie-breaks in
    Step); the fp32 sideband must match tightly."""
    (gc, gmn, gstep), (rc, rmn, rstep) = got, ref
    diff = np.asarray(gc).astype(np.int32) - np.asarray(rc).astype(np.int32)
    mismatch = diff != 0
    assert mismatch.mean() < 1e-3, mismatch.mean()
    assert np.abs(diff[mismatch]).max(initial=0) <= 1
    np.testing.assert_allclose(np.asarray(gmn), np.asarray(rmn),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gstep), np.asarray(rstep),
                               rtol=1e-5, atol=1e-5)


def _fp8_gemm_case(shape, dist):
    def build(rng):
        M, K, N = shape
        k1, k2 = jax.random.split(rng)
        x = jax.random.normal(k1, (M, K), jnp.float32)
        w = jax.random.normal(k2, (K, N), jnp.float32)
        if dist == "heavy":
            x = x * jnp.exp(jax.random.normal(k2, (M, K)))
        return (x, w), {}
    return build, _allclose(2e-2, 2e-2)


def _mla_case(dims, dtype):
    def build(rng):
        B, H, R, Rr, T = dims
        ks = jax.random.split(rng, 4)
        qa = jax.random.normal(ks[0], (B, H, R), jnp.float32)
        qr = jax.random.normal(ks[1], (B, H, Rr), jnp.float32)
        ckv = jax.random.normal(ks[2], (B, T, R)).astype(dtype)
        kr = jax.random.normal(ks[3], (B, T, Rr)).astype(dtype)
        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        npos = (T * 3) // 4
        pos = jnp.where(pos < npos, pos, -1)
        qpos = jnp.full((B,), npos - 1)
        return (qa, qr, ckv, kr, pos, qpos), dict(scale=0.11)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    return build, _allclose(tol, tol)


def _moe_case(dims, dtype):
    def build(rng):
        E, C, D, F = dims
        x = jax.random.normal(rng, (E, C, D)).astype(dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (E, D, F)).astype(dtype)
        return (x, w), {}
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    return build, _allclose(tol, tol)


def _paged_mla_case(dims, storage):
    def build(rng):
        from repro.core import paged
        B, H, R, Rr, pool, page, pp = dims
        ks = jax.random.split(rng, 4)
        qa = jax.random.normal(ks[0], (B, H, R), jnp.float32)
        qr = jax.random.normal(ks[1], (B, H, Rr), jnp.float32)
        ckv = jax.random.normal(ks[2], (pool + 1, page, R), jnp.float32)
        kr = jax.random.normal(ks[3], (pool + 1, page, Rr), jnp.float32)
        if storage == "fp8":
            ckv, cs = paged.quantize_vecs(ckv)
            kr, ks_ = paged.quantize_vecs(kr)
        else:
            cs = jnp.ones((pool + 1, page), jnp.float32)
            ks_ = jnp.ones((pool + 1, page), jnp.float32)
        # each slot owns a disjoint run of physical pages, trash beyond
        ids = jax.random.permutation(jax.random.PRNGKey(7), pool)[:B * pp]
        table = ids.reshape(B, pp).astype(jnp.int32)
        qpos = jnp.arange(B, dtype=jnp.int32) * 3 + (pp * page) // 2
        return (qa, qr, ckv, kr, cs, ks_, table, qpos), dict(scale=0.11)
    return build, _allclose(1e-4, 1e-4)


def _paged_gqa_case(dims, storage):
    def build(rng):
        from repro.core import paged
        B, H, KV, hd, pool, page, pp = dims
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (pool + 1, page, KV, hd), jnp.float32)
        v = jax.random.normal(ks[2], (pool + 1, page, KV, hd), jnp.float32)
        if storage == "fp8":
            k, k_s = paged.quantize_vecs(k, vec_ndim=2)
            v, v_s = paged.quantize_vecs(v, vec_ndim=2)
        else:
            k_s = jnp.ones((pool + 1, page), jnp.float32)
            v_s = jnp.ones((pool + 1, page), jnp.float32)
        # pools store one token's (KV, hd) entry as a flat row
        k = k.reshape(pool + 1, page, KV * hd)
        v = v.reshape(pool + 1, page, KV * hd)
        ids = jax.random.permutation(jax.random.PRNGKey(7), pool)[:B * pp]
        table = ids.reshape(B, pp).astype(jnp.int32)
        qpos = jnp.arange(B, dtype=jnp.int32) * 3 + (pp * page) // 2
        return (q, k, v, k_s, v_s, table, qpos), dict(scale=0.13)
    return build, _allclose(1e-4, 1e-4)


def _flash_prefill_case(dims, dtype, causal):
    def build(rng):
        B, S, T, H, KV, hd = dims
        ks = jax.random.split(rng, 4)
        q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
        k = jax.random.normal(ks[1], (B, T, KV, hd)).astype(dtype)
        v = jax.random.normal(ks[2], (B, T, KV, hd)).astype(dtype)
        qp = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        # ragged rows: row b keeps T - b real keys, pads carry kpos = -1
        lens = T - jnp.arange(B, dtype=jnp.int32)
        kp = jnp.where(jnp.arange(T)[None, :] < lens[:, None],
                       jnp.arange(T, dtype=jnp.int32)[None, :], -1)
        return (q, k, v, qp, kp), dict(causal=causal, scale=0.13)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    return build, _allclose(tol, tol)


def _logfmt_encode_case(shape, n_bits):
    def build(rng):
        x = jax.random.normal(rng, shape) * jnp.exp(
            jax.random.normal(jax.random.PRNGKey(2), shape))
        x = x.at[0, :3].set(0.0)
        return (x,), dict(n_bits=n_bits)
    return build, _logfmt_codes_close


def _logfmt_decode_case(shape, n_bits):
    def build(rng):
        from repro.core import logfmt
        x = jax.random.normal(rng, shape) * 5
        c, mn, step = logfmt.encode(x, n_bits)
        return (c, mn, step), dict(n_bits=n_bits, dtype=jnp.float32)
    return build, _allclose(1e-4, 1e-5)


PARITY_CASES = {
    "fp8_gemm": [
        _fp8_gemm_case((128, 128, 128), "normal"),
        _fp8_gemm_case((256, 256, 128), "heavy"),
        _fp8_gemm_case((384, 512, 256), "normal"),
        _fp8_gemm_case((100, 200, 72), "normal"),    # ragged -> padded
        _fp8_gemm_case((128, 384, 384), "heavy"),
    ],
    "mla_decode": [
        _mla_case((2, 8, 64, 16, 64), jnp.float32),
        _mla_case((2, 8, 64, 16, 64), jnp.bfloat16),
        _mla_case((1, 4, 128, 32, 96), jnp.float32),
        _mla_case((3, 16, 32, 8, 128), jnp.bfloat16),
        _mla_case((1, 4, 64, 16, 40), jnp.float32),  # ragged cache length
    ],
    "moe_gemm": [
        _moe_case((2, 16, 32, 24), jnp.float32),
        _moe_case((4, 128, 128, 128), jnp.float32),
        _moe_case((4, 128, 128, 128), jnp.bfloat16),
        _moe_case((1, 8, 256, 64), jnp.bfloat16),
        _moe_case((3, 40, 72, 96), jnp.float32),     # ragged -> padded
    ],
    "paged_mla_decode": [
        _paged_mla_case((2, 8, 64, 16, 12, 16, 4), "fp8"),
        _paged_mla_case((2, 8, 64, 16, 12, 16, 4), "bf16"),
        _paged_mla_case((1, 4, 128, 32, 8, 8, 6), "fp8"),
        _paged_mla_case((3, 16, 32, 8, 24, 4, 8), "fp8"),
    ],
    "paged_gqa_decode": [
        _paged_gqa_case((2, 8, 2, 32, 12, 16, 4), "fp8"),
        _paged_gqa_case((2, 8, 2, 32, 12, 16, 4), "bf16"),
        _paged_gqa_case((1, 4, 4, 64, 8, 8, 6), "fp8"),     # G = 1 (MHA)
        _paged_gqa_case((3, 16, 2, 32, 24, 4, 8), "fp8"),
    ],
    "flash_prefill": [
        _flash_prefill_case((2, 16, 16, 4, 2, 32), jnp.float32, True),
        _flash_prefill_case((2, 16, 16, 4, 2, 32), jnp.bfloat16, True),
        _flash_prefill_case((1, 8, 8, 4, 4, 16), jnp.float32, True),
        _flash_prefill_case((2, 32, 32, 8, 2, 64), jnp.float32, True),
        _flash_prefill_case((1, 128, 128, 4, 2, 32), jnp.float32, True),
        _flash_prefill_case((2, 16, 16, 2, 1, 32), jnp.float32, False),
    ],
    "logfmt_encode": [
        _logfmt_encode_case((8, 128), 8),
        _logfmt_encode_case((64, 256), 10),
        _logfmt_encode_case((128, 512), 8),
        _logfmt_encode_case((100, 384), 8),          # ragged rows
    ],
    "logfmt_decode": [
        _logfmt_decode_case((32, 256), 8),
        _logfmt_decode_case((8, 128), 10),
        _logfmt_decode_case((100, 384), 8),          # ragged rows
    ],
}


class TestBackendParity:
    def test_every_registered_kernel_is_swept(self):
        """Adding a kernel to the registry obliges you to add parity
        cases here — the sweep is the contract, not per-kernel tests."""
        assert set(kernels.names()) == set(PARITY_CASES)

    @pytest.mark.parametrize(
        "name,case_idx",
        [(n, i) for n, cs in sorted(PARITY_CASES.items())
         for i in range(len(cs))])
    def test_ref_vs_interpret(self, rng, name, case_idx):
        build, compare = PARITY_CASES[name][case_idx]
        args, kwargs = build(rng)
        op = kernels.get(name)
        with kernels.use_backend("interpret", clear_caches=False):
            got = op(*args, **kwargs)
        with kernels.use_backend("ref", clear_caches=False):
            ref = op(*args, **kwargs)
        compare(got, ref)
