"""Chunked-attention equivalence + SSD/RG-LRU numerics (property tests on
the recurrences). moe_gemm parity moved to test_kernel_registry.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st


class TestChunkedAttention:
    @pytest.mark.parametrize("S,KV,window", [(1024, 2, 0), (2048, 4, 0),
                                             (1024, 1, 256)])
    def test_matches_direct(self, rng, S, KV, window):
        from repro.models.layers import _attn_direct, attention_scores
        B, H, hd = 2, 4, 32
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, KV, hd))
        v = jax.random.normal(ks[2], (B, S, KV, hd))
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        y1 = attention_scores(q, k, v, causal=True, q_pos=pos, k_pos=pos,
                              window=window, block_q=256)
        y2 = _attn_direct(q, k, v, causal=True, q_pos=pos, k_pos=pos,
                          window=window, scale=1 / 32 ** 0.5)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-4, atol=2e-4)

    def test_grads_match(self, rng):
        from repro.models.layers import _attn_direct, attention_scores
        B, S, H, hd = 1, 512, 2, 16
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, 2, hd))
        v = jax.random.normal(ks[2], (B, S, 2, hd))
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        g1 = jax.grad(lambda q: (attention_scores(
            q, k, v, causal=True, q_pos=pos, k_pos=pos, block_q=128) ** 2
        ).sum())(q)
        g2 = jax.grad(lambda q: (_attn_direct(
            q, k, v, causal=True, q_pos=pos, k_pos=pos,
            scale=1 / 16 ** 0.5) ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-3, atol=1e-3)


class TestSSD:
    def test_chunked_equals_stepwise(self, rng):
        """SSD chunked scan == token-by-token recurrence (state-space
        duality, both sides)."""
        from repro.models.ssm import _ssd_scan
        B, S, H, P, N = 1, 32, 2, 4, 8
        ks = jax.random.split(rng, 4)
        x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
        Cm = jax.random.normal(jax.random.PRNGKey(9), (B, S, N), jnp.float32)
        y_chunk, S_f = _ssd_scan(x, dt, A, Bm, Cm, chunk=8)
        # stepwise reference
        st = jnp.zeros((B, H, P, N))
        ys = []
        for t in range(S):
            a = jnp.exp(dt[:, t] * A)                       # (B,H)
            upd = jnp.einsum("bh,bn,bhp->bhpn", dt[:, t], Bm[:, t], x[:, t])
            st = st * a[..., None, None] + upd
            ys.append(jnp.einsum("bn,bhpn->bhp", Cm[:, t], st))
        y_ref = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(S_f), np.asarray(st),
                                   rtol=2e-3, atol=2e-3)

    @given(st.integers(1, 6))
    @settings(max_examples=5, deadline=None)
    def test_property_chunk_size_invariance(self, c_pow):
        """Output independent of chunk size (exactness of the duality)."""
        from repro.models.ssm import _ssd_scan
        chunk = 2 ** c_pow
        rs = np.random.RandomState(c_pow)
        B, S, H, P, N = 1, 64, 1, 2, 4
        x = jnp.asarray(rs.randn(B, S, H, P), jnp.float32)
        dt = jax.nn.softplus(jnp.asarray(rs.randn(B, S, H), jnp.float32))
        A = -jnp.exp(jnp.asarray(rs.randn(H), jnp.float32) * 0.2)
        Bm = jnp.asarray(rs.randn(B, S, N), jnp.float32)
        Cm = jnp.asarray(rs.randn(B, S, N), jnp.float32)
        y1, _ = _ssd_scan(x, dt, A, Bm, Cm, chunk=min(chunk, 64))
        y2, _ = _ssd_scan(x, dt, A, Bm, Cm, chunk=64)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=3e-3, atol=3e-3)


class TestRGLRU:
    def test_scan_equals_stepwise(self, rng):
        from repro.models.rglru import _rg_lru
        w = 16
        p = {"wa": jax.random.normal(rng, (w, w)) * 0.1,
             "ba": jnp.zeros(w),
             "wi": jax.random.normal(jax.random.PRNGKey(1), (w, w)) * 0.1,
             "bi": jnp.zeros(w),
             "lam": jax.random.normal(jax.random.PRNGKey(2), (w,))}
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, w), jnp.float32)
        y, h_last = _rg_lru(x, p, None)
        # stepwise
        h = jnp.zeros((2, w))
        for t in range(24):
            xt = x[:, t]
            r = jax.nn.sigmoid(xt @ p["wa"] + p["ba"])
            i = jax.nn.sigmoid(xt @ p["wi"] + p["bi"])
            a = jnp.exp(-8.0 * jax.nn.softplus(p["lam"]) * r)
            h = a * h + jnp.sqrt(jnp.maximum(1 - a * a, 1e-12)) * (i * xt)
        np.testing.assert_allclose(np.asarray(y[:, -1]), np.asarray(h),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_last), np.asarray(h),
                                   rtol=1e-4, atol=1e-5)
