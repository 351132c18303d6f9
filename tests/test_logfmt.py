"""LogFMT codec: unit + property + kernel-vs-oracle (paper §3.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import logfmt


class TestCodec:
    def test_roundtrip_relative_error_8bit(self, rng):
        x = jax.random.normal(rng, (32, 256)) * jnp.exp(
            jax.random.normal(jax.random.PRNGKey(1), (32, 256)))
        y = logfmt.qdq(x, 8)
        rel = jnp.abs(x - y) / jnp.maximum(jnp.abs(x), 1e-12)
        # 127 log-levels across the dynamic range
        assert float(rel.max()) < 0.12

    def test_more_bits_monotone(self, rng):
        x = jax.random.normal(rng, (16, 128)) * 3.7
        errs = []
        for n in (6, 8, 10, 12):
            y = logfmt.qdq(x, n)
            errs.append(float(jnp.abs(x - y).max()))
        assert errs == sorted(errs, reverse=True)

    def test_zeros_and_signs(self):
        x = jnp.array([[0.0, -1.5, 2.5, -0.01] + [1.0] * 124])
        y = logfmt.qdq(x, 8)
        assert float(y[0, 0]) == 0.0
        assert float(y[0, 1]) < 0 and float(y[0, 2]) > 0 and float(y[0, 3]) < 0

    def test_min_max_codes(self):
        """min encodes as code 1, max as the top code (paper's S.00..01 /
        S.11..11), and both decode exactly."""
        vals = jnp.array([[0.001, 1000.0] + [1.0] * 126])
        c, mn, st_ = logfmt.encode(vals, 8)
        y = logfmt.decode(c, mn, st_, 8, dtype=jnp.float32)
        np.testing.assert_allclose(float(y[0, 1]), 1000.0, rtol=1e-4)

    def test_range_clamp(self):
        """Paper: min is clamped to max - log(2^32) (E5-like range)."""
        x = jnp.array([[1e30, 1e-30] + [1.0] * 126])
        y = logfmt.qdq(x, 8)
        assert jnp.isfinite(y).all()
        # the tiny value is pulled up to the clamped range bottom
        assert float(y[0, 1]) >= 1e30 / 2.0 ** 33

    @given(st.integers(6, 12))
    @settings(max_examples=7, deadline=None)
    def test_property_idempotent(self, n_bits):
        """QDQ is idempotent: grid points map to themselves."""
        x = np.random.RandomState(n_bits).randn(4, 128).astype(np.float32)
        y1 = np.asarray(logfmt.qdq(jnp.asarray(x), n_bits))
        y2 = np.asarray(logfmt.qdq(jnp.asarray(y1), n_bits))
        np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-6)

    def test_wire_cost(self):
        assert logfmt.compressed_bits_per_element(8) == 8.5
        assert logfmt.compressed_bits_per_element(10) == 10.5


# Codec-kernel-vs-oracle parity sweeps live in test_kernel_registry.py
# (TestBackendParity) — one sweep for every registered kernel.
