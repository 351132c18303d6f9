"""FP8 fine-grained quantization: unit + property + kernel-vs-oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core import fp8

# every finite e4m3 value, ascending
_E4M3_GRID = np.unique(np.arange(256, dtype=np.uint8).view(fp8.E4M3)
                       .astype(np.float64))
_E4M3_GRID = _E4M3_GRID[np.isfinite(_E4M3_GRID)]


def _scale_invariance_faults(quantize, x: np.ndarray, scale: float) -> int:
    """Elements of ``x`` where the tile quantizer ``quantize`` is not
    relative. Quantizing ``x * scale`` must give each live tile ``scale``
    times the tile scale (to fp32 rounding) and every element the same
    e4m3 code, except where ``x / tile_scale`` sits on a rounding tie
    between two neighbouring codes: the rounding of the fp32 scale may
    then send it to either one."""
    d = x.shape[-1]

    def per_elem(a):   # (..., tiles) -> (..., d)
        return np.repeat(np.asarray(a, np.float64), fp8.TILE, axis=-1)[..., :d]

    (q1, s1), (q2, s2) = (quantize(jnp.asarray(v)) for v in (x, x * scale))
    q1, q2 = np.asarray(q1, np.float64), np.asarray(q2, np.float64)
    s1, s2 = per_elem(s1), per_elem(s2)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, (-d) % fp8.TILE)]
    amax = np.abs(np.pad(x, pad)).reshape(x.shape[:-1] + (-1, fp8.TILE))
    live = per_elem(amax.max(-1)) > 0      # an all-zero tile keeps the floor
    bad_scale = live & (np.abs(s2 / (s1 * scale) - 1) > 1e-6)
    t = x / s1                             # the value the code rounds
    neighbours = np.abs(np.searchsorted(_E4M3_GRID, q1)
                        - np.searchsorted(_E4M3_GRID, q2)) == 1
    tie = np.abs(t - (q1 + q2) / 2) <= 1e-5 * np.abs(t)
    bad_code = (q1 != q2) & ~(neighbours & tie)
    return int((bad_scale | bad_code).sum())


def _off_by_a_binade(x):
    """A broken tile quantizer: right codes, but once a tile's amax passes
    1 its scale doubles, so it dequantizes a binade too high."""
    q, s = fp8.quantize_tilewise(x)
    amax = s * fp8.E4M3_MAX
    return q, jnp.where(amax > 1.0, 2 * s, s)


class TestQuantization:
    def test_tile_roundtrip_error_bound(self, rng):
        x = jax.random.normal(rng, (16, 384), jnp.float32)
        y = fp8.qdq_tile(x)
        # E4M3 has 3 mantissa bits -> relative error <= 2^-4 per element
        # within each tile (scale sets the exponent window)
        rel = jnp.abs(x - y) / jnp.maximum(jnp.abs(x), 1e-12)
        assert float(rel.max()) < 0.07

    def test_block_roundtrip_error_bound(self, rng):
        w = jax.random.normal(rng, (256, 384), jnp.float32)
        y = fp8.qdq_block(w)
        rel = jnp.abs(w - y) / jnp.maximum(jnp.abs(w).max(), 1e-12)
        assert float(rel.max()) < 0.07

    def test_tile_scale_shapes(self, rng):
        x = jax.random.normal(rng, (4, 300))     # non-multiple of 128
        q, s = fp8.quantize_tilewise(x)
        assert q.shape == (4, 300) and q.dtype == fp8.E4M3
        assert s.shape == (4, 3)                 # ceil(300/128)

    def test_zero_preserved(self):
        x = jnp.zeros((2, 128))
        q, s = fp8.quantize_tilewise(x)
        assert bool((fp8.dequant_tilewise(q, s) == 0).all())

    @given(st.integers(1, 4), st.integers(1, 300), st.floats(0.01, 1e4))
    @example(3, 3, 0.85)
    @example(3, 3, 3396.96528666261)
    @settings(max_examples=20, deadline=None)
    def test_property_scale_invariance(self, rows, cols, scale):
        """Quantization is relative: scaling the input scales each tile
        scale and keeps every e4m3 code, but for values on a rounding tie
        (at (3, 3, 0.85), x = 0.75 gives x / scale = 336, halfway between
        320 and 352), which may land on either neighbour."""
        x = np.linspace(-1, 1, rows * cols, dtype=np.float32).reshape(
            rows, cols)
        assert _scale_invariance_faults(fp8.quantize_tilewise, x,
                                        scale) == 0

    @pytest.mark.parametrize("scale", [1.5, 3396.96528666261])
    def test_scale_invariance_check_fails_a_broken_quantizer(self, scale):
        x = np.linspace(-1, 1, 9, dtype=np.float32).reshape(3, 3)
        assert _scale_invariance_faults(_off_by_a_binade, x, scale) > 0
        assert _scale_invariance_faults(fp8.quantize_tilewise, x, scale) == 0

    def test_linear_grads_close_to_exact(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        x = jax.random.normal(k1, (32, 256))
        w = jax.random.normal(k2, (256, 128)) * 0.05
        ct = jax.random.normal(k3, (32, 128))

        def f_fp8(x, w):
            return (fp8.fp8_linear(x, w) * ct).sum()

        def f_ref(x, w):
            return ((x @ w) * ct).sum()

        g8 = jax.grad(f_fp8, argnums=(0, 1))(x, w)
        gr = jax.grad(f_ref, argnums=(0, 1))(x, w)
        for a, b in zip(g8, gr):
            rel = jnp.abs(a - b) / jnp.maximum(jnp.abs(b).max(), 1e-9)
            assert float(rel.max()) < 0.15   # fp8 bwd quantization noise


class TestKernel:
    # kernel-vs-oracle parity sweeps live in test_kernel_registry.py
    # (TestBackendParity) — one sweep for every registered kernel.

    def test_accuracy_vs_bf16_paper_claim(self, rng):
        """Paper §2.4: FP8 relative loss vs BF16 below 0.25% on real
        workloads; here: GEMM-level relative error small for activation-
        scale inputs."""
        from repro import kernels
        from repro.kernels.fp8_gemm import ops
        x = jax.random.normal(rng, (256, 512)) * 0.5
        w = jax.random.normal(jax.random.PRNGKey(3), (512, 256)) * 0.02
        exact = x @ w
        with kernels.use_backend("ref", clear_caches=False):
            y = ops.fp8_matmul(x, w)
        rel = jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact)
        assert float(rel) < 0.05
