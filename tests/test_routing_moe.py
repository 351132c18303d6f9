"""Node-limited routing invariants + MoE dispatch equivalences (T2/T3)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import MoEConfig, get_config, smoke_config
from repro.core import moe as moe_mod
from repro.core import routing


def mk(e=16, k=4, g=4, lim=2, **kw):
    return MoEConfig(num_experts=e, top_k=k, num_groups=g, group_limit=lim,
                     expert_ff=32, **kw)


class TestRouting:
    def test_group_limit_invariant(self, rng):
        """THE paper invariant: every token touches <= M groups."""
        mc = mk()
        x = jax.random.normal(rng, (512, 64))
        wg = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        rr = routing.route(x, wg, mc)
        m = routing.groups_per_token(rr.expert_idx, mc)
        assert int(m.max()) <= mc.group_limit

    def test_topk_distinct(self, rng):
        mc = mk()
        x = jax.random.normal(rng, (128, 64))
        wg = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        rr = routing.route(x, wg, mc)
        idx = np.asarray(rr.expert_idx)
        for row in idx:
            assert len(set(row.tolist())) == mc.top_k

    def test_weights_normalized(self, rng):
        mc = mk(route_norm=True)
        x = jax.random.normal(rng, (64, 64))
        wg = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        rr = routing.route(x, wg, mc)
        np.testing.assert_allclose(np.asarray(rr.weights.sum(-1)), 1.0,
                                   rtol=1e-5)

    def test_bias_changes_selection_not_weights(self, rng):
        """Aux-loss-free balancing: bias shifts WHO is selected, never the
        mixture weights of the selected experts."""
        mc = mk(score_fn="sigmoid", route_norm=False)
        x = jax.random.normal(rng, (256, 64))
        wg = jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 0.01
        bias = jnp.zeros(16).at[3].set(10.0)   # force expert 3 selection
        rr = routing.route(x, wg, mc, bias=bias)
        assert bool((rr.expert_idx == 3).any(axis=-1).all())
        # weight of expert 3 comes from unbiased scores (sigmoid < 1)
        w3 = jnp.take_along_axis(
            rr.weights, jnp.argmax(rr.expert_idx == 3, -1)[:, None], 1)
        assert float(w3.max()) <= 1.0

    def test_update_bias_balances(self, rng):
        """Bias feedback drives load toward uniform (paper/V3 mechanism).
        Start from a FORCED imbalance (one expert's gate offset) so there
        is something to correct."""
        mc = mk(e=8, k=2, g=2, lim=2)
        x = jax.random.normal(rng, (2048, 32)) * 0.5
        x = x.at[:, 0].set(2.0)            # constant feature channel
        wg = jax.random.normal(jax.random.PRNGKey(1), (32, 8)) * 0.5
        wg = wg.at[0, 0].set(2.0)          # expert 0: +4 constant logit
        bias = jnp.zeros(8)
        rr0 = routing.route(x, wg, mc, bias=bias)
        var0 = float(rr0.load.std())
        max0 = float(rr0.load.max())
        assert max0 > 0.3                  # premise: gross imbalance
        tail = []
        for it in range(120):
            rr = routing.route(x, wg, mc, bias=bias)
            bias = routing.update_bias(bias, rr.load, lr=0.02)
            if it >= 110:
                tail.append(rr.load)       # smooth the sign-update cycle
        load_f = jnp.stack(tail).mean(0)
        assert float(load_f.std()) < var0 * 0.5
        assert float(load_f.max()) < max0

    @given(st.integers(2, 64))
    @settings(max_examples=10, deadline=None)
    def test_property_dispatch_plan_capacity(self, cap):
        """No expert ever receives more than C rows; kept slots unique."""
        rs = np.random.RandomState(cap)
        idx = jnp.asarray(rs.randint(0, 8, size=(64, 2)))
        plan = moe_mod.dispatch_plan(idx, 8, cap)
        dest = np.asarray(plan.dest)[np.asarray(plan.keep)]
        assert len(set(dest.tolist())) == len(dest)       # unique slots
        counts = np.bincount(dest // cap, minlength=8)
        assert counts.max() <= cap


class TestMoELayer:
    @pytest.fixture
    def setup(self, rng):
        cfg = smoke_config(get_config("deepseek-v3-671b"))
        cfg = dataclasses.replace(
            cfg, fp8=False,
            moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        from repro.models.api import build_model
        m = build_model(cfg)
        params = m.init(rng)
        pm = jax.tree.map(lambda x: x[0], params["blocks"])["moe"]
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                              jnp.float32) * 0.5
        return cfg, pm, x

    def test_capacity_matches_oracle(self, setup):
        cfg, pm, x = setup
        y, rr, drop = moe_mod.moe_ffn(pm, x, cfg, capacity_override=256)
        y_ref = moe_mod.moe_ffn_oracle(pm, x, cfg)
        assert float(drop) == 0.0
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_drops_under_tight_capacity(self, setup):
        cfg, pm, x = setup
        _, _, drop = moe_mod.moe_ffn(pm, x, cfg, capacity_override=8)
        assert float(drop) > 0.0

    def test_moe_grads_finite(self, setup):
        cfg, pm, x = setup

        def loss(p):
            y, _, _ = moe_mod.moe_ffn(p, x, cfg, capacity_override=128)
            return (y ** 2).sum()

        g = jax.grad(loss)(pm)
        for leaf in jax.tree.leaves(g):
            assert bool(jnp.isfinite(leaf).all())
