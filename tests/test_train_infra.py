"""Trainer substrate: optimizer math, checkpoint atomicity + elastic
restore, fault injection / SDC recovery, data determinism, convergence."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.data.pipeline import Prefetcher, SyntheticCorpus
from repro.train import checkpoint as ckpt
from repro.train import optimizer as optim
from repro.train import schedule as sched
from repro.train.fault import FailureInjector, NodeFailure, StragglerMonitor
from repro.train.trainer import Trainer, TrainConfig


class TestOptimizer:
    def test_adamw_descends_quadratic(self):
        params = {"w": jnp.array([5.0, -3.0])}
        st = optim.init(params)
        for i in range(200):
            g = {"w": 2 * params["w"]}
            params, st, _ = optim.update(g, st, params, lr=0.05,
                                         weight_decay=0.0)
        assert float(jnp.abs(params["w"]).max()) < 0.2

    def test_state_dtypes_paper_recipe(self):
        """fp32 master, bf16 m/v (10 bytes/param, DESIGN §5)."""
        params = {"w": jnp.zeros((4, 4), jnp.bfloat16)}
        st = optim.init(params)
        assert st.master["w"].dtype == jnp.float32
        assert st.m["w"].dtype == jnp.bfloat16
        assert st.v["w"].dtype == jnp.bfloat16

    def test_grad_clip(self):
        params = {"w": jnp.zeros((8,))}
        st = optim.init(params)
        g = {"w": jnp.full((8,), 1e6)}
        _, _, stats = optim.update(g, st, params, lr=1.0, clip_norm=1.0)
        assert float(stats["grad_norm"]) > 1e5   # reported pre-clip

    def test_no_decay_on_1d(self):
        params = {"gamma": jnp.ones((16,)), "w": jnp.ones((4, 4))}
        st = optim.init(params)
        g = jax.tree.map(jnp.zeros_like, params)
        p2, _, _ = optim.update(g, st, params, lr=0.1, weight_decay=0.5)
        np.testing.assert_allclose(np.asarray(p2["gamma"]), 1.0)
        assert float(p2["w"].max()) < 1.0        # decayed

    def test_schedule(self):
        lr0 = sched.warmup_cosine(0, peak_lr=1.0, warmup=10, total=100)
        lr10 = sched.warmup_cosine(10, peak_lr=1.0, warmup=10, total=100)
        lr100 = sched.warmup_cosine(100, peak_lr=1.0, warmup=10, total=100)
        assert float(lr0) == 0.0 and float(lr10) == 1.0
        assert 0.05 < float(lr100) < 0.15


class TestCheckpoint:
    def test_roundtrip_and_gc(self, rng):
        tree = {"a": jax.random.normal(rng, (4, 8)),
                "b": {"c": jnp.arange(5),
                      "d": jnp.ones((3,), jnp.bfloat16)}}
        with tempfile.TemporaryDirectory() as d:
            for step in (1, 2, 3, 4, 5):
                ckpt.save(d, step, tree, extras={"step": step}, keep=2)
            assert ckpt.latest_step(d) == 5
            assert len(os.listdir(d)) == 2       # keep=2 gc'd the rest
            got, extras = ckpt.restore(d, tree)
            assert extras["step"] == 5
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))
            assert got["b"]["d"].dtype == jnp.bfloat16

    def test_corruption_detected(self, rng):
        tree = {"a": jax.random.normal(rng, (16,))}
        with tempfile.TemporaryDirectory() as d:
            path = ckpt.save(d, 1, tree)
            # flip bytes in the array file
            fn = os.path.join(path, "arrays.npz")
            data = bytearray(open(fn, "rb").read())
            data[-20] ^= 0xFF
            open(fn, "wb").write(bytes(data))
            with pytest.raises(Exception):
                ckpt.restore(d, tree)

    def test_restore_falls_back_to_newest_intact(self, rng):
        """Corrupt/partial newest steps are warned about and skipped;
        auto-restore lands on the newest step that verifies end to end.
        Asking for the bad step explicitly still raises (ISSUE 9)."""
        tree = {"a": jax.random.normal(rng, (16,))}
        with tempfile.TemporaryDirectory() as d:
            for step in (1, 2, 3):
                ckpt.save(d, step, tree, extras={"step": step}, keep=5)
            # newest (3): flipped byte in the arrays -> CRC mismatch
            fn = os.path.join(d, "step_00000003", "arrays.npz")
            data = bytearray(open(fn, "rb").read())
            data[-20] ^= 0xFF
            open(fn, "wb").write(bytes(data))
            # even newer (4): partial — manifest only, no arrays
            partial = os.path.join(d, "step_00000004")
            os.makedirs(partial)
            with open(os.path.join(partial, "MANIFEST.json"), "w") as f:
                f.write("{}")
            with pytest.warns(UserWarning, match="skipping damaged"):
                got, extras = ckpt.restore(d, tree)
            assert extras["step"] == 2
            np.testing.assert_array_equal(np.asarray(got["a"]),
                                          np.asarray(tree["a"]))
            # strict path unchanged: explicit bad step raises
            with pytest.raises(Exception):
                ckpt.restore(d, tree, step=3)

    def test_latest_step_tolerates_malformed_names(self, rng):
        tree = {"a": jax.random.normal(rng, (4,))}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 7, tree, extras={"step": 7})
            os.makedirs(os.path.join(d, "step_junk"))
            os.makedirs(os.path.join(d, "step_"))
            assert ckpt.latest_step(d) == 7
            _, extras = ckpt.restore(d, tree)
            assert extras["step"] == 7
            # gc walks the same listing — debris must not crash it either
            ckpt.save(d, 8, tree, keep=1)
            assert ckpt.latest_step(d) == 8

    def test_elastic_restore_shardings(self, rng):
        """Restore onto explicit (different) shardings — elastic re-mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        tree = {"w": jax.random.normal(rng, (8, 4))}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 1, tree)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((1,), ("data",))
            sh = {"w": NamedSharding(mesh, P("data", None))}
            got, _ = ckpt.restore(d, tree, shardings=sh)
            assert got["w"].sharding == sh["w"]


class TestFaultTolerance:
    def test_failure_recovery_end_to_end(self):
        cfg = smoke_config(get_config("qwen1.5-4b"))
        with tempfile.TemporaryDirectory() as d:
            tc = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=30,
                             ckpt_dir=d, ckpt_every=4, sdc_check_every=9)
            inj = FailureInjector({9: "node", 18: "sdc"})
            tr = Trainer(cfg, tc, injector=inj, global_batch=2, seq_len=16)
            out = tr.run(22)
            assert out["final_step"] == 22
            assert out["restarts"] == 1
            assert out["sdc_alarms"] == [18]

    def test_straggler_monitor(self):
        mon = StragglerMonitor(n_replicas=4, threshold=1.5)
        for step in range(10):
            times = [1.0, 1.0, 1.0, 3.0]     # replica 3 is slow
            slow = mon.observe(step, times)
        assert slow == [3]
        assert mon.events

    def test_data_determinism_across_restart(self):
        c1 = SyntheticCorpus(1000, 32, 4, seed=7)
        c2 = SyntheticCorpus(1000, 32, 4, seed=7)
        b1 = c1.batch_at(13)
        b2 = c2.batch_at(13)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_prefetcher(self):
        c = SyntheticCorpus(100, 8, 2)
        pf = Prefetcher(c.iterate(), depth=2)
        b = next(pf)
        assert b["tokens"].shape == (2, 8)
        pf.close()


class TestConvergence:
    def test_loss_decreases_moe_mla_mtp(self):
        """The full paper stack (MLA + MoE + MTP + FP8) learns the
        synthetic bigram structure."""
        cfg = smoke_config(get_config("deepseek-v3-671b"))
        tc = TrainConfig(peak_lr=3e-3, warmup=5, total_steps=40)
        tr = Trainer(cfg, tc, global_batch=4, seq_len=32)
        out = tr.run(30)
        h = out["history"]
        first = np.mean([x["loss"] for x in h[:3]])
        last = np.mean([x["loss"] for x in h[-3:]])
        assert last < first - 0.5, (first, last)
