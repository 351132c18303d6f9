"""Distributed training path tests (ISSUE 3): sharded-vs-single-device
trajectory parity, checkpoint -> elastic re-mesh round trip, dual-
microbatch overlap structure at the HLO level, real per-replica straggler
observation, and the ep_dedup < ep_flat wire-byte claim.

Like test_distributed.py, every test spawns a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps seeing 1 device (assignment requirement).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")   # for benchmarks.*


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # the child measures on a virtual CPU mesh; it must never reach for
    # an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (SRC + os.pathsep + ROOT + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


HEADER = """
import dataclasses, jax, jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh as mk
from repro.configs.base import get_config, smoke_config
from repro.parallel import context as pctx_mod
from repro.train.trainer import Trainer, TrainConfig
"""


def _max_param_diff():
    return """
def max_param_diff(p0, p1):
    return max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)
                             ).max())
               for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)))
"""


class TestDualLossEquivalence:
    def test_weighted_dual_matches_single_with_uneven_pads(self):
        """loss_dual must equal Model.loss even when the halves carry
        unequal valid-token counts (pad labels -1): the combination is
        valid-token-weighted, not a flat microbatch average."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        from repro.configs.base import get_config, smoke_config
        from repro.models.api import build_model

        cfg = smoke_config(get_config("qwen1.5-4b"))
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab_size)
        labels = jnp.roll(toks, -1, axis=1)
        # rows 0-1: only 3 valid labels; rows 2-3: 15 -> halves unequal
        mask = jnp.arange(16) < 3
        labels = labels.at[:2].set(jnp.where(mask, labels[:2], -1))
        labels = labels.at[:, -1].set(-1)
        batch = {"tokens": toks, "labels": labels}
        bA = {k: v[:2] for k, v in batch.items()}
        bB = {k: v[2:] for k, v in batch.items()}
        l_single, _ = m.loss(params, batch)
        l_dual, _ = m.loss_dual(params, bA, bB)
        assert abs(float(l_single) - float(l_dual)) < 1e-5, \
            (float(l_single), float(l_dual))


class TestShardedParity:
    def test_dense_matches_single_device(self):
        """Meshed dual-microbatch step == unsharded step, loss + params."""
        out = run_sub(HEADER + _max_param_diff() + """
cfg = smoke_config(get_config("qwen1.5-4b"))
tc = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=10)
tr0 = Trainer(cfg, tc, global_batch=8, seq_len=16)
out0 = tr0.run(3)
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",))
tr1 = Trainer(cfg, tc, global_batch=8, seq_len=16, ctx=ctx)
out1 = tr1.run(3)
for h0, h1 in zip(out0["history"], out1["history"]):
    d = abs(h0["loss"] - h1["loss"])
    assert d < 2e-3, (h0["step"], h0["loss"], h1["loss"])
pd = max_param_diff(tr0.params, tr1.params)
assert pd < 2e-3, pd
print("dense parity OK", pd)
""")
        assert "dense parity OK" in out

    def test_moe_matches_single_device_both_impls(self):
        """MoE (MLA + MTP) trajectory parity under ep_flat AND ep_dedup."""
        out = run_sub(HEADER + _max_param_diff() + """
cfg = smoke_config(get_config("deepseek-v3-671b"))
cfg = dataclasses.replace(cfg, fp8=False,
    moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
tc = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=10)
tr0 = Trainer(cfg, tc, global_batch=8, seq_len=16)
out0 = tr0.run(3)
mesh = mk((2, 4), ("data", "model"))
for impl in ("ep_flat", "ep_dedup"):
    ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                               moe_impl=impl, wire="fp32")
    tr1 = Trainer(cfg, tc, global_batch=8, seq_len=16, ctx=ctx)
    out1 = tr1.run(3)
    for h0, h1 in zip(out0["history"], out1["history"]):
        d = abs(h0["loss"] - h1["loss"])
        assert d < 5e-3, (impl, h0["step"], h0["loss"], h1["loss"])
    pd = max_param_diff(tr0.params, tr1.params)
    assert pd < 5e-3, (impl, pd)
    print(impl, "parity OK", pd)
""")
        assert "ep_flat parity OK" in out and "ep_dedup parity OK" in out

    def test_fp8_wire_trains(self):
        """The default FP8 dispatch wire keeps the meshed step finite and
        within quantization noise of the fp32-wire trajectory."""
        out = run_sub(HEADER + """
cfg = smoke_config(get_config("deepseek-v3-671b"))
cfg = dataclasses.replace(cfg, fp8=False,
    moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
tc = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=10)
mesh = mk((2, 4), ("data", "model"))
losses = {}
for wire in ("fp32", "fp8"):
    ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                               moe_impl="ep_dedup", wire=wire)
    tr = Trainer(cfg, tc, global_batch=8, seq_len=16, ctx=ctx)
    out1 = tr.run(2)
    losses[wire] = [h["loss"] for h in out1["history"]]
    assert all(np.isfinite(v) for v in losses[wire])
for a, b in zip(losses["fp32"], losses["fp8"]):
    assert abs(a - b) / abs(a) < 0.05, (a, b)
print("fp8 wire train OK", losses["fp8"])
""")
        assert "fp8 wire train OK" in out


class TestElasticRemesh:
    def test_checkpoint_remesh_roundtrip(self):
        """Save on (2,4), restore onto (1,4) survivors: training resumes
        with the uninterrupted run's losses at the same steps."""
        out = run_sub(HEADER + """
import tempfile
cfg = smoke_config(get_config("qwen1.5-4b"))
tc0 = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=10)
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",))
tr_ref = Trainer(cfg, tc0, global_batch=8, seq_len=16, ctx=ctx)
ref = [h["loss"] for h in tr_ref.run(6)["history"]]
with tempfile.TemporaryDirectory() as d:
    tc = dataclasses.replace(tc0, ckpt_dir=d, ckpt_every=4)
    tr = Trainer(cfg, tc, global_batch=8, seq_len=16, ctx=ctx)
    tr.run(4)
    mesh1 = mk((1, 4), ("data", "model"))
    ctx1 = pctx_mod.ParallelCtx(mesh=mesh1, dp_axes=("data",))
    tr2 = Trainer(cfg, tc, global_batch=8, seq_len=16, ctx=ctx1)
    tr2._init_state(restore=True)
    assert tr2.step == 4
    # restored leaves actually live on the survivor mesh's shardings
    shd = tr2._state_shardings()["params"]
    for leaf, want in zip(jax.tree.leaves(tr2.params), jax.tree.leaves(shd)):
        assert leaf.sharding == want, (leaf.sharding, want)
    res = [h["loss"] for h in tr2.run(2)["history"]]
for a, b in zip(ref[4:], res):
    assert abs(a - b) < 1e-4, (a, b)
print("elastic roundtrip OK", res)
""")
        assert "elastic roundtrip OK" in out

    def test_node_failure_auto_remesh(self):
        """Injected node failure mid-run: the trainer re-meshes onto the
        survivor mesh (dp halved) and finishes from the checkpoint."""
        out = run_sub(HEADER + """
import tempfile
from repro.train.fault import FailureInjector
cfg = smoke_config(get_config("qwen1.5-4b"))
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",))
with tempfile.TemporaryDirectory() as d:
    tc = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=8, ckpt_dir=d,
                     ckpt_every=2)
    inj = FailureInjector({3: "node"})
    tr = Trainer(cfg, tc, injector=inj, global_batch=8, seq_len=16, ctx=ctx)
    out = tr.run(6)
assert out["final_step"] == 6
assert out["restarts"] == 1
assert out["mesh_shape"] == (1, 4), out["mesh_shape"]
print("auto remesh OK", out["mesh_shape"])
""")
        assert "auto remesh OK" in out


class TestOverlapStructure:
    def test_dual_microbatch_one_scan_body(self):
        """Both microbatches' all-to-alls appear in ONE scan body: the
        dual step's while body carries exactly 2x the single-microbatch
        all-to-all count (the schedulable-overlap property, T7)."""
        out = run_sub(HEADER + """
from repro.models.api import build_model
from repro.parallel import overlap
mesh = mk((2, 4), ("data", "model"))
cfg = smoke_config(get_config("qwen3-moe-30b-a3b"))
cfg = dataclasses.replace(cfg, fp8=False)
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
bA = {"tokens": toks, "labels": toks}
bB = {"tokens": toks + 1, "labels": toks}
batch = {k: jnp.concatenate([bA[k], bB[k]]) for k in bA}
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",), moe_impl="ep_flat")
txt1 = overlap.lowered_text(lambda p: m.loss(p, batch, pctx=ctx)[0], params)
txt2 = overlap.lowered_text(lambda p: m.loss_dual(p, bA, bB, pctx=ctx)[0],
                            params)
c1 = overlap.while_body_op_counts(txt1)
c2 = overlap.while_body_op_counts(txt2)
assert max(c1) > 0, c1
assert max(c2) == 2 * max(c1), (c1, c2)
# dual path is ONE joint scan, not two sequential ones: a single body
# carries all of both microbatches' collectives
assert len([c for c in c2 if c > 0]) == 1, c2
print("overlap structure OK", max(c1), "->", max(c2))
""")
        assert "overlap structure OK" in out


class TestStragglerObservation:
    def test_real_replica_times_and_injected_slow_replica(self):
        """Per-replica times come from real per-shard completion
        measurements (one entry per DP replica), and an injected slow
        replica trips StragglerMonitor.events on that replica."""
        out = run_sub(HEADER + """
from repro.train.fault import FailureInjector
cfg = smoke_config(get_config("qwen1.5-4b"))
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",))
tc = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=8)
inj = FailureInjector({2: "slow:1", 3: "slow:1"})
tr = Trainer(cfg, tc, injector=inj, global_batch=8, seq_len=16, ctx=ctx)
out = tr.run(4)
assert tr.straggler.ewma and len(tr.straggler.ewma) == 2  # one per replica
assert out["straggler_events"], "no straggler event fired"
assert all(ev["slow"] == [1] for ev in out["straggler_events"]), \\
    out["straggler_events"]
# no fabricated [dt]*4: a clean run on the same mesh records nothing
tr2 = Trainer(cfg, tc, global_batch=8, seq_len=16, ctx=ctx)
out2 = tr2.run(3)
assert not out2["straggler_events"], out2["straggler_events"]
print("straggler OK", out["straggler_events"][0]["slow"])
""")
        assert "straggler OK" in out

    def test_sdc_guard_consumes_device_shards(self):
        """Meshed SDC checks read back every device's local shards; an
        injected corruption between reads raises the alarm + restore."""
        out = run_sub(HEADER + """
import tempfile
from repro.train.fault import FailureInjector
cfg = smoke_config(get_config("qwen1.5-4b"))
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",))
with tempfile.TemporaryDirectory() as d:
    tc = TrainConfig(peak_lr=1e-3, warmup=2, total_steps=8, ckpt_dir=d,
                     ckpt_every=2, sdc_check_every=3)
    inj = FailureInjector({3: "sdc"})
    tr = Trainer(cfg, tc, injector=inj, global_batch=8, seq_len=16, ctx=ctx)
    out = tr.run(5)
assert out["sdc_alarms"] == [3], out["sdc_alarms"]
assert len(tr.last_device_checksums) == 8   # one checksum per device
print("sdc OK", out["sdc_alarms"])
""")
        assert "sdc OK" in out


class TestWireBytes:
    def test_ep_dedup_bytes_less_than_flat(self):
        """The paper's §4.3 claim on the slow fabric: node-limited dedup
        dispatch moves strictly fewer all-to-all bytes than flat EP when
        top_k > group_limit (same measurement train_bench reports into
        BENCH_train.json)."""
        out = run_sub("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh as mk
from repro.models.api import build_model
from repro.parallel import context as pctx_mod, ep, overlap
from benchmarks.train_bench import bench_config

cfg = bench_config()
mesh = mk((2, 4), ("data", "model"))
m = build_model(cfg)
pm = jax.tree.map(lambda x: x[0], m.init(jax.random.PRNGKey(0))["blocks"])["moe"]
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)
nbytes = {}
for impl in ("ep_flat", "ep_dedup"):
    ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",), moe_impl=impl,
                               wire="fp8")
    def f(pm, x):
        with pctx_mod.use(ctx):
            y, _, _ = ep.moe_ffn_sharded(pm, x, cfg, ctx)
        return (y ** 2).sum()
    txt = overlap.lowered_text(jax.grad(f, argnums=(0, 1)), pm, x)
    nbytes[impl] = overlap.collective_bytes(txt, "all_to_all")
assert 0 < nbytes["ep_dedup"] < nbytes["ep_flat"], nbytes
print("wire bytes OK", nbytes)
""")
        assert "wire bytes OK" in out
