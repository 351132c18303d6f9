"""Multi-device distribution tests. These spawn subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps seeing 1 device (assignment requirement)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # the child measures on a virtual CPU mesh; it must never reach for
    # an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


HEADER = """
import dataclasses, jax, jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh as mk
from repro.configs.base import get_config, smoke_config
from repro.core import moe as moe_mod
from repro.models.api import build_model
from repro.parallel import context as pctx_mod, ep
"""


class TestEP:
    def test_flat_and_dedup_match_local(self):
        out = run_sub(HEADER + """
mesh = mk((2, 4), ("data", "model"))
cfg = smoke_config(get_config("deepseek-v3-671b"))
cfg = dataclasses.replace(cfg, fp8=False,
    moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
pm = jax.tree.map(lambda x: x[0], params["blocks"])["moe"]
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.float32) * 0.5
y_ref, _, _ = moe_mod.moe_ffn(pm, x, cfg, capacity_override=512)
for impl in ["ep_flat", "ep_dedup"]:
    ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                               moe_impl=impl, wire="fp32")
    with pctx_mod.use(ctx):
        y, _, _ = ep.moe_ffn_sharded(pm, x, cfg, ctx)
    err = float(jnp.abs(y - y_ref).max() / jnp.abs(y_ref).max())
    assert err < 1e-4, (impl, err)
    print(impl, "OK", err)
""")
        assert "ep_flat OK" in out and "ep_dedup OK" in out

    def test_dedup_ring_cpg2(self):
        """cpg=2 exercises the intra-group ring exchange (hop 2)."""
        out = run_sub(HEADER + """
mesh = mk((1, 8), ("data", "model"))
cfg = smoke_config(get_config("deepseek-v3-671b"))
cfg = dataclasses.replace(cfg, fp8=False,
    moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
pm = jax.tree.map(lambda x: x[0], params["blocks"])["moe"]
x = jax.random.normal(jax.random.PRNGKey(1), (8, 8, cfg.d_model),
                      jnp.float32) * 0.5
y_ref, _, _ = moe_mod.moe_ffn(pm, x, cfg, capacity_override=512)
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                           moe_impl="ep_dedup", wire="fp32")
with pctx_mod.use(ctx):
    y, _, _ = ep.moe_ffn_sharded(pm, x, cfg, ctx)
err = float(jnp.abs(y - y_ref).max() / jnp.abs(y_ref).max())
assert err < 1e-4, err
print("cpg2 OK", err)
""")
        assert "cpg2 OK" in out

    def test_ftp_decode_mode(self):
        out = run_sub(HEADER + """
mesh = mk((2, 4), ("data", "model"))
cfg = smoke_config(get_config("deepseek-v3-671b"))
cfg = dataclasses.replace(cfg, fp8=False,
    moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
pm = jax.tree.map(lambda x: x[0], params["blocks"])["moe"]
x = jax.random.normal(jax.random.PRNGKey(1), (3, 1, cfg.d_model),
                      jnp.float32) * 0.5
y_ref, _, _ = moe_mod.moe_ffn(pm, x, cfg, capacity_override=512)
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                           moe_impl="ep_dedup", ep_ftp=True, wire="fp32")
with pctx_mod.use(ctx):
    y, _, _ = ep.moe_ffn_sharded(pm, x, cfg, ctx)
err = float(jnp.abs(y - y_ref).max() / jnp.abs(y_ref).max())
assert err < 1e-4, err
print("ftp OK", err)
""")
        assert "ftp OK" in out

    def test_fp8_wire_bounded_error(self):
        out = run_sub(HEADER + """
mesh = mk((1, 4), ("data", "model"))
cfg = smoke_config(get_config("qwen3-moe-30b-a3b"))
cfg = dataclasses.replace(cfg, fp8=False,
    moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
pm = jax.tree.map(lambda x: x[0], params["blocks"])["moe"]
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.float32) * 0.5
y_ref, _, _ = moe_mod.moe_ffn(pm, x, cfg, capacity_override=512)
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                           moe_impl="ep_flat", wire="fp8")
with pctx_mod.use(ctx):
    y, _, _ = ep.moe_ffn_sharded(pm, x, cfg, ctx)
rel = float(jnp.abs(y - y_ref).max() / jnp.abs(y_ref).max())
assert rel < 0.05, rel    # fp8 dispatch + bf16 combine noise
print("fp8 wire OK", rel)
""")
        assert "fp8 wire OK" in out


class TestCollectives:
    def test_compressed_psum(self):
        out = run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh as mk
from repro.parallel import collectives
mesh = mk((4,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 256), jnp.float32)
def f(xl):
    return collectives.compressed_psum(xl[0], "pod", n_bits=10)[None]
y = jax.shard_map(f, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                  check_vma=False)(x)
ref = x.sum(0)
for i in range(4):
    rel = float(jnp.abs(y[i] - ref).max() / jnp.abs(ref).max())
    assert rel < 0.05, rel
print("compressed psum OK")
""")
        assert "compressed psum OK" in out

    def test_pipeline_fwd_and_grad(self):
        out = run_sub("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh as mk
from repro.parallel import pipeline
mesh = mk((4,), ("pipe",))
Pn, M, mb, d = 4, 8, 2, 16
Ws = jax.random.normal(jax.random.PRNGKey(0), (Pn, d, d)) * 0.3
stage = lambda w, x: jnp.tanh(x @ w)
x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
y = pipeline.pipeline_forward(stage, Ws, x, mesh)
ref = x
for i in range(Pn):
    ref = jnp.tanh(ref @ Ws[i])
assert float(jnp.abs(y - ref).max()) < 1e-5
g1 = jax.grad(lambda W: (pipeline.pipeline_forward(stage, W, x, mesh)**2
                         ).sum())(Ws)
def seq(W):
    r = x
    for i in range(Pn):
        r = jnp.tanh(r @ W[i])
    return (r ** 2).sum()
g2 = jax.grad(seq)(Ws)
assert float(jnp.abs(g1 - g2).max() / jnp.abs(g2).max()) < 1e-4
print("pipeline OK")
""")
        assert "pipeline OK" in out

    def test_dual_microbatch_overlap_structure(self):
        """Both microbatches' collectives must appear in one scan body
        (the schedulable-overlap property, T7)."""
        out = run_sub(HEADER + """
from repro.parallel import overlap
mesh = mk((1, 4), ("data", "model"))
cfg = smoke_config(get_config("qwen3-moe-30b-a3b"))
cfg = dataclasses.replace(cfg, fp8=False)
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
bA = {"tokens": toks, "labels": toks}
bB = {"tokens": toks + 1, "labels": toks}
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",), moe_impl="ep_flat")
with pctx_mod.use(ctx):
    loss = overlap.dual_microbatch_loss(m, params, bA, bB)
    txt = jax.jit(lambda p: overlap.dual_microbatch_loss(m, p, bA, bB)
                  ).lower(params).as_text()
assert bool(jnp.isfinite(loss))
# two independent all-to-all chains inside the while body
assert txt.count("all_to_all") >= 4 or txt.count("all-to-all") >= 4
print("overlap OK", float(loss))
""")
        assert "overlap OK" in out

    def test_schedule_models(self):
        from repro.parallel.pipeline import dualpipe_bubble, onef1b_bubble
        a = onef1b_bubble(16, 64)
        b = dualpipe_bubble(16, 64, w=0.5)
        assert b.bubble_frac < a.bubble_frac    # paper's claim
        assert b.comm_overlapped and not a.comm_overlapped
