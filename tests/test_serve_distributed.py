"""Distributed serving path tests (ISSUE 5): sharded-vs-single-device
token-stream parity for dense (GQA) and MoE (MLA, ep_flat/ep_dedup)
engines, paged-bf16 stream parity under the mesh, the cross-mesh-size
disaggregation handoff roundtrip, and the ep_dedup < ep_flat decode
wire-byte claim.

Like test_train_distributed.py, every test spawns a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps seeing 1 device (assignment requirement).

Parity contract (docs/serving.md §5): a deterministic greedy request
stream through the sharded engine must reproduce the single-device
engine's streams. Dense GQA and MoE-at-fp32-wire are exact. Two
documented tolerances: the fp8 dispatch wire quantizes EP payloads, and
the paged MLA pool partitions attention differently from the T-sharded
dense ring (replicated pool vs model-sharded length axis), so in both
cases a greedy near-tie can flip — those streams are asserted to match
at >= 90% of tokens instead of bitwise.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.parallel import context as pctx_mod
from repro.serve.engine import Request, ServeEngine

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
import dataclasses, numpy as np, jax
from repro.launch.mesh import make_mesh as mk
from repro.configs.base import get_config, smoke_config
from repro.parallel import context as pctx_mod
from repro.serve.engine import Request, ServeEngine

def prompts_for(cfg, n=5):
    return [np.arange(4 + i * 3) * (i + 3) % cfg.vocab_size
            for i in range(n)]

def stream(cfg, ctx=None, slots=4, max_new=6, chunk=4, n=5, **kw):
    eng = ServeEngine(cfg, slots=slots, max_len=32, seed=0, chunk=chunk,
                      ctx=ctx, **kw)
    reqs = [Request(i, p, max_new=max_new)
            for i, p in enumerate(prompts_for(cfg, n))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [r.out for r in reqs]

def match_frac(a, b):
    toks = [(x, y) for ra, rb in zip(a, b) for x, y in zip(ra, rb)]
    return sum(x == y for x, y in toks) / len(toks)

def moe_cfg():
    cfg = smoke_config(get_config("deepseek-v3-671b"))
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
"""


class TestCtxDefault:
    """ctx=None (and an unmeshed ctx) stay the single-device path —
    cheap in-process checks, no subprocess."""

    def test_ctx_none_is_unmeshed(self):
        from repro.configs.base import get_config, smoke_config
        cfg = smoke_config(get_config("qwen1.5-4b"))
        eng = ServeEngine(cfg, slots=2, max_len=16)
        assert not eng.meshed and eng.ctx is None
        assert eng._cache_shardings is None

    def test_unmeshed_ctx_is_unmeshed(self):
        from repro.configs.base import get_config, smoke_config
        cfg = smoke_config(get_config("qwen1.5-4b"))
        eng = ServeEngine(cfg, slots=2, max_len=16,
                          ctx=pctx_mod.ParallelCtx())   # no mesh
        assert not eng.meshed
        assert eng.decode_alltoall_bytes() == 0


class TestShardedDenseParity:
    def test_gqa_stream_matches_single_device(self):
        """Dense GQA (qwen3-14b-style) sharded over (2, 4): the token
        streams are exactly the single-device engine's, and the fused
        hot path still compiles once per entry point."""
        out = run_sub(HEADER + """
cfg = smoke_config(get_config("qwen3-14b"))
_, s0 = stream(cfg)
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",))
eng, s1 = stream(cfg, ctx=ctx)
assert s1 == s0, (s0, s1)
tc = eng.trace_counts
assert tc["decode"] == 1 and tc["splice"] == 1, tc
assert tc["prefill"] == len(eng.compiled_prefill_buckets), tc
print("gqa sharded parity OK", tc)
""")
        assert "gqa sharded parity OK" in out


class TestShardedMoEParity:
    def test_moe_both_impls_fp32_wire_exact(self):
        """MoE (MLA + MTP arch) decode through the EP shard_map: at fp32
        wire, ep_flat AND ep_dedup reproduce the single-device token
        streams exactly (capacity-headroom config: nothing drops, so the
        sharded dispatch is token-for-token the local one)."""
        out = run_sub(HEADER + """
cfg = moe_cfg()
_, s0 = stream(cfg)
mesh = mk((2, 4), ("data", "model"))
for impl in ("ep_flat", "ep_dedup"):
    ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                               moe_impl=impl, wire="fp32")
    eng, s1 = stream(cfg, ctx=ctx)
    assert s1 == s0, (impl, s0, s1)
    assert eng.trace_counts["decode"] == 1, eng.trace_counts
    print(impl, "moe sharded parity OK")
""")
        assert "ep_flat moe sharded parity OK" in out
        assert "ep_dedup moe sharded parity OK" in out

    def test_fp8_wire_within_documented_tolerance(self):
        """The default FP8 dispatch wire quantizes the EP payload; greedy
        near-ties can flip, so the documented bound is >= 90% token
        match vs the single-device engine (and every emitted token must
        be a valid vocab id)."""
        out = run_sub(HEADER + """
cfg = moe_cfg()
_, s0 = stream(cfg)
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                           moe_impl="ep_flat", wire="fp8")
_, s1 = stream(cfg, ctx=ctx)
mf = match_frac(s0, s1)
assert mf >= 0.9, (mf, s0, s1)
assert all(0 <= t < cfg.vocab_size for r in s1 for t in r)
print("fp8 wire tolerance OK", mf)
""")
        assert "fp8 wire tolerance OK" in out

    def test_mtp_drafts_under_mesh(self):
        """MTP drafting folded into the sharded fused loop matches the
        single-device engine (streams + acceptance accounting)."""
        out = run_sub(HEADER + """
cfg = moe_cfg()
e0, s0 = stream(cfg, use_mtp=True)
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                           moe_impl="ep_flat", wire="fp32")
e1, s1 = stream(cfg, ctx=ctx, use_mtp=True)
assert s1 == s0, (s0, s1)
assert e1.stats["drafts"] == e0.stats["drafts"]
assert e1.stats["accepted_drafts"] == e0.stats["accepted_drafts"]
print("mtp sharded OK", e1.stats["drafts"], e1.stats["accepted_drafts"])
""")
        assert "mtp sharded OK" in out


class TestShardedPaged:
    def test_paged_bf16_gqa_stream_matches_single_device(self):
        """Paged block-pool cache at native storage, sharded: GQA streams
        are exactly the single-device dense engine's."""
        out = run_sub(HEADER + """
cfg = smoke_config(get_config("qwen3-14b"))
_, s0 = stream(cfg)
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",))
eng, s1 = stream(cfg, ctx=ctx, paged=True, page_size=8,
                 page_storage="bf16")
assert s1 == s0, (s0, s1)
assert eng.trace_counts["decode"] == 1, eng.trace_counts
print("paged gqa sharded parity OK")
""")
        assert "paged gqa sharded parity OK" in out

    def test_paged_bf16_mla_within_documented_tolerance(self):
        """MLA paged pools replicate while the dense ring shards its
        length axis over the model axis, so SPMD partitions the two
        attention layouts differently — same values, different reduction
        order. Documented bound: >= 90% token match vs the sharded dense
        engine (unmeshed, the same pair is bitwise — test_paged_cache)."""
        out = run_sub(HEADER + """
cfg = moe_cfg()
mesh = mk((2, 4), ("data", "model"))
ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                           moe_impl="ep_flat", wire="fp32")
_, sd = stream(cfg, ctx=ctx)
_, sp = stream(cfg, ctx=ctx, paged=True, page_size=8,
               page_storage="bf16")
mf = match_frac(sd, sp)
assert mf >= 0.9, (mf, sd, sp)
print("paged mla sharded tolerance OK", mf)
""")
        assert "paged mla sharded tolerance OK" in out


class TestCrossMeshDisagg:
    def test_handoff_roundtrip_different_mesh_sizes(self):
        """The paper's disagg deployment: prefill on a (2, 4) mesh hands
        off to decode on a (1, 4) mesh through host memory. Dense
        roundtrip reproduces the single-device streams exactly; the
        paged payload completes the same requests while shipping fewer
        wire bytes than the dense max_len-slot handoff."""
        out = run_sub(HEADER + """
from repro.serve.disagg import Disaggregator
cfg = moe_cfg()
_, s0 = stream(cfg, slots=3)
pmesh = mk((2, 4), ("data", "model"))
dmesh = mk((1, 4), ("data", "model"))
pctx = pctx_mod.ParallelCtx(mesh=pmesh, dp_axes=("data",),
                            moe_impl="ep_flat", wire="fp32")
dctx = pctx_mod.ParallelCtx(mesh=dmesh, dp_axes=("data",),
                            moe_impl="ep_flat", wire="fp32")

def run_disagg(**kw):
    dis = Disaggregator(cfg, decode_slots=3, max_len=32, chunk=4,
                        ctx=dctx, prefill_ctx=pctx, **kw)
    assert dis.cross_mesh
    reqs = [Request(i, p, max_new=6)
            for i, p in enumerate(prompts_for(cfg))]
    for r in reqs:
        dis.submit(r)
    dis.run()
    assert all(r.done for r in reqs)
    return dis, [r.out for r in reqs]

dis_d, s_dense = run_disagg()
assert s_dense == s0, (s0, s_dense)
dis_p, s_paged = run_disagg(paged=True, page_size=8, page_storage="bf16")
assert match_frac(s0, s_paged) >= 0.9
assert 0 < dis_p.handoff_bytes < dis_d.handoff_bytes, (
    dis_p.handoff_bytes, dis_d.handoff_bytes)
print("cross-mesh disagg OK", dis_d.handoff_bytes, dis_p.handoff_bytes)
""")
        assert "cross-mesh disagg OK" in out


class TestDecodeWireBytes:
    def test_ep_dedup_fewer_decode_alltoall_bytes(self):
        """The §4.3 dedup claim on the serving hot path: with
        top_k=4 > group_limit=2 and enough slots that per-shard token
        counts clear the 8-row capacity floor, ep_dedup's fused decode
        chunk moves strictly fewer all-to-all bytes than ep_flat (read
        off the lowering — same measurement serve_bench records into
        BENCH_serve.json)."""
        out = run_sub("""
import jax
from repro.launch.mesh import make_mesh as mk
from repro.parallel import context as pctx_mod
from repro.serve.engine import ServeEngine
from benchmarks.train_bench import bench_config

cfg = bench_config()
mesh = mk((2, 4), ("data", "model"))
nb = {}
for impl in ("ep_flat", "ep_dedup"):
    ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                               moe_impl=impl, wire="fp8")
    eng = ServeEngine(cfg, slots=64, max_len=32, chunk=8, ctx=ctx)
    nb[impl] = eng.decode_alltoall_bytes()
assert 0 < nb["ep_dedup"] < nb["ep_flat"], nb
print("decode wire bytes OK", nb)
""")
        assert "decode wire bytes OK" in out


class TestDecodeOverlap:
    """Dual-microbatch decode (ISSUE 10): the fused decode chunk runs
    the slots as two anti-phase halves through ONE scanned layer step,
    so each half's EP all-to-alls overlap the other half's dense
    compute (§2.3.1 — the serving mirror of the training-side
    dual_microbatch_loss)."""

    def _stream(self, cfg, **kw):
        import numpy as np
        eng = ServeEngine(cfg, slots=4, max_len=32, seed=0, chunk=4, **kw)
        prompts = [np.arange(4 + i * 3) * (i + 3) % cfg.vocab_size
                   for i in range(5)]
        reqs = [Request(i, p, max_new=6) for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in reqs)
        return eng, [r.out for r in reqs]

    def test_unmeshed_streams_bitwise_and_body_doubled(self):
        """Dense model, one device: the dual-scan decode must reproduce
        the single-scan streams bitwise (both halves see identical math,
        only the batch is split), and its while body must carry BOTH
        halves' layer compute — dot_general count per scan iteration is
        exactly doubled."""
        from repro.configs.base import get_config, smoke_config
        from repro.parallel import overlap
        cfg = smoke_config(get_config("qwen3-14b"))
        eng, s0 = self._stream(cfg)
        oeng, s1 = self._stream(cfg, params=eng.params, decode_overlap=True)
        assert s1 == s0
        ops = overlap.while_body_op_counts(
            eng.decode_lowered_text(), "dot_general")
        oops = overlap.while_body_op_counts(
            oeng.decode_lowered_text(), "dot_general")
        assert max(oops) == 2 * max(ops) > 0, (ops, oops)

    def test_constructor_validation(self):
        from repro.configs.base import get_config, smoke_config
        cfg = smoke_config(get_config("qwen3-14b"))
        with pytest.raises(ValueError, match="even"):
            ServeEngine(cfg, slots=3, max_len=32, decode_overlap=True)
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(cfg, slots=4, max_len=32, paged=True, page_size=8,
                        decode_overlap=True)
        dcfg = smoke_config(get_config("deepseek-v3-671b"))
        with pytest.raises(ValueError, match="use_mtp"):
            ServeEngine(dcfg, slots=4, max_len=32, use_mtp=True,
                        decode_overlap=True)

    def test_meshed_alltoalls_doubled_in_one_body(self):
        """Under the (2, 4) EP mesh, the overlapped decode's while body
        carries both halves' dispatch+combine all-to-alls (exactly 2x
        the single-scan count, in ONE loop body — that co-residency is
        what lets the compiler overlap them), and the a2a bytes stay
        within [1x, 2x] of single-scan (2x when half-batches pad to the
        8-row dispatch capacity floor; equal once real rows dominate).
        Lowering-only: nothing is executed on the 8 fake devices."""
        out = run_sub("""
import jax
from repro.launch.mesh import make_mesh as mk
from repro.parallel import context as pctx_mod
from repro.parallel import overlap
from repro.serve.engine import ServeEngine
from benchmarks.train_bench import bench_config

cfg = bench_config()
mesh = mk((2, 4), ("data", "model"))
for impl in ("ep_flat", "ep_dedup"):
    ctx = pctx_mod.ParallelCtx(mesh=mesh, dp_axes=("data",),
                               moe_impl=impl, wire="fp8")
    eng = ServeEngine(cfg, slots=8, max_len=32, chunk=8, ctx=ctx)
    oeng = ServeEngine(cfg, params=eng.params, slots=8, max_len=32,
                       chunk=8, ctx=ctx, decode_overlap=True)
    txt, otxt = eng.decode_lowered_text(), oeng.decode_lowered_text()
    ops = max(overlap.while_body_op_counts(txt) or [0])
    oops = max(overlap.while_body_op_counts(otxt) or [0])
    assert oops == 2 * ops > 0, (impl, ops, oops)
    nb = overlap.collective_bytes(txt)
    onb = overlap.collective_bytes(otxt)
    assert nb <= onb <= 2 * nb, (impl, nb, onb)
print("decode overlap a2a OK")
""")
        assert "decode overlap a2a OK" in out
