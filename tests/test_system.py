"""End-to-end system behaviour: train -> checkpoint -> serve round trip on
the paper's full stack, MTP learns, cost-model calibration, config
registry integrity, and the chip smoke script's refusal of the CPU."""
import dataclasses
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, get_config, list_archs, smoke_config
from repro.models.api import build_model, count_params
from repro.train.trainer import Trainer, TrainConfig


def test_registry_complete():
    archs = list_archs()
    assert len(archs) == 11          # 10 assigned + the paper's own
    assert "deepseek-v3-671b" in archs


def test_assigned_dims_exact():
    """Spot-check the assignment's exact dims."""
    c = get_config("yi-34b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (60, 7168, 56, 8, 20480, 64000)
    c = get_config("qwen3-moe-30b-a3b")
    assert (c.moe.num_experts, c.moe.top_k, c.moe.expert_ff) == (128, 8, 768)
    c = get_config("mamba2-2.7b")
    assert c.ssm.d_state == 128 and c.vocab_size == 50280
    c = get_config("recurrentgemma-9b")
    assert c.num_kv_heads == 1 and c.rglru.window == 2048


def test_param_counts_match_nominal():
    for arch, lo, hi in [("deepseek-v3-671b", 650e9, 700e9),
                         ("yi-34b", 32e9, 36e9),
                         ("qwen3-moe-30b-a3b", 29e9, 32e9),
                         ("llama4-maverick-400b-a17b", 380e9, 420e9),
                         ("mamba2-2.7b", 2.5e9, 3.1e9)]:
        n = count_params(get_config(arch))
        assert lo < n < hi, (arch, n)


def test_train_checkpoint_serve_roundtrip():
    """Train the paper stack briefly, checkpoint, restore into the serving
    engine, decode — the full lifecycle."""
    cfg = smoke_config(get_config("deepseek-v3-671b"))
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(peak_lr=2e-3, warmup=3, total_steps=20,
                         ckpt_dir=d, ckpt_every=8)
        tr = Trainer(cfg, tc, global_batch=2, seq_len=24)
        tr.run(16)
        from repro.train import checkpoint as ckpt
        assert ckpt.latest_step(d) == 16

        from repro.serve.engine import Request, ServeEngine
        like = {"params": tr.model.init(jax.random.PRNGKey(0))}
        state, _ = ckpt.restore(d, like)
        eng = ServeEngine(cfg, params=state["params"], slots=2, max_len=48,
                          use_mtp=True)
        eng.add_request(Request(0, np.arange(6) % cfg.vocab_size,
                                max_new=8))
        eng.run_until_done()
        assert eng.stats["tokens"] >= 8


def test_mtp_learns_predictable_stream():
    """On a fully deterministic stream the MTP draft acceptance should rise
    well above chance (paper §2.3.3 reports 80-90% on natural text)."""
    cfg = smoke_config(get_config("deepseek-v3-671b"))
    cfg = dataclasses.replace(
        cfg, vocab_size=32,
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    from repro.data.pipeline import SyntheticCorpus

    class Cyclic(SyntheticCorpus):
        def batch_at(self, step):
            t = (np.arange(self.seq) + step) % 8
            toks = np.tile(t, (self.batch, 1)).astype(np.int32)
            labels = np.concatenate(
                [toks[:, 1:], np.full((self.batch, 1), -1, np.int32)], 1)
            return {"tokens": toks, "labels": labels}

    tc = TrainConfig(peak_lr=5e-3, warmup=3, total_steps=60)
    tr = Trainer(cfg, tc, data=Cyclic(32, 24, 4), global_batch=4, seq_len=24)
    tr.run(50)

    from repro.serve.engine import Request, ServeEngine
    eng = ServeEngine(cfg, params=tr.params, slots=1, max_len=64,
                      use_mtp=True)
    eng.add_request(Request(0, (np.arange(10) % 8).astype(np.int32),
                            max_new=20))
    eng.run_until_done()
    assert eng.acceptance_rate() > 0.5, eng.stats


def test_cost_model_vs_paper_table2():
    """Analytic FLOPs reproduce the paper's Table 2 within 5%."""
    from repro.launch.costs import step_costs
    cfg = get_config("deepseek-v3-671b")
    c = step_costs(cfg, SHAPES["train_4k"], remat="none")
    gflops_tok = c.flops_fwd * 3 / c.tokens / 1e9
    assert abs(gflops_tok - 250) / 250 < 0.05


def test_cost_model_calibration_unrolled():
    """Calibrate analytic FLOPs against XLA cost_analysis on a small
    config where loop undercounting is bounded (2 layers)."""
    from repro.launch import costs as costs_mod
    from repro.configs.base import ShapeCfg
    cfg = smoke_config(get_config("glm4-9b"))
    cfg = dataclasses.replace(cfg, num_layers=2, fp8=False)
    m = build_model(cfg)
    B, S = 2, 128
    shape = ShapeCfg("cal", S, B, "train")

    def fwd(params, batch):
        return m.loss(params, batch)[0]

    structs = m.param_structs()
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    compiled = jax.jit(jax.grad(fwd)).lower(structs, batch).compile()
    xla_flops = compiled.cost_analysis().get("flops", 0)
    c = costs_mod.step_costs(cfg, shape, remat="none")
    ratio = xla_flops / c.flops_total
    assert 0.2 < ratio < 2.0, (xla_flops, c.flops_total)


def test_chip_smoke_refuses_cpu():
    """``chip_smoke.py`` must fail, naming the platform, before it builds
    anything when JAX has no TPU: a smoke run that fell back to the CPU
    would pass without proving anything about the chip."""
    script = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr, r.stderr[-2000:]
    assert r.stdout == "", r.stdout      # no phase ran, no result line
