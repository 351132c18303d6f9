"""The check at a tiny size on the CPU: the float32 reference against the
serving engine (teacher-forced through bucketed prefill and the paged
cache), the control one precision step down, and a whole run of the
harness with the timed path broken underneath, which must come out not
correct. The harness's look for a chip is skipped; everything else runs
as on the chip."""
from __future__ import annotations

import json
import os
import shutil
from typing import Optional

import jax
import pytest

from bench import check, harness

ROOT = harness.ROOT

TINY = {
    "dsv3-mla3": dict(
        hf=dict(hidden_size=128, num_attention_heads=4, kv_lora_rank=32,
                q_lora_rank=48, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, intermediate_size=256, vocab_size=512,
                num_hidden_layers=2),
        program=dict(num_layers=2, d_model=128, num_heads=4,
                     num_kv_heads=4, d_ff=256, vocab_size=512, head_dim=16,
                     mla=dict(kv_lora_rank=32, q_lora_rank=48,
                              qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)),
        engine=dict(slots=4, page_size=8, chunk=4)),
}
# the cell's kind of traffic at a CPU size: a backlog whose first requests
# stand for a full engine's steady state
MIX = dict(kind="backlog", requests=400, start="steady", block=4,
           prompt=dict(dist="lognormal", median=24, sigma=0.6, min=8,
                       max=48),
           output=dict(dist="lognormal", median=10, sigma=0.5, min=4,
                       max=16))


def tiny_root(tmp_path, name: str, f32: bool = False, storage=None,
              limit: Optional[float] = None) -> str:
    """A checkout holding the harness's data files, with ``name``'s
    configuration cut to a CPU size (every published key scaled, the
    program overridden to match) and one backlog cell ``<name>.tiny``.
    The check's limit stays the configuration's unless ``limit`` is
    given."""
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", sub), root / "bench" / sub)
    b = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    path = f"bench/configs/{name}.json"
    cfg = json.loads(open(os.path.join(ROOT, path)).read())
    t = TINY[name]
    cfg.update(t["hf"])
    over = dict(cfg["program"].get("overrides", {}))
    over.update(t["program"])
    if f32:
        over.update(dtype="float32", param_dtype="float32", fp8=False)
        cfg.pop("quantization_config")
    cfg["program"] = dict(cfg["program"], overrides=over)
    cfg["engine"] = dict(cfg["engine"], **t["engine"])
    if storage:
        cfg["engine"]["page_storage"] = storage
    cfg["check"] = dict(cfg["check"], requests=4, min_tokens=10)
    if limit is not None:
        cfg["check"]["max_logit_gap"] = limit
    (root / path).write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(MIX))
    b["configs"] = [dict(name=name, source=cfg["source"], file=path,
                         reduced=cfg["reduced"], why="tiny")]
    b["workloads"] = [dict(name=f"{name}.tiny", config=name, traffic="tiny",
                           chips=1, why="tiny")]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return str(root)


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peak_flops", lambda kind: 1e12)
    monkeypatch.setattr(harness, "enable_compile_cache",
                        lambda root: "off")


def run_tiny(root: str, name: str, seed: int = 2**31 + 77):
    return harness.run(f"{name}.tiny", seed, 1.5, False, 0.0,
                       os.path.join(root, "out"), root)


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_agrees_with_engine_in_float32(tmp_path, on_cpu, name):
    root = tiny_root(tmp_path, name, f32=True, storage="bf16")
    r = run_tiny(root, name)
    gap = r["check"]["max_logit_gap"]["value"]
    assert r["check"]["checked_tokens"]["value"] >= 10
    assert gap < 1e-3, gap
    assert r["correct"]
    assert set(r["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert r["attempted"] > 4                 # slots refilled in the window


@pytest.mark.parametrize("name", sorted(TINY))
def test_altered_token_comes_out_not_correct(tmp_path, on_cpu, monkeypatch,
                                             name):
    """A token altered where it is produced (the fused decode chunk's
    output) must fail the check."""
    from repro.serve import engine as eng_mod
    root = tiny_root(tmp_path, name)
    orig = eng_mod.ServeEngine.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        fn = self._decode_fn

        def broken(params, cache, state):
            toks, emitted, cache, st = fn(params, cache, state)
            return (toks + 1) % self.cfg.vocab_size, emitted, cache, st

        self._decode_fn = broken

    monkeypatch.setattr(eng_mod.ServeEngine, "__init__", init)
    r = run_tiny(root, name)
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > 0.5


def _in_cells():
    b = harness.benchmark()
    return sorted({w["config"] for w in b["workloads"]} & set(TINY))


@pytest.mark.parametrize("name", _in_cells())
def test_control_reads_above_the_program(tmp_path, on_cpu, name):
    """The control, the reference with its operands one precision step
    down, put in the program's place on the same served positions and
    judged by the same comparison at the configuration's limit, comes out
    not correct; the program as configured comes out correct."""
    root = tiny_root(tmp_path, name)
    served = {}
    orig = check.run_check

    def keep(config, params, seed, cands, limit=None):
        out = orig(config, params, seed, cands, limit)
        served.update(config=config, params=params, seed=seed, cands=cands)
        return out

    import unittest.mock as mock
    with mock.patch.object(check, "run_check", keep):
        r = run_tiny(root, name)
    ctrl = check.run_check(served["config"], served["params"],
                           served["seed"], served["cands"], control=True)
    prog = r["check"]["max_logit_gap"]
    assert r["correct"], r["check"]
    assert not ctrl["correct"], ctrl["numbers"]
    assert ctrl["numbers"]["max_logit_gap"]["limit"] == prog["limit"]
    assert ctrl["numbers"]["max_logit_gap"]["value"] > 2 * prog["value"]
