"""fp8 GEMM weights quantized once at engine build (``ServeEngine``
holds each weight that ``linear`` sends to the fp8 GEMM as a
``fp8.BlockQuantized`` pair).

The pair is what ``quantize_blockwise`` returned inside every step
before, so the serving programs compute the same numbers bit for bit,
and no weight quantization is left inside them.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.core import fp8
from repro.serve.engine import Request, ServeEngine


def _dsv3(wide: bool):
    cfg = smoke_config(get_config("deepseek-v3-671b"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    if wide:
        # inputs of 256 put the MLA projections on the fp8 path too
        # (smoke widths send only the FFN's w_down and MTP's w_proj)
        cfg = dataclasses.replace(
            cfg, d_model=256,
            mla=dataclasses.replace(cfg.mla, q_lora_rank=256,
                                    v_head_dim=64))
    assert cfg.fp8
    return cfg


def _pairs(params):
    flat = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=fp8.is_quantized)[0]
    return {jax.tree_util.keystr(p): w for p, w in flat
            if fp8.is_quantized(w)}


def _engine(cfg, params, paged):
    kw = dict(paged=True, page_size=8, page_storage="fp8") if paged else {}
    return ServeEngine(cfg, params=params, slots=2, max_len=32, seed=0,
                       chunk=4, **kw)


@pytest.mark.parametrize("wide", [False, True], ids=["smoke", "wide"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_prequantized_logits_bitwise_equal(wide, paged):
    cfg = _dsv3(wide)
    params = jax.jit(_engine(cfg, None, False).model.init)(
        jax.random.PRNGKey(1))
    eng = _engine(cfg, params, paged)
    pairs = _pairs(eng.params)
    assert "['dense0']['mlp']['w_down']" in pairs
    if wide:
        for k in ("w_dq", "w_uq", "w_dkv", "w_kr"):
            assert f"['dense0']['attn']['{k}']" in pairs
        # decode absorbs these in f32 einsums: they stay as given
        assert "['dense0']['attn']['w_uk']" not in pairs
    for w in pairs.values():
        assert w.q.dtype == fp8.E4M3 and w.scale.dtype == jnp.float32

    model = eng.model
    toks = jnp.asarray((np.arange(11) * 5 % cfg.vocab_size)[None],
                       jnp.int32)
    prefill = jax.jit(lambda p: model.prefill(p, {"tokens": toks},
                                              extra_slots=5)[0])
    np.testing.assert_array_equal(prefill(eng.params), prefill(params))

    r = Request(0, np.asarray(toks[0]), max_new=4)
    eng.add_request(r)
    step = jax.jit(lambda p, c, t, q: model.decode_step(p, c, t, q)[0])
    t = jnp.asarray([[r.out[0]], [0]], jnp.int32)
    q = jnp.asarray([[toks.shape[1]], [0]], jnp.int32)
    np.testing.assert_array_equal(step(eng.params, eng.cache, t, q),
                                  step(params, eng.cache, t, q))

    # quantizing twice returns the same leaves
    again = _engine(cfg, eng.params, paged)
    assert all(a is b for a, b in zip(jax.tree.leaves(again.params),
                                      jax.tree.leaves(eng.params)))
    assert fp8.quantize_weight(pairs["['dense0']['mlp']['w_down']"]) is \
        pairs["['dense0']['mlp']['w_down']"]
    assert (again.stats["fp8_prequantized_bytes"]
            == eng.stats["fp8_prequantized_bytes"])


def test_no_weight_quantization_in_serving_programs(monkeypatch):
    # the dense-first layer alone, as the benchmark cuts DeepSeek-V3: the
    # MoE experts (core/moe.py) still quantize their weights per call
    cfg = dataclasses.replace(_dsv3(wide=True), num_layers=1)
    eng = _engine(cfg, None, paged=True)
    n = eng.stats["fp8_prequantized_bytes"]
    assert n == sum(w.q.nbytes + w.scale.nbytes
                    for w in _pairs(eng.params).values())
    assert n > 0

    def refuse(*a, **k):
        raise AssertionError("weight quantized inside a serving program")

    monkeypatch.setattr(fp8, "quantize_blockwise", refuse)
    eng._decode_fn.lower(eng.params, eng.cache, eng._device_state())
    eng._get_prefill(16).lower(eng.params, jnp.zeros((1, 16), jnp.int32),
                               jnp.asarray([16], jnp.int32), {})
    # the bf16 path still quantizes per call: the patch is live
    with pytest.raises(AssertionError, match="serving program"):
        fp8.fp8_linear(jnp.ones((2, 256)), jnp.ones((256, 128)))


def test_non_fp8_config_holds_no_pairs():
    cfg = smoke_config(get_config("qwen3-14b"))
    assert not cfg.fp8
    eng = ServeEngine(cfg, slots=1, max_len=16)
    assert eng.stats["fp8_prequantized_bytes"] == 0
    assert not _pairs(eng.params)
