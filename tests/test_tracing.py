"""The serving path's own names for its work, on the CPU at smoke size:
engine spans in a profiler trace (nested, per-request ones carrying
``rid``), the engine's prefill and decode counters, the device scopes the
decode chunk's HLO carries, and a ``name=`` on every ``pallas_call``."""
import ast
import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro import kernels
from repro.configs.base import get_config, smoke_config
from repro.serve.engine import Request, ServeEngine, bucket_length

KERNELS = os.path.dirname(kernels.__file__)


@pytest.fixture(scope="module")
def dsv3_cfg():
    cfg = smoke_config(get_config("deepseek-v3-671b"))
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))


def _paged(cfg, **kw):
    return ServeEngine(cfg, slots=2, max_len=64, seed=0, chunk=4,
                       paged=True, page_size=8, **kw)


def _serve(eng, lengths, max_new=6):
    reqs = [Request(i, (np.arange(n) * (i + 3)) % eng.cfg.vocab_size,
                    max_new=max_new) for i, n in enumerate(lengths)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return reqs


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name.split("#")[0], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_engine_spans_nest_in_the_tick_and_carry_rid(dsv3_cfg, tmp_path):
    eng = _paged(dsv3_cfg)
    _serve(eng, [5], max_new=3)                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    _serve(eng, [5, 13], max_new=6)
    jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = {n for n, *_ in spans}
    assert {"engine.step", "engine.admit", "engine.prefill",
            "engine.sample_first", "engine.scatter", "engine.decode",
            "engine.sync", "engine.harvest"} <= names

    def inside(span, outer):
        return any(o[0] == outer and o[1] <= span[1] and span[2] <= o[2]
                   for o in spans)

    for s in spans:
        if s[0] in ("engine.admit", "engine.decode", "engine.sync",
                    "engine.harvest"):
            assert inside(s, "engine.step"), s
        if s[0] in ("engine.prefill", "engine.sample_first",
                    "engine.scatter"):
            assert inside(s, "engine.admit"), s
            assert s[3].get("rid") in (0, 1), s
    rids = {s[3]["rid"] for s in spans if s[0] == "engine.prefill"}
    assert rids == {0, 1}


def test_prefill_counters_count_prompt_and_bucket_positions(dsv3_cfg):
    eng = _paged(dsv3_cfg)
    lengths = [5, 13, 20]
    _serve(eng, lengths)
    assert eng.stats["prefill_tokens"] == sum(lengths)
    assert eng.stats["prefill_padded_tokens"] == sum(
        bucket_length(n, eng.max_len) for n in lengths) == 8 + 16 + 32


def test_chunked_prefill_counts_whole_chunks(dsv3_cfg):
    eng = _paged(dsv3_cfg, prefill_chunk=8)
    _serve(eng, [5, 13])
    assert eng.stats["prefill_tokens"] == 18
    assert eng.stats["prefill_padded_tokens"] == 8 + 16


def test_decode_counters_count_lanes_and_emitted_tokens(dsv3_cfg):
    eng = _paged(dsv3_cfg)
    reqs = _serve(eng, [5, 13, 20], max_new=7)
    s = eng.stats
    assert s["decode_dispatches"] > 0
    assert s["decode_lane_steps"] == (s["decode_dispatches"] * eng.slots
                                      * eng.chunk)
    # every token after each request's first came out of a decode chunk
    assert s["decode_tokens"] == sum(len(r.out) - 1 for r in reqs)
    assert s["decode_tokens"] == s["tokens"] - s["first_tokens"]
    assert 0 < s["decode_tokens"] <= s["decode_lane_steps"]


def test_live_page_counters_follow_each_lanes_position(dsv3_cfg):
    """One decode chunk of 4 steps on 2 slots of 8 pages of 8: lane 0
    (prompt 5) decodes at 5, 6, 7, 8, so 1, 1, 1, 2 live pages; lane 1
    (prompt 13, one token left after its first) decodes at 13, then
    stays at 14: 2 pages each step."""
    eng = _paged(dsv3_cfg, attn_impl="pallas")
    for i, (n, new) in enumerate([(5, 10), (13, 2)]):
        eng.submit(Request(i, (np.arange(n) * (i + 3)) % eng.cfg.vocab_size,
                           max_new=new))
    eng.step()
    assert eng.stats["decode_dispatches"] == 1
    assert eng.stats["decode_live_pages"] == (1 + 1 + 1 + 2) + 4 * 2
    assert eng.stats["decode_grid_pages"] == 2 * 8 * 4


@pytest.mark.parametrize("kw", [dict(paged=False), dict(paged=True)],
                         ids=["dense_pallas", "paged_xla"])
def test_live_page_counters_stay_zero_off_the_kernel(dsv3_cfg, kw):
    impl = "pallas" if not kw["paged"] else ""
    eng = ServeEngine(dsv3_cfg, slots=2, max_len=64, seed=0, chunk=4,
                      attn_impl=impl, **kw)
    _serve(eng, [5, 13], max_new=3)
    assert eng.stats["decode_dispatches"] > 0
    assert eng.stats["decode_live_pages"] == 0
    assert eng.stats["decode_grid_pages"] == 0


def test_decode_chunk_hlo_carries_the_device_scopes(dsv3_cfg):
    cfg = dataclasses.replace(dsv3_cfg, fp8=True)
    eng = _paged(cfg)
    _serve(eng, [5], max_new=2)
    # the optimized HLO: its ops' op_name metadata is what the device
    # trace reports as each op's scope path
    hlo = eng._decode_fn.lower(eng.params, eng.cache,
                               eng._device_state()).compile().as_text()
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', hlo)]
    for scope in ("layers", "attn", "ffn", "fp8_quant", "cache_write",
                  "unembed", "sample"):
        assert any(scope in p for p in paths), scope
    # the weight and activation quantization sits inside the sub-blocks,
    # and they inside the layer scan, whose own slicing is named too
    assert any("fp8_quant" in p and "ffn" in p and "layers" in p
               for p in paths)
    assert any("layers" in p and not {"attn", "ffn"} & set(p)
               for p in paths)


def _pallas_call_names():
    """``{file: [name= of each pl.pallas_call]}`` over the kernel
    packages (None where a call has no ``name=``)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(KERNELS, "*", "*.py"))):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                name = next((k.value.value for k in node.keywords
                             if k.arg == "name"), None)
                out.setdefault(os.path.relpath(path, KERNELS), []).append(
                    name)
    return out


def test_every_pallas_call_is_named_after_its_registry_op():
    found = _pallas_call_names()
    names = [n for ns in found.values() for n in ns]
    assert None not in names, found
    assert len(names) == len(set(names)), found
    assert set(names) == set(kernels.registry.names()), found
