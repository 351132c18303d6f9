"""CPU tests of ``bench/scopes.py`` on synthetic events: the scope split of
a program (a loop op never counted beside its body), kernels found by
name, idle time given to the innermost engine span, span metadata
stripped, and every reading absent, not wrong, on a program that names
nothing."""
from __future__ import annotations

import pytest

from bench import scopes
from bench import trace as trace_mod

E = scopes.Event
DEV = "/device:TPU:0"
HOST = "/host:CPU"
ms = 1e6


def _op(name, t0, t1, scope):
    return E(DEV, "XLA Ops", name, t0 * ms, (t1 - t0) * ms, scope)


def _span(name, t0, t1):
    return E(HOST, "python", name, t0 * ms, (t1 - t0) * ms)


def _events(named=True):
    """One decode chunk (0-50 ms: a while loop over attention, its fp8
    quantization, the MLA kernel, the FFN with the conversion and copy
    made for it, two copies of the loop's own, then the unembedding) and
    one prefill (60-80 ms) in two engine ticks."""
    def sc(path):
        return f"jit(decode_chunk)/while/body/{path}" if named else ""

    kernel = "%paged_mla_decode.12" if named else "%_paged_mla_decode_kernel.12"
    ev = [
        _span("bench.window", 0, 100),
        E(DEV, "XLA Modules", "jit_decode_chunk(3)", 0, 50 * ms),
        E(DEV, "XLA Modules", "jit_prefill(4)", 60 * ms, 20 * ms),
        _op("%while.48", 0, 40, "jit(decode_chunk)/while" if named else ""),
        _op("%fusion.1 = f32[8] fusion(%p)", 0, 10,
            sc("attn/fp8_quant/convert_element_type")),
        _op(kernel, 10, 25, sc("attn/pallas_call")),
        # a weight's conversion and layout copy, with no op_name, run
        # just ahead of the FFN fusion they are made for
        _op("%fusion.7 = f32[8]{0:T(8,128)} fusion(bf16[8] %w)", 25, 26, ""),
        _op("%copy.9 = f32[8]{0:T(128)} copy(f32[8]{0:T(8,128)} %fusion.7)",
            26, 27, ""),
        _op("%fusion.2 = f32[8] fusion(f32[8] %copy.9, f32[8] %p)", 27, 33,
            sc("ffn/dot_general")),
        # a copy with no op_name ahead of the loop's own carry copy, whose
        # path holds none of the scopes
        _op("%copy.8 = f32[8] copy(f32[8] %fusion.2)", 33, 35, ""),
        _op("%copy.3 = f32[8] copy(%p)", 35, 40,
            "jit(decode_chunk)/while" if named else ""),
        _op("%fusion.4", 40, 50, "jit(decode_chunk)/unembed/dot_general"
            if named else ""),
        _op("%fusion.5", 60, 80, "jit(prefill)/attn/dot_general"
            if named else ""),
    ]
    if named:
        ev += [
            _span("engine.step", 0, 55), _span("engine.admit", 0, 0),
            _span("engine.decode", 0, 1), _span("engine.sync", 1, 50),
            _span("engine.harvest", 50, 55),
            _span("engine.step", 58, 85), _span("engine.admit", 58, 62),
            _span("engine.prefill", 59, 61),
        ]
    return ev


STATS = {"prefill_tokens": 72, "prefill_padded_tokens": 100,
         "decode_tokens": 60, "decode_lane_steps": 64}


def test_scope_seconds_count_leaf_ops_once():
    s = scopes.scope_seconds(_events(), "decode_chunk")
    assert s == {"attn": pytest.approx(0.025),
                 "fp8_quant": pytest.approx(0.010),
                 # with the conversion and copy made for it
                 "ffn": pytest.approx(0.008),
                 "inherited": pytest.approx(0.004),
                 "unembed": pytest.approx(0.010),
                 "": pytest.approx(0.007),          # the loop's copies
                 "total": pytest.approx(0.050)}     # not 0.090: no while
    assert scopes.scope_seconds(_events(), "prefill") == {
        "attn": pytest.approx(0.020), "total": pytest.approx(0.020)}
    assert scopes.scope_seconds(_events(), "quant") == {}


def test_kernel_found_by_name_despite_its_suffix():
    assert scopes.kernel_seconds(_events(), "paged_mla_decode") == \
        pytest.approx(0.015)
    assert scopes.kernel_name("%fusion.1 = f32[8] fusion(%p)") == "fusion"
    # an unnamed kernel keeps its function's name, which is not the op's
    assert scopes.kernel_seconds(_events(named=False),
                                 "paged_mla_decode") is None


def test_idle_time_goes_to_the_innermost_engine_span():
    g = scopes.engine_gaps(_events(), 0, 100 * ms)
    assert g["ticks"] == 2
    # idle 50-60 ms: 50-55 in engine.harvest, 55-60 outside any tick;
    # 58-60: 58-59 in engine.admit, 59-60 in engine.prefill; 80-85 in
    # the second tick under no phase span
    assert g["by_span"] == {"engine.harvest": pytest.approx(0.005),
                            "engine.admit": pytest.approx(0.001),
                            "engine.prefill": pytest.approx(0.001),
                            "engine.step": pytest.approx(0.005)}
    assert g["idle_s"] == pytest.approx(0.012)


def test_span_metadata_is_stripped():
    assert scopes.span_name("engine.prefill#rid=17#") == "engine.prefill"
    assert scopes.span_name("engine.scatter#rid=3,n=2#") == "engine.scatter"
    assert scopes.span_name("bench.step") == "bench.step"


def test_metrics_per_step_per_tick_and_shares():
    m = scopes.metrics(_events(), STATS, chunk=8)
    assert m == {"decode_attn_ms": pytest.approx(25 / 8),
                 "decode_fp8_quant_ms": pytest.approx(10 / 8),
                 "mla_kernel_ms": pytest.approx(15 / 8),
                 "host_gap_ms": pytest.approx(6.0),
                 "prefill_pad_pct": pytest.approx(28.0),
                 "decode_occupancy_pct": pytest.approx(93.75)}


def test_a_program_that_names_nothing_reads_nothing():
    m = scopes.metrics(_events(named=False), {"tokens": 5}, chunk=8)
    assert m == dict.fromkeys(m, None) and len(m) == 6
    r = scopes.report(_events(named=False), {}, chunk=8)
    assert r["engine_gaps"]["ticks"] == 0 and r["ticks"] == 0


def test_scoped_events_reduce_as_the_harness_reads_them():
    """``bench.trace.reduce`` takes the scoped events unchanged."""
    r = trace_mod.reduce(_events(), "bench.window")
    assert r["program_counts"] == {"decode_chunk": {DEV: 1},
                                   "prefill": {DEV: 1}}
    assert r["busy_s"] == {DEV: pytest.approx(0.070)}


def test_load_reads_engine_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("engine.step"):
            with jax.profiler.TraceAnnotation("engine.prefill", rid=7):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = scopes.load(trace_mod.find_xplane(str(tmp_path)))
    names = [e.name for e in scopes.engine_spans(ev)]
    assert sorted(names) == ["engine.prefill", "engine.step"]
    r = scopes.report(ev, {}, chunk=8)      # no device planes on CPU
    assert r["engine_gaps"]["ticks"] == 1 and r["window_s"] > 0


# -- reading the device planes of a trace file ----------------------------------

def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        if not x:
            out.append(b)
            return bytes(out)
        out.append(b | 0x80)


def _msg(*fields):
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _xspace():
    """A device plane with two ops (one with a scope path in its
    metadata's ``tf_op`` statistic) and a host plane with one span."""
    stat_names = [(1, "tf_op"), (2, "other")]
    device = _msg(
        (1, 7), (2, DEV),
        (3, _msg((1, 1), (2, "XLA Ops"), (3, 1000),
                 (4, _msg((1, 10), (2, 5_000_000), (3, 2_000_000))),
                 (4, _msg((1, 11), (2, 8_500), (3, 1_500))))),
        (4, _msg((1, 10), (2, _msg(
            (1, 10), (2, "%fusion.1 = f32[8] fusion(%p)"),
            (5, _msg((1, 1), (5, "jit(f)/attn/dot"))))))),
        (4, _msg((1, 11), (2, _msg((1, 11), (2, "%copy.2 = f32[8] copy(%p)"),
                                   (5, _msg((1, 2), (5, "x/y"))))))),
        *[(5, _msg((1, k), (2, _msg((1, k), (2, n))))) for k, n in stat_names])
    host = _msg((1, 8), (2, HOST),
                (3, _msg((1, 2), (2, "python"), (3, 1000),
                         (4, _msg((1, 20), (2, 1_000_000), (3, 9_000_000))))),
                (4, _msg((1, 20), (2, _msg((1, 20),
                                           (2, "engine.step#rid=3#"))))))
    return _msg((1, device), (1, host))


def test_device_ops_read_with_their_scope_as_profile_data_times_them(
        tmp_path):
    from jax.profiler import ProfileData
    raw = _xspace()
    ours = scopes.device_events(raw)
    assert [e.scope for e in ours] == ["jit(f)/attn/dot", ""]
    theirs = [(line.name, ev.name, ev.start_ns, ev.duration_ns)
              for plane in ProfileData.from_serialized_xspace(raw).planes
              if plane.name == DEV for line in plane.lines
              for ev in line.events]
    assert [(e.line, e.name, e.start_ns, e.dur_ns) for e in ours] == theirs
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    ev = scopes.load(str(path))
    assert sorted((e.plane, e.name) for e in ev) == [
        (DEV, "%copy.2 = f32[8] copy(%p)"),
        (DEV, "%fusion.1 = f32[8] fusion(%p)"), (HOST, "engine.step")]
