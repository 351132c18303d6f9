"""CPU tests of the benchmark harness: window arithmetic, traffic, files
found by name, FLOP counts, trace reduction, the references against the
serving engine, and the check's control and faults at a tiny size."""
from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from bench import costs, harness, traffic, window as win
from bench import trace as trace_mod

ROOT = harness.ROOT


# -- window arithmetic ---------------------------------------------------------

def _track(rid, due, deliveries):
    t = win.Track(rid, due, submitted=due)
    t.deliveries = list(deliveries)
    return t


def test_window_with_no_finished_request_yields_every_latency_metric():
    # two long requests, neither near its end: chunks of 8 tokens
    tracks = [_track(0, 0.5, [(1.0, 1), (1.5, 8), (2.0, 8)]),
              _track(1, 0.7, [(1.5, 1), (2.0, 8), (2.5, 8)])]
    m = win.end_to_end(tracks, 0.0, 3.0)
    assert set(m) == {"ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms",
                      "output_tokens_per_s"}
    assert m["ttft_p50_ms"] == pytest.approx(650.0)
    assert m["tpot_p95_ms"] == pytest.approx(1000.0 / 16)
    assert m["output_tokens_per_s"] == pytest.approx(34 / 3.0)


def test_censored_ttft_enters_at_its_lower_bound():
    tracks = [_track(0, 1.0, [(1.2, 1)]), _track(1, 2.0, []),
              _track(2, 2.5, [(3.5, 1)])]
    ttft, censored = win.ttft_sample(tracks, 0.0, 3.0)
    assert censored == 2
    assert ttft == pytest.approx([0.2, 1.0, 0.5])


def test_tpot_counts_only_deliveries_inside_the_window():
    t = _track(0, -5.0, [(-4.0, 1), (-1.0, 8), (0.5, 8), (1.0, 8)])
    assert win.tpot_sample([t], 0.0, 2.0) == pytest.approx([0.5 / 8])
    assert win.tokens_in([t], 0.0, 2.0) == 16


# -- traffic --------------------------------------------------------------------

MIX = dict(kind="open", rate_per_s=10.0, lead_s=1.0,
           prompt=dict(dist="lognormal", median=64, sigma=0.8, min=8,
                       max=256),
           output=dict(dist="lognormal", median=16, sigma=0.6, min=4,
                       max=64))


def test_traffic_same_seed_same_inputs_and_every_seed_same_work():
    big = 2**31 + 12345
    a = traffic.generate(MIX, big, 5.0, 1000)
    b = traffic.generate(MIX, big, 5.0, 1000)
    c = traffic.generate(MIX, 7, 5.0, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert a[-1].due == pytest.approx(c[-1].due)      # same gaps, reordered
    assert [r.due for r in a] != [r.due for r in c]


def test_sessions_share_document_prefixes():
    mix = dict(MIX, kind="sessions", sessions=dict(
        document=dict(dist="loguniform", min=64, max=128),
        asks=dict(dist="uniform", min=3, max=5),
        in_play=dict(dist="uniform", min=2, max=5)))
    reqs = traffic.generate(mix, 3, 5.0, 1000)
    by_doc = {}
    for r in reqs:
        by_doc.setdefault(r.group, []).append(r)
    for rs in by_doc.values():
        assert all((r.prompt[:64] == rs[0].prompt[:64]).all() for r in rs)
    assert min(len(v) for v in list(by_doc.values())[:-1]) >= 3
    # between two asks of one document come other documents' asks
    order = [r.group for r in reqs]
    half = len(order) // 2
    assert all(order[i] != order[i + 1] for i in range(half))


BACKLOG = dict(kind="backlog", requests=200, start="steady", block=16,
               prompt=dict(dist="lognormal", median=1024, sigma=0.7,
                           min=256, max=4096),
               output=dict(dist="lognormal", median=512, sigma=0.5,
                           min=128, max=1024))


def test_steady_backlog_same_work_for_every_seed():
    slots = 32
    a = traffic.generate(BACKLOG, 2**31 + 5, 30.0, 1000, slots)
    b = traffic.generate(BACKLOG, 11, 30.0, 1000, slots)

    def lens(rs):
        return sorted((len(r.prompt), r.max_new) for r in rs)

    assert lens(a[:slots]) == lens(b[:slots])          # the residents
    for k in range(slots, 200 - 16, 16):               # each dealt block
        blk = slice(k, k + 16)
        assert sorted(len(r.prompt) for r in a[blk]) == \
            sorted(len(r.prompt) for r in b[blk])
        assert sorted(r.max_new for r in a[blk]) == \
            sorted(r.max_new for r in b[blk])
    assert [r.max_new for r in a[slots:]] != [r.max_new for r in b[slots:]]
    longest = traffic.longest_context(BACKLOG)
    assert all(len(r.prompt) + r.max_new <= longest for r in a)


def test_steady_residents_free_slots_at_the_steady_rate():
    """In a full engine's steady state the tokens a slot has left are
    spread as ``P(L > r) / E[L]``: their mean is ``E[L^2] / 2E[L]``, far
    below the mean answer, and they rise evenly from a few tokens."""
    spec = BACKLOG["output"]
    age, left = traffic.steady_residents(spec, 128)
    L = traffic.lengths(spec, 1024, np.random.default_rng(0)).astype(float)
    assert left.mean() == pytest.approx((L * L).sum() / L.sum() / 2, rel=0.02)
    assert left.min() < 8 and left.max() <= spec["max"]
    assert (np.diff(left) >= 0).all()
    assert ((age + left) >= spec["min"]).all()
    assert ((age + left) <= spec["max"]).all()


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Engine:
    """Serves ``chunk`` tokens per slot per tick, each tick one second:
    admission gives a request its first token, then it decodes."""

    def __init__(self, slots, clock, chunk=4):
        self.slots, self.clock, self.chunk = slots, clock, chunk
        self.active = [None] * slots
        self.pending = []
        self.stats, self.trace_counts = {"steps": 0}, {"decode": 1}

    def prefix_stats(self):
        return {}

    def submit(self, q):
        self.pending.append(q)

    def has_work(self):
        return bool(self.pending) or any(self.active)

    def step(self):
        for i in range(self.slots):
            if self.active[i] is None and self.pending:
                self.active[i] = self.pending.pop(0)
                self.active[i].out.append(0)
        for i, q in enumerate(self.active):
            if q is None:
                continue
            q.out.extend([1] * min(self.chunk, q.max_new - len(q.out)))
            if len(q.out) >= q.max_new:
                q.done, self.active[i] = True, None
        self.clock.t += 1.0
        self.stats["steps"] += 1


def test_backlog_window_holds_whole_ticks_after_the_fill():
    clock = _Clock()
    eng = _Engine(2, clock)
    reqs = [traffic.Req(i, 0.0, np.zeros(4, np.int32), n)
            for i, n in enumerate([5, 9] + [8] * 18)]
    w = harness.run_window(eng, reqs, dict(kind="backlog"), 2.5, None,
                           clock=clock, sleep=lambda s: None)
    # tick 1 fills both slots; the window holds ticks 2-4, the first that
    # ends 2.5 s or more after it opened closing it
    assert (w.w0, w.w1) == (1.0, 4.0)
    assert win.tokens_in(w.tracks, w.w0, w.w1) == 9 + 8 + 8
    assert win.attempted(w.tracks, w.w0, w.w1) == 4
    m = win.end_to_end(w.tracks, w.w0, w.w1)
    assert m["output_tokens_per_s"] == pytest.approx(25 / 3)


# -- files found by name -----------------------------------------------------------

def _copy_root(tmp_path):
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", sub), root / "bench" / sub)
    return root


def test_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    root = _copy_root(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/dsv3-mla3.json").read_text())
    cfg["name"] = "newcfg"
    (root / "bench/configs/newcfg.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/newmix.json").write_text(json.dumps(MIX))
    (root / "bench/metrics/new_metric.x.py").write_text(
        "def read(rec):\n    return 2.0 * rec['w1']\n")
    b["configs"].append(dict(b["configs"][0], name="newcfg",
                             file="bench/configs/newcfg.json"))
    b["workloads"].append(dict(name="newcfg.newmix", config="newcfg",
                               traffic="newmix", chips=1, why="test"))
    b["per_layer"].append(dict(name="new_metric.x", unit="ms",
                               better="lower", source="host_clock",
                               layer="x", moves="setup_s",
                               workloads=["newcfg.newmix"]))
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    c = harness.cell("newcfg.newmix", str(root))
    assert c.mix == MIX and c.config["name"] == "newcfg"
    assert [m["name"] for m in c.per_layer] == ["new_metric.x"]
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert harness.metric_reader("new_metric.x", str(root))({"w1": 3}) == 6.0


def _record():
    """A run's record as the readers see it: two requests, one traced
    second, a decode chunk and a prefill chunk in the trace."""
    tracks = [_track(0, 0.1, [(0.5, 1), (1.0, 8)]),
              _track(1, 0.2, [(1.0, 1)])]
    dev = {DEV: 0.5}
    trace = dict(programs={"decode_chunk": dev, "prefill": dev,
                           "quant": dev, "scatter": dev},
                 program_counts={"decode_chunk": {DEV: 2},
                                 "prefill": {DEV: 1}})
    return dict(hf={"num_hidden_layers": 3}, tracks=tracks, w0=0.0, w1=2.0,
                trace=trace, stats={}, chips=1, prompts={0: 64, 1: 128},
                chunk=8, busy_wall_s=1.0, flops=1e12, peak_flops=1e14,
                traced_prompt_tokens=128)


@pytest.mark.parametrize("name", sorted(
    f[:-3] for f in os.listdir(os.path.join(ROOT, "bench", "metrics"))
    if f.endswith(".py")))
def test_every_metric_reader_reads_a_record(name):
    v = harness.metric_reader(name)(_record())
    assert isinstance(v, float) and v > 0


def test_every_listed_metric_and_cell_has_its_files():
    b = harness.benchmark()
    for m in b["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for w in b["workloads"]:
        c = harness.cell(w["name"])
        assert c.end_to_end and c.per_layer
        assert harness.program_config(c.config) is not None


def test_published_yarn_factor_is_refused():
    c = harness.cell("dsv3-mla3.batch").config
    published = dict(c, rope_scaling=dict(c["rope_scaling"], factor=40))
    with pytest.raises(ValueError, match="YaRN"):
        harness.program_config(published)


# -- FLOP counts -----------------------------------------------------------------

def test_flops_match_hand_counts_for_one_layer():
    dsv3 = harness.load_json(os.path.join(ROOT, "bench/configs/dsv3-mla3.json"))
    d, nh = 7168, 128
    attn = (d * 1536 + 1536 * nh * 192 + d * 512 + d * 64
            + 512 * nh * (128 + 128) + nh * 128 * d)
    ffn = 3 * d * 18432
    assert costs.layer_flops(dsv3, 0, 100) == 2 * (attn + ffn) \
        + 2 * 100 * nh * (192 + 128)
    qwen = dict(hidden_size=2048, num_attention_heads=32,
                num_key_value_heads=4, head_dim=128, num_experts=128,
                num_experts_per_tok=8, moe_intermediate_size=768,
                num_hidden_layers=48, vocab_size=151936)
    attn_q = 2048 * 128 * (32 + 8) + 32 * 128 * 2048
    ffn_q = 3 * 2048 * 768 * 8 + 2048 * 128
    assert costs.layer_flops(qwen, 5, 10) == 2 * (attn_q + ffn_q) \
        + 2 * 10 * 32 * 256
    one = dict(dsv3, num_hidden_layers=1)
    assert costs.prompt_flops(one, 0, 3) == sum(
        costs.layer_flops(one, 0, k) for k in (1, 2, 3))
    assert costs.decode_flops(one, 7) == costs.layer_flops(one, 0, 7) \
        + 2 * d * 129280


# -- trace reduction ---------------------------------------------------------------

E = trace_mod.Event
DEV, DEV1 = "/device:TPU:0", "/device:TPU:1"


def _events():
    ms = 1e6
    return [
        E("/host:CPU", "python", "bench.window", 0, 100 * ms),
        E("/host:CPU", "python", "bench.step", 0, 40 * ms),
        E("/host:CPU", "python", "bench.sleep", 60 * ms, 40 * ms),
        E(DEV, "XLA Modules", "jit_decode_chunk(12)", 10 * ms, 30 * ms),
        E(DEV, "XLA Ops", "fusion.1", 10 * ms, 20 * ms),
        E(DEV, "XLA Ops", "all-to-all.3", 25 * ms, 15 * ms),
        E(DEV, "XLA Ops", "fusion.1 = f32[8] fusion(x)", 45 * ms, 5 * ms),
        E(DEV1, "XLA Modules", "jit_decode_chunk(12)", 10 * ms, 20 * ms),
        E(DEV1, "XLA Ops", "all-to-all.3", 10 * ms, 20 * ms),
        E("/device:TPU:0 SparseCore 0", "XLA Ops", "x", 0, 100 * ms),
    ]


def test_trace_reduction_on_synthetic_events():
    r = trace_mod.reduce(_events(), "bench.window")
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == {DEV: pytest.approx(0.035), DEV1: pytest.approx(0.02)}
    assert r["programs"] == {"decode_chunk": {DEV: pytest.approx(0.03),
                                              DEV1: pytest.approx(0.02)}}
    assert r["program_counts"] == {"decode_chunk": {DEV: 1, DEV1: 1}}
    assert r["device_ops"] == [["all-to-all.3", pytest.approx(0.0175)],
                               ["fusion.1", pytest.approx(0.0125)]]
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench.sleep", pytest.approx(0.05)]
    assert gaps[1] == ["bench.step", pytest.approx(0.01)]


def test_trace_load_reads_host_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace_mod.load(trace_mod.find_xplane(str(tmp_path)))
    names = {e.name for e in trace_mod.host_spans(ev)}
    assert {"bench.window", "bench.step"} <= names
    r = trace_mod.reduce(ev, "bench.window")     # no device planes on CPU
    assert r["devices"] == 0 and r["window_s"] > 0
