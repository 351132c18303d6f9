"""The benchmark's driver: set-up, the measured window, the traced window,
the correctness check and the result line. ``bench/run.py`` is its
command line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file found by name:

* ``bench/configs/<config>.json``: the published keys, what was cut, the
  program's arch and overrides, the engine settings, the reference
  module and the check's limit.
* ``bench/traffic/<mix>.json``: the load (``bench/traffic.py`` reads it);
  the engine's context is sized from its longest request.
* ``bench/metrics/<name>.py``: ``read(rec)`` returns the metric from the
  run's record, or None where the run has nothing to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import traffic as traffic_mod
from bench import window as win

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS = 10.0          # length of the traced part of a --trace 1 run
WINDOW_SPAN = "bench.window"   # bench/trace.py's WINDOW_SPAN


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- the files ---------------------------------------------------------------

def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(items: List[Dict], name: str, what: str) -> Dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict           # bench/configs/<config>.json
    mix: Dict              # bench/traffic/<traffic>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and the metrics it
    reports, all found by the names in ``BENCHMARK.json``."""
    b = benchmark(root)
    w = find(b["workloads"], name, "workload")
    cfg_entry = find(b["configs"], w["config"], "config")
    config = load_json(os.path.join(root, cfg_entry["file"]))
    mix = load_json(os.path.join(root, "bench", "traffic",
                                 w["traffic"] + ".json"))

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name, w["chips"], config, mix,
                [m for m in b["end_to_end"] if mine(m)],
                [m for m in b["per_layer"] if mine(m)])


def metric_reader(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(config: Dict):
    return importlib.import_module("bench.reference." + config["reference"])


def peak_flops(kind: str) -> float:
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return float(peaks[kind]["bf16_flops"])


# -- the device -------------------------------------------------------------

def require_chips(n: int):
    """The TPU devices of this process, or NoChip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX has {len(devs)}")
    return devs[:n]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at a fixed directory inside the checkout."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# -- the program under test ---------------------------------------------------

def program_config(config: Dict):
    """The program's ModelConfig for a configuration file, checked against
    the published keys the file states."""
    from repro.configs.base import get_config
    prog = config["program"]
    base = get_config(prog["arch"])
    over = {}
    for k, v in prog.get("overrides", {}).items():
        sub = getattr(base, k)
        over[k] = (dataclasses.replace(sub, **v)
                   if isinstance(v, dict) and sub is not None else v)
    cfg = get_config(prog["arch"], **over)
    for path, want in reference(config).program_view(config).items():
        got = cfg
        for part in path.split("."):
            got = getattr(got, part)
        if got != want:
            raise ValueError(f"program config {path} = {got!r}, but the "
                             f"configuration file states {want!r}")
    check_quantization(config, cfg)
    return cfg


def check_quantization(config: Dict, cfg):
    """A published ``quantization_config`` (fp8 e4m3 GEMMs in weight
    blocks) holds the program to fp8 GEMMs in blocks of the same size."""
    q = config.get("quantization_config")
    if not q:
        return
    import jax.numpy as jnp
    from repro.core import fp8
    got = dict(fp8=cfg.fp8, fmt=jnp.dtype(fp8.E4M3).name,
               weight_block_size=[fp8.BLOCK, fp8.BLOCK])
    want = dict(fp8=q["quant_method"] == "fp8",
                fmt={"e4m3": "float8_e4m3fn"}[q["fmt"]],
                weight_block_size=list(q["weight_block_size"]))
    if got != want:
        raise ValueError(f"program GEMMs {got} differ from the published "
                         f"quantization_config {want}")


def engine_settings(config: Dict, mix: Dict) -> Dict:
    """Engine keyword arguments: the configuration's, with ``max_len``
    the mix's longest request rounded up to whole prefill chunks or
    pages."""
    kw = dict(config["engine"])
    need = traffic_mod.longest_context(mix)
    grain = kw.get("prefill_chunk") or kw.get("page_size", 8)
    kw["max_len"] = int(math.ceil(need / grain) * grain)
    return kw


def draw_params(cfg, config: Dict, seed: int):
    """The weights, drawn on the device in one jit from the seed by the
    reference's rule, in the dtypes the program serves."""
    import jax
    from repro.models.api import build_model
    structs = build_model(cfg).param_structs()
    key = jax.random.PRNGKey(int(traffic_mod.rng_for(seed, "weights")
                                 .integers(0, 2**31 - 1)))
    ref = reference(config)
    params = jax.jit(lambda k: ref.draw(structs, k))(key)
    return jax.block_until_ready(params)


def build_engine(cfg, params, kw: Dict):
    from repro.serve.engine import ServeEngine
    return ServeEngine(cfg, params=params, **kw)


def warm_up(eng, reqs: List[traffic_mod.Req], vocab: int, seed: int):
    """Compile every program the cell's traffic can reach: one request
    per prefill bucket its prompts fall into (bucketed engines), or one
    that takes two prefill chunks (chunked engines), each decoding past
    one fused chunk, so prefill, admission, decode and release all run.
    Their token ids come from a stream of their own, so no warm-up page
    can serve a measured prompt."""
    from repro.serve.engine import Request, bucket_length
    rng = traffic_mod.rng_for(seed, "warm-up")
    if eng.prefill_chunk:
        lengths = [eng.prefill_chunk + 1]
    else:
        lengths = sorted({bucket_length(len(r.prompt), eng.max_len)
                          for r in reqs})
    new = eng.chunk + 2
    for i, n in enumerate(lengths):
        n = min(n, eng.max_len - new)
        eng.submit(Request(-1 - i, rng.integers(0, vocab, size=n)
                           .astype(np.int32), max_new=new))
    eng.run_until_done(max_steps=10_000)


# -- the window ---------------------------------------------------------------

@dataclasses.dataclass
class Window:
    tracks: List[win.Track]
    reqs: Dict[int, Any]              # rid -> engine Request
    w0: float
    w1: float
    step_s: List[tuple]               # (start, end) of every step()
    traced: Optional[tuple] = None    # (start, end) of the traced window
    stats: Dict = dataclasses.field(default_factory=dict)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _diff(after: Dict, before: Dict) -> Dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))}


def run_window(eng, reqs: List[traffic_mod.Req], mix: Dict, seconds: float,
               trace_dir: Optional[str], clock=time.perf_counter,
               sleep=time.sleep) -> Window:
    """Drive ``eng`` open loop: submit each request at its due time, step
    while there is work, sleep to the next due time otherwise. An open
    mix's window opens ``lead_s`` after traffic starts and closes
    ``seconds`` later. A backlog is submitted at once; its window opens
    after the tick that has served the first ``slots`` requests (the
    fill) and closes at the end of the first tick that ends ``seconds`` or
    more after that, so it holds whole ticks: their tokens and all their
    time. A traced run records the first ``TRACE_SECONDS`` of the
    window."""
    import jax
    from repro.serve.engine import Request
    t0 = clock()
    tracks = [win.Track(r.rid, t0 + r.due) for r in reqs]
    by_rid = {}
    live: Dict[int, tuple] = {}
    backlog = mix["kind"] == "backlog"
    w0 = None if backlog else t0 + mix.get("lead_s", 0.0)
    w1 = None if backlog else w0 + seconds
    fill = reqs[:eng.slots]
    nxt, steps = 0, []
    tracing, traced, ann = False, None, None
    snap: Dict[str, Dict] = {}

    def snapshot(tag):
        snap[tag] = dict(stats=dict(eng.stats),
                         prefix=dict(eng.prefix_stats()),
                         traces=dict(eng.trace_counts))

    while True:
        now = clock()
        if w0 is not None and now >= w0 and "w0" not in snap:
            snapshot("w0")
            if trace_dir is not None:
                jax.profiler.start_trace(trace_dir)
                tracing = True
                ann = span(WINDOW_SPAN)
                ann.__enter__()
                traced = [clock(), None]
        if tracing and (now >= traced[0] + TRACE_SECONDS
                        or (w1 is not None and now >= w1)):
            ann.__exit__(None, None, None)
            traced[1] = clock()
            jax.profiler.stop_trace()
            tracing = False
        if w1 is not None and now >= w1:
            break
        with span("bench.submit"):
            while nxt < len(reqs) and tracks[nxt].due <= now:
                r = reqs[nxt]
                q = Request(r.rid, r.prompt, max_new=r.max_new)
                eng.submit(q)
                tracks[nxt].submitted = now
                by_rid[r.rid] = q
                live[r.rid] = (q, tracks[nxt])
                nxt += 1
        if eng.has_work():
            with span("bench.step"):
                s = clock()
                eng.step()
                e = clock()
            steps.append((s, e))
            with span("bench.harvest"):
                for rid in list(live):
                    q, t = live[rid]
                    got = len(q.out) - t.delivered()
                    if got > 0:
                        t.deliveries.append((e, got))
                    if q.done:
                        del live[rid]
            if backlog:
                if w0 is None and all(by_rid[r.rid].out for r in fill):
                    w0 = clock()
                elif w0 is not None and e >= w0 + seconds:
                    w1 = e
        elif backlog:
            raise RuntimeError("the backlog drained before its window "
                               "closed: the mix is too small for its "
                               "slots and seconds")
        else:
            with span("bench.sleep"):
                due = tracks[nxt].due if nxt < len(reqs) else w1
                sleep(max(0.0, min(due, w1) - clock()))
    snapshot("w1")
    out = Window(tracks, by_rid, w0, w1, steps,
                 tuple(traced) if traced else None)
    out.stats = dict(
        engine=_diff(snap["w1"]["stats"], snap["w0"]["stats"]),
        prefix=_diff(snap["w1"]["prefix"], snap["w0"]["prefix"]),
        retraces=_diff(snap["w1"]["traces"], snap["w0"]["traces"]))
    return out


# -- the record the per-layer readers take ------------------------------------

def traced_flops(hf: Dict, w: Window, prompts: Dict[int, int]) -> float:
    """Model FLOPs of the work delivered inside the traced window: every
    token decoded, at its own context, and the whole prompt of every
    request whose first token came inside it."""
    from bench import costs
    lo, hi = w.traced
    total = 0.0
    for t in w.tracks:
        seen = 0
        for s, n in t.deliveries:
            if lo <= s <= hi:
                L = prompts[t.rid]
                for j in range(seen, seen + n):
                    if j == 0:
                        total += costs.prompt_flops(hf, 0, L) + \
                            costs.unembed_flops(hf)
                    else:
                        total += costs.decode_flops(hf, L + j)
            seen += n
    return total


def record(cell_: Cell, eng, w: Window, trace: Optional[Dict], chips: int,
           kind: str) -> Dict:
    hf = cell_.config
    prompts = {rid: len(q.prompt) for rid, q in w.reqs.items()}
    rec = dict(hf=hf, tracks=w.tracks, w0=w.w0, w1=w.w1, trace=trace,
               stats=w.stats, chips=chips, prompts=prompts,
               chunk=eng.chunk)
    if w.traced and trace is not None:
        lo, hi = w.traced
        rec["busy_wall_s"] = sum(min(e, hi) - max(s, lo)
                                 for s, e in w.step_s if e > lo and s < hi)
        rec["flops"] = traced_flops(hf, w, prompts)
        rec["peak_flops"] = peak_flops(kind)
        rec["traced_prompt_tokens"] = sum(
            prompts[t.rid] for t in w.tracks
            if t.first is not None and lo <= t.first <= hi)
    return rec


# -- the whole run -------------------------------------------------------------

def log(*parts):
    print("[bench]", *parts, file=sys.stderr, flush=True)


def run(name: str, seed: int, seconds: float, trace: bool,
        t_start: float, out_dir: str, root: str = ROOT) -> Dict:
    cell_ = cell(name, root)
    devices = require_chips(cell_.chips)
    cache_dir = enable_compile_cache(root)
    from bench import check as check_mod
    kind = devices[0].device_kind
    peak_flops(kind)                        # unknown devices fail early
    config, mix = cell_.config, cell_.mix
    cfg = program_config(config)
    kw = engine_settings(config, mix)
    params = draw_params(cfg, config, seed)
    t_params = time.perf_counter()
    eng = build_engine(cfg, params, kw)
    reqs = traffic_mod.generate(mix, seed, seconds, cfg.vocab_size,
                                eng.slots)
    warm_up(eng, reqs, cfg.vocab_size, seed)
    t_warm = time.perf_counter()
    log(f"cell={name} seed={seed} chips={cell_.chips} kind={kind!r} "
        f"cache={cache_dir} params_s={t_params - t_start:.3f} "
        f"warm_s={t_warm - t_params:.3f} requests={len(reqs)} "
        f"engine={json.dumps(kw, sort_keys=True)}")
    trace_dir = os.path.join(out_dir, "trace") if trace else None
    w = run_window(eng, reqs, mix, seconds, trace_dir)
    setup_s = w.w0 - t_start
    counts = win.counts(w.tracks, w.w0, w.w1)
    late = [t.submitted - t.due for t in w.tracks
            if t.submitted is not None]
    log(f"setup_s={setup_s:.3f} window_s={w.w1 - w.w0:.3f} "
        f"steps={len(w.step_s)} "
        f"counts={json.dumps(counts)} retraces={json.dumps(w.stats['retraces'])} "
        f"generator_late_ms_p50={np.percentile(late, 50) * 1e3:.3f} "
        f"generator_late_ms_max={max(late) * 1e3:.3f}")
    peak = memory_peak(devices)
    device = dict(platform=devices[0].platform, kind=kind,
                  count=len(devices), memory_peak_bytes=peak)
    reduced = None
    if trace:
        from bench import trace as trace_mod
        events = trace_mod.load(trace_mod.find_xplane(trace_dir))
        lines: Dict[str, int] = {}
        for e in events:
            k = f"{e.plane}|{e.line}"
            lines[k] = lines.get(k, 0) + 1
        log(f"trace events={len(events)} lines={json.dumps(lines)}")
        reduced = trace_mod.reduce(events, WINDOW_SPAN)
        busy = reduced["busy_s"]
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = reduced["window_s"]
        log(f"trace programs={json.dumps(reduced['programs'])} "
            f"counts={json.dumps(reduced['program_counts'])}")
    rec = record(cell_, eng, w, reduced, cell_.chips, kind)
    if trace:
        metrics = {}
        for m in cell_.per_layer:
            v = metric_reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
    else:
        e2e = win.end_to_end(w.tracks, w.w0, w.w1)
        e2e["setup_s"] = setup_s
        metrics = {}
        for m in cell_.end_to_end:
            if m["name"] not in e2e:
                raise RuntimeError(f"no reading of {m['name']} in this "
                                   f"window: {json.dumps(counts)}")
            metrics[m["name"]] = dict(value=float(e2e[m["name"]]),
                                      unit=m["unit"])
    # the check runs once the window's numbers and the memory peak are
    # read; the program's cache goes first so the reference fits
    served = check_mod.served(w)
    del eng, w
    gc.collect()
    check = check_mod.run_check(config, params, seed, served)
    result = dict(correct=check["correct"], attempted=counts["attempted"],
                  failed=0, metrics=metrics, device=device)
    if reduced is not None:
        result["breakdown"] = dict(device_ops=reduced["device_ops"],
                                   idle_gaps=reduced["idle_gaps"])
    result["check"] = check["numbers"]
    return result

