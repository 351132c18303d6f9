"""Where the time goes inside the engine's programs and its tick, read from
the names the program gives its work in a profiler trace.

The program names its work in three ways, all on the profiler's clock:

* device scopes (``jax.named_scope``): every op of a jitted program
  carries its scope path (``jit(decode_chunk)/while/body/attn/...``) in
  its event metadata (``SCOPE_STAT``). ``SCOPES`` are the program's.
* kernel names: each ``pallas_call`` is named after its registry op, so
  its device events read ``%paged_mla_decode.12`` and the like.
* engine spans (``jax.profiler.TraceAnnotation``): ``engine.step`` around
  a scheduler tick and ``engine.*`` around its phases, the per-request
  ones with ``rid`` metadata.

``load`` reads a trace into ``bench.trace``-compatible events that also
carry the scope path; the reductions below work on those, so they are
tested on synthetic events. ``metrics`` turns them, with the engine's
counters diffed over the window, into per-step, per-tick and share
numbers. Run as a script it serves one traced cell through
``bench.harness.run`` and prints them:

    python3 bench/scopes.py --workload <name> --seed <n> [--seconds 12]

from the root of a checkout, on the chips the cell asks for. Each reading
is None where the program lacks the name it reads (an older program has
no scopes, kernel names, engine spans or counters).
"""
from __future__ import annotations

import collections
import os
import re
import struct
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import trace as trace_mod  # noqa: E402

SCOPES = ("layers", "attn", "ffn", "fp8_quant", "cache_write", "unembed",
          "sample")
ENGINE_SPAN_PREFIX = "engine."
TICK_SPAN = "engine.step"
HARNESS_TICK_SPAN = "bench.step"
# the statistic of a device op's event metadata that holds its scope path
# (its HLO ``op_name``, then ``:``), as a TPU trace writes it
SCOPE_STAT = "tf_op"
_META = re.compile(r"#.*#$")
_SUFFIX = re.compile(r"\.\d+$")
_DEVICE = re.compile(r"^/device:TPU:\d+$")     # as in bench/trace.py


class Event(NamedTuple):
    """``bench.trace.Event`` with the op's scope path (``""`` where the
    trace gives none)."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def span_name(name: str) -> str:
    """A host span's name without TraceMe ``#k=v,...#`` metadata."""
    return _META.sub("", name)


def load(path: str) -> List[Event]:
    """The trace at ``path``: device op events carry their scope path,
    host span names lose their metadata."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    out = device_events(raw)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if _DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, span_name(ev.name),
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


# -- the device planes of an XSpace ---------------------------------------------
# ``ProfileData`` gives an event its own statistics but not its metadata's,
# where a device op's scope path lives, so the device planes are decoded
# from the XSpace protobuf (tsl/profiler/protobuf/xplane.proto) here: only
# the fields named below, the rest skipped by length.

def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, lo: int, hi: int):
    """(field number, value) of the message in ``b[lo:hi]``: an int for a
    varint, ``(start, end)`` of the bytes of a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = b[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")
        yield key >> 3, val


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _stat(b: bytes, span, names: Dict[int, str]):
    """An XStat as (name, value); ref values resolve to their name."""
    mid, val = 0, None
    for num, v in _fields(b, *span):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num in (3, 4):
            val = v
        elif num == 5:
            val = _text(b, v)
        elif num == 7:
            val = names.get(v, "")
    return names.get(mid, ""), val


def _map_entries(b: bytes, spans):
    for span in spans:
        key, val = 0, None
        for num, v in _fields(b, *span):
            if num == 1:
                key = v
            elif num == 2:
                val = v
        yield key, val


def device_events(raw: bytes) -> List[Event]:
    """Every event of the ``/device:TPU:<n>`` planes of a serialized
    XSpace, named and timed as ``ProfileData`` names and times them, with
    the scope path its metadata holds."""
    out = []
    for num, pspan in _fields(raw, 0, len(raw)):
        if num != 1:
            continue
        name, lines, emeta, smeta = "", [], [], []
        for n, v in _fields(raw, *pspan):
            if n == 2:
                name = _text(raw, v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                emeta.append(v)
            elif n == 5:
                smeta.append(v)
        if not _DEVICE.match(name):
            continue
        stat_names = {}
        for key, v in _map_entries(raw, smeta):
            stat_names[key] = next((_text(raw, s) for f, s in
                                    _fields(raw, *v) if f == 2), "")
        meta = {}
        for key, v in _map_entries(raw, emeta):
            mname, display, stats = "", "", {}
            for f, s in _fields(raw, *v):
                if f == 2:
                    mname = _text(raw, s)
                elif f == 4:
                    display = _text(raw, s)
                elif f == 5:
                    k, val = _stat(raw, s, stat_names)
                    stats[k] = val
            scope = stats.get(SCOPE_STAT)
            meta[key] = (mname or display,
                         scope if isinstance(scope, str) else "")
        for lspan in lines:
            lname, ts, evs = "", 0, []
            for f, v in _fields(raw, *lspan):
                if f == 2:
                    lname = _text(raw, v)
                elif f == 3:
                    ts = v
                elif f == 4:
                    evs.append(v)
            for espan in evs:
                mid = off = dur = 0
                for f, v in _fields(raw, *espan):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off = v
                    elif f == 3:
                        dur = v
                ename, scope = meta.get(mid, ("", ""))
                out.append(Event(name, lname, ename,
                                 float(ts + off // 1000),
                                 float(dur // 1000), scope))
    return out


# -- device ops ------------------------------------------------------------------

def _ops(events: Iterable[Event], plane: str) -> List[Event]:
    return [e for e in events if e.plane == plane and e.line == "XLA Ops"]


def leaf_ops(ops: List[Event]) -> List[Event]:
    """Ops that hold no other op: a loop's ``while`` spans its body's ops
    on the same line, so counting it would count the body twice."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.end_ns))
    out = []
    for i, e in enumerate(ops):
        j = i + 1
        while j < len(ops) and ops[j].start_ns < e.end_ns:
            if ops[j].end_ns <= e.end_ns:
                break                    # ops[j] runs inside e
            j += 1
        else:
            out.append(e)
    return out


def _in_program(events: List[Event], plane: str, program: str):
    iv = [(e.start_ns, e.end_ns) for e in events
          if e.plane == plane and e.line == "XLA Modules"
          and trace_mod.program_name(e.name) == program]
    return trace_mod.union(iv)


def scope_seconds(events: List[Event], program: str,
                  scopes: Iterable[str] = SCOPES) -> Dict[str, float]:
    """Device seconds of the leaf ops run inside executions of ``program``
    per scope in ``scopes``, averaged over the devices that ran it. An op
    counts under every scope on its path (``fp8_quant`` sits inside
    ``attn`` or ``ffn``), and under ``""`` where its path holds none of
    them. An op with no path at all (a fusion whose root is a layout
    bitcast, a copy XLA put in for a layout) takes the path of the next op
    on the device that has one, the op it was made for, and also counts
    under ``"inherited"``; ``"total"`` is every leaf op once."""
    scopes = tuple(scopes)
    per: Dict[str, float] = collections.defaultdict(float)
    planes = 0
    for plane in trace_mod.device_planes(events):
        runs = _in_program(events, plane, program)
        if not runs:
            continue
        planes += 1
        mine = [e for e in _ops(events, plane)
                if any(s <= e.start_ns < t for s, t in runs)]
        pending: List[Event] = []

        def add(ops, path, inherited):
            parts = set(path.split("/"))
            hit = [s for s in scopes if s in parts] or [""]
            for e in ops:
                for s in hit:
                    per[s] += e.dur_ns / 1e9
                if inherited:
                    per["inherited"] += e.dur_ns / 1e9
                per["total"] += e.dur_ns / 1e9

        for e in leaf_ops(mine):
            if not e.scope:
                pending.append(e)
                continue
            add(pending, e.scope, True)
            add([e], e.scope, False)
            pending = []
        add(pending, "", False)
    return {k: v / planes for k, v in per.items()} if planes else {}


def kernel_name(event_name: str) -> str:
    """An op's name without ``%``, its HLO text and its ``.N`` suffix."""
    return _SUFFIX.sub("", trace_mod.op_name(event_name).lstrip("%"))


def kernel_seconds(events: List[Event], name: str) -> Optional[float]:
    """Device seconds of the op named ``name`` (a ``pallas_call``'s
    ``name=``), averaged over devices; None where no op has the name."""
    planes = trace_mod.device_planes(events)
    tot, seen = 0.0, False
    for p in planes:
        for e in _ops(events, p):
            if kernel_name(e.name) == name:
                tot += e.dur_ns / 1e9
                seen = True
    return tot / len(planes) if seen else None


# -- engine spans -------------------------------------------------------------------

def engine_spans(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if e.name.startswith(ENGINE_SPAN_PREFIX)
            and not _DEVICE.match(e.plane)]


def idle_stretches(events: List[Event], lo: float, hi: float):
    """The stretches of [lo, hi] ns in which the first device ran no op."""
    planes = trace_mod.device_planes(events)
    if not planes:
        return []
    ops = _ops(events, planes[0]) or [e for e in events
                                      if e.plane == planes[0]]
    busy = trace_mod.clip(trace_mod.union((e.start_ns, e.end_ns)
                                          for e in ops), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def engine_gaps(events: List[Event], lo: float, hi: float) -> Dict:
    """The device's idle time inside ``engine.step`` spans that start in
    [lo, hi] ns, each stretch split at span edges and given to the
    innermost ``engine.*`` span over it (``engine.step`` itself where no
    phase span covers it): ``{"by_span": {name: seconds}, "idle_s",
    "ticks"}``."""
    spans = engine_spans(events)
    ticks = [s for s in spans if s.name == TICK_SPAN
             and lo <= s.start_ns <= hi]
    if not ticks:
        return dict(by_span={}, idle_s=0.0, ticks=0)
    by: Dict[str, float] = collections.defaultdict(float)
    idle = idle_stretches(events, lo, hi)
    for t in ticks:
        inner = [s for s in spans if s.start_ns >= t.start_ns
                 and s.end_ns <= t.end_ns]
        for g0, g1 in trace_mod.clip(idle, t.start_ns, t.end_ns):
            cuts = sorted({g0, g1} | {x for s in inner
                                      for x in (s.start_ns, s.end_ns)
                                      if g0 < x < g1})
            for a, b in zip(cuts, cuts[1:]):
                over = [s for s in inner
                        if s.start_ns <= a and s.end_ns >= b]
                # innermost: the covering span that started last
                best = max(over, key=lambda s: (s.start_ns, -s.end_ns))
                by[best.name] += (b - a) / 1e9
    return dict(by_span=dict(by), idle_s=sum(by.values()), ticks=len(ticks))


def tick_seconds(events: List[Event], lo: float, hi: float,
                 name: str = HARNESS_TICK_SPAN) -> List[float]:
    """Wall seconds of each host span ``name`` that starts in [lo, hi]."""
    return [e.dur_ns / 1e9 for e in events if e.name == name
            and not _DEVICE.match(e.plane) and lo <= e.start_ns <= hi]


# -- the numbers -------------------------------------------------------------------

def _stat_share(stats: Dict, part: str, whole: str, complement: bool):
    if not stats.get(whole):
        return None
    v = stats.get(part, 0) / stats[whole]
    return 100.0 * (1.0 - v if complement else v)


def metrics(events: List[Event], stats: Dict, chunk: int,
            window: str = trace_mod.WINDOW_SPAN) -> Dict[str, Optional[float]]:
    """Per decode step (``decode_chunk`` executions x ``chunk``, the
    denominator of ``decode_step_ms``): device ms under ``attn``, under
    ``fp8_quant`` (which overlaps ``attn`` and ``ffn``), and in the
    ``paged_mla_decode`` kernel; per engine tick: device-idle ms inside
    ``engine.step``; from the engine's counters diffed over the window
    (``stats``): the share of prefill positions that were padding and of
    decode lanes that emitted a token."""
    lo, hi = trace_mod.window_bounds(events, window) or (None, None)
    runs = max(trace_mod.program_counts(events).get("decode_chunk", {})
               .values(), default=0)
    steps = runs * chunk
    sc = scope_seconds(events, "decode_chunk") if steps else {}

    def per_step(v):
        return 1e3 * v / steps if v and steps else None

    kern = kernel_seconds(events, "paged_mla_decode")
    gaps = (engine_gaps(events, lo, hi) if lo is not None
            else dict(ticks=0))
    return {
        "decode_attn_ms": per_step(sc.get("attn")),
        "decode_fp8_quant_ms": per_step(sc.get("fp8_quant")),
        "mla_kernel_ms": per_step(kern),
        "host_gap_ms": (1e3 * gaps["idle_s"] / gaps["ticks"]
                        if gaps["ticks"] else None),
        "prefill_pad_pct": _stat_share(stats, "prefill_tokens",
                                       "prefill_padded_tokens", True),
        "decode_occupancy_pct": _stat_share(stats, "decode_tokens",
                                            "decode_lane_steps", False),
    }


def report(events: List[Event], stats: Dict, chunk: int) -> Dict:
    """``metrics`` with the tables behind them, all in the window: the
    scope split of ``decode_chunk`` and ``prefill`` (seconds), the
    engine-attributed idle time, each engine span's count and wall
    seconds, and the harness's tick wall times."""
    lo, hi = trace_mod.window_bounds(events, trace_mod.WINDOW_SPAN)
    ticks = tick_seconds(events, lo, hi)
    spans: Dict[str, List] = {}
    for e in engine_spans(events):
        if lo <= e.start_ns <= hi:
            n_s = spans.setdefault(e.name, [0, 0.0])
            n_s[0] += 1
            n_s[1] += e.dur_ns / 1e9
    return dict(
        metrics=metrics(events, stats, chunk),
        scopes={p: scope_seconds(events, p)
                for p in ("decode_chunk", "prefill")},
        engine_gaps=engine_gaps(events, lo, hi),
        engine_spans=spans,
        ticks=len(ticks),
        tick_ms_mean=1e3 * sum(ticks) / len(ticks) if ticks else None,
        window_s=(hi - lo) / 1e9)


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import time
    t_start = time.perf_counter()
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    out_dir = os.path.join(harness.ROOT, ".bench_runs",
                           f"scopes.{args.workload}.{args.seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    kept = {}
    record = harness.record

    def keep(*a, **kw):
        kept["rec"] = record(*a, **kw)
        return kept["rec"]

    harness.record = keep
    try:
        result = harness.run(args.workload, args.seed, args.seconds, True,
                             t_start, out_dir)
        events = load(trace_mod.find_xplane(os.path.join(out_dir, "trace")))
    except harness.NoChip as e:
        print(f"scopes: {e}; no result", file=sys.stderr)
        return 2
    finally:
        harness.record = record
    rec = kept["rec"]
    out = report(events, rec["stats"].get("engine", {}), rec["chunk"])
    shutil.rmtree(out_dir, ignore_errors=True)
    for p, tab in out["scopes"].items():
        harness.log(f"scopes {p}: " + " ".join(
            f"{k or '(none)'}={v:.6f}s" for k, v in sorted(tab.items())))
    harness.log(f"engine gaps: {json.dumps(out['engine_gaps'])}")
    out.update(correct=result["correct"], per_layer=result["metrics"],
               device=result["device"], check=result["check"],
               seed=args.seed, workload=args.workload)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
