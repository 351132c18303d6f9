"""Window bookkeeping: what each request received and when, and the
end-to-end metrics computed from it.

Every metric here is defined over what the window produced, finished or
not, so none can be missing from a run's result:

* TTFT: over every request due inside the window, from its due time to
  its first token. A request with no first token by the window's end
  enters at ``window end - due`` (a lower bound) and is counted as
  censored.
* TPOT: per request, the time from its first delivery inside the window
  to its last one, over the tokens that came after that first delivery.
  The engine delivers up to a chunk of tokens at once, so a request needs
  two deliveries inside the window to give a reading.
* Output tokens per second: every token delivered inside the window over
  the window's length (a backlog's window holds whole engine ticks).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Track:
    """Host-clock history of one request (times in seconds, one clock)."""
    rid: int
    due: float
    submitted: Optional[float] = None
    deliveries: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)             # (time, tokens in this delivery)

    @property
    def first(self) -> Optional[float]:
        return self.deliveries[0][0] if self.deliveries else None

    def delivered(self) -> int:
        return sum(n for _, n in self.deliveries)


def inside(s: float, w0: float, w1: float) -> bool:
    """A delivery at ``s`` belongs to the window ``(w0, w1]``."""
    return w0 < s <= w1


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in(tracks: List[Track], w0: float, w1: float) -> List[Track]:
    return [t for t in tracks if w0 <= t.due < w1]


def ttft_sample(tracks: List[Track], w0: float, w1: float):
    """(TTFTs in seconds, number censored) over requests due in [w0, w1)."""
    out, censored = [], 0
    for t in due_in(tracks, w0, w1):
        if t.first is not None and t.first <= w1:
            out.append(t.first - t.due)
        else:
            out.append(w1 - t.due)
            censored += 1
    return out, censored


def tpot_sample(tracks: List[Track], w0: float, w1: float) -> List[float]:
    """Seconds per token over every request with two or more deliveries
    inside (w0, w1], finished or not."""
    out = []
    for t in tracks:
        got = [(s, n) for s, n in t.deliveries if inside(s, w0, w1)]
        if len(got) < 2:
            continue
        after = sum(n for _, n in got[1:])
        out.append((got[-1][0] - got[0][0]) / after)
    return out


def tokens_in(tracks: List[Track], w0: float, w1: float) -> int:
    return sum(n for t in tracks for s, n in t.deliveries
               if inside(s, w0, w1))


def end_to_end(tracks: List[Track], w0: float, w1: float) -> Dict[str, float]:
    """Every end-to-end metric the harness knows, in the units of
    ``BENCHMARK.json``. A latency whose sample is empty (no request due
    inside the window) is left out, and the harness then fails the run:
    the traffic mix is wrong for its window."""
    ttft, _ = ttft_sample(tracks, w0, w1)
    out = {"output_tokens_per_s": tokens_in(tracks, w0, w1) / (w1 - w0)}
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
        out["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
    tpot = tpot_sample(tracks, w0, w1)
    if tpot:
        out["tpot_p95_ms"] = percentile(tpot, 95) * 1e3
    return out


def attempted(tracks: List[Track], w0: float, w1: float) -> int:
    """Requests the window owed work to: due inside it, or served inside
    it (a backlog's requests are all due before the window opens)."""
    return sum(1 for t in tracks if w0 <= t.due < w1
               or any(inside(s, w0, w1) for s, _ in t.deliveries))


def counts(tracks: List[Track], w0: float, w1: float) -> Dict[str, int]:
    """The sample sizes behind ``end_to_end``, printed on an earlier line."""
    ttft, censored = ttft_sample(tracks, w0, w1)
    return dict(due=len(ttft), ttft_censored=censored,
                tpot_requests=len(tpot_sample(tracks, w0, w1)),
                tokens=tokens_in(tracks, w0, w1),
                attempted=attempted(tracks, w0, w1))
