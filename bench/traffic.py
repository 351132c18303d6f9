"""Open-loop traffic generator: one general reader of the mix files in
``bench/traffic/<mix>.json``.

A mix is data only. Three kinds exist:

* ``open``: independent requests, Poisson arrivals at ``rate_per_s``.
* ``sessions``: documents, each asked several times (shared prefix),
  interleaved with other documents' asks, Poisson arrivals.
* ``backlog``: every request due at once, before the window. With
  ``"start": "steady"`` its first ``slots`` requests stand for the ones a
  full engine holds in its steady state (``steady_residents``), so slots
  free up and refill from the first tick on.

Every seed gets the same multiset of lengths and of inter-arrival gaps
(quantiles of the stated distributions), in an order the seed permutes, so
two seeds differ in order and token ids and not in the amount of work. A
backlog deals its lengths in blocks of ``block`` quantiles, each block
permuted on its own, so every run serves the same lengths up to the last
block it reaches. Token ids are drawn from the seed. The engine sees only
the requests.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Req:
    """One generated request: due time (seconds after traffic start),
    prompt token ids and the number of new tokens to generate."""
    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    group: int = -1        # document id for session traffic


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named use of the run's seed (any size of
    integer: numpy's seed sequence takes big ints)."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([int(seed), tag])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from the quantiles of a distribution spec, permuted.

    ``lognormal``: median and sigma (of the log), clipped to [min, max].
    ``loguniform``: between min and max. ``uniform``: integers in
    [min, max]."""
    q = _quantiles(n)
    lo, hi = spec["min"], spec["max"]
    kind = spec["dist"]
    if kind == "lognormal":
        from statistics import NormalDist
        z = np.asarray([NormalDist().inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "loguniform":
        v = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        v = lo + q * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    v = np.clip(np.rint(v), lo, hi).astype(np.int64)
    return rng.permutation(v)


def gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """Exponential inter-arrival gaps at ``rate`` per second: the ``n``
    quantiles of the distribution, in the seed's order."""
    return rng.permutation(-np.log(1.0 - _quantiles(n)) / rate)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n)).astype(np.int32)


def count_for(mix: Dict, seconds: float) -> int:
    """Requests an open-loop mix sends over its lead-in, the window and a
    tail of ``tail_s`` seconds past it."""
    span = mix.get("lead_s", 0.0) + seconds + mix.get("tail_s", 0.0)
    return int(math.ceil(mix["rate_per_s"] * span)) + 1


def steady_residents(spec: Dict, n: int, grid: int = 1024):
    """(age, residual) of ``n`` requests in flight in a full engine at its
    steady state, for answers of length ``spec``.

    There a slot holds an answer of length L with odds proportional to L,
    and has served a uniform share of it: every pair (L, age) with
    ``0 <= age < L`` is equally likely, over ``grid`` quantile lengths.
    The ``n`` pairs taken sit at even steps through all pairs ordered by
    residual ``L - age`` (the tokens left, the first included), so slots
    free up at the steady rate from the start. The same for every seed."""
    L = np.sort(lengths(spec, grid, np.random.default_rng(0)))
    long_ = np.repeat(L, L)
    age = np.concatenate([np.arange(x) for x in L])
    left = long_ - age
    order = np.lexsort((long_, left))
    pick = order[((np.arange(n) + 0.5) / n * len(order)).astype(np.int64)]
    return age[pick], left[pick]


def _dealt(spec: Dict, n: int, block: int, rng) -> np.ndarray:
    """``n`` lengths in blocks of ``block`` quantiles, each permuted."""
    out = [lengths(spec, block, rng) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


def _backlog(mix: Dict, seed: int, vocab: int, slots: int, tok_rng):
    n = mix["requests"]
    block = mix.get("block", n)
    p = _dealt(mix["prompt"], n, block, rng_for(seed, "prompt"))
    o = _dealt(mix["output"], n, block, rng_for(seed, "output"))
    out = []
    if mix.get("start") == "steady":
        # residents pair prompts with (age, left) the same way for every
        # seed, which only deals them to the slots in another order
        age, left = steady_residents(mix["output"], slots)
        rp = lengths(mix["prompt"], slots, np.random.default_rng(0))
        turn = rng_for(seed, "residents").permutation(slots)
        out = [Req(i, 0.0, _tokens(tok_rng, rp[j] + age[j], vocab),
                   int(left[j])) for i, j in enumerate(turn)]
    for i in range(len(out), n):
        out.append(Req(i, 0.0, _tokens(tok_rng, p[i], vocab), int(o[i])))
    return out


def generate(mix: Dict, seed: int, seconds: float, vocab: int,
             slots: int = 1) -> List[Req]:
    kind = mix["kind"]
    tok_rng = rng_for(seed, "tokens")
    if kind == "backlog":
        return _backlog(mix, seed, vocab, slots, tok_rng)
    if kind == "open":
        n = count_for(mix, seconds)
        p = lengths(mix["prompt"], n, rng_for(seed, "prompt"))
        o = lengths(mix["output"], n, rng_for(seed, "output"))
        due = np.cumsum(gaps(mix["rate_per_s"], n, rng_for(seed, "gaps")))
        return [Req(i, float(due[i]), _tokens(tok_rng, p[i], vocab),
                    int(o[i])) for i in range(n)]
    if kind == "sessions":
        return _sessions(mix, seed, seconds, vocab, tok_rng)
    raise ValueError(f"unknown traffic kind {kind!r}")


def _sessions(mix, seed, seconds, vocab, tok_rng) -> List[Req]:
    """Documents asked ``asks`` times each. Asks of the documents in play
    are dealt round-robin, so between two asks of one document come the
    asks of the others (``in_play`` documents at once: 1 to 4 others)."""
    n = count_for(mix, seconds)
    s = mix["sessions"]
    n_docs = n // s["asks"]["min"] + 1
    docs = lengths(s["document"], n_docs, rng_for(seed, "document"))
    asks = lengths(s["asks"], n_docs, rng_for(seed, "asks"))
    q = lengths(mix["prompt"], n, rng_for(seed, "prompt"))
    o = lengths(mix["output"], n, rng_for(seed, "output"))
    in_play = lengths(s["in_play"], n_docs, rng_for(seed, "in_play"))
    due = np.cumsum(gaps(mix["rate_per_s"], n, rng_for(seed, "gaps")))
    doc_tokens = [_tokens(tok_rng, d, vocab) for d in docs]
    left = list(asks)
    playing: List[int] = []
    nxt, out, turn = 0, [], 0
    while len(out) < n:
        while nxt < n_docs and len(playing) < in_play[min(nxt, n_docs - 1)]:
            playing.append(nxt)
            nxt += 1
        if not playing:
            break
        d = playing[turn % len(playing)]
        i = len(out)
        prompt = np.concatenate([doc_tokens[d], _tokens(tok_rng, q[i],
                                                        vocab)])
        out.append(Req(i, float(due[i]), prompt, int(o[i]), group=d))
        left[d] -= 1
        if left[d] == 0:
            playing.remove(d)
        else:
            turn += 1
    return out


def longest_context(mix: Dict) -> int:
    """Largest prompt plus output a mix can send (sizes the engine)."""
    prompt = mix["prompt"]["max"]
    if mix["kind"] == "sessions":
        prompt += mix["sessions"]["document"]["max"]
    return prompt + mix["output"]["max"]
