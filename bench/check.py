"""The check that decides ``correct``: served tokens against the float32
reference.

Once the window has closed, a sample of the requests that received tokens
inside it (finished or not) is drawn from the seed, always with the one
that received the most. The reference runs once over each prompt with its
served tokens, and the number compared is the widest gap by which a
served token's logit lies below the reference's best logit at that
position. Greedy decoding from the exact model gives 0; rounding in the
program flips near-ties and gives small gaps; a wrong token gives a gap
of the order of the logits' spread.

The control (``control_gaps``) is the reference computed one precision
step below the program, at the same positions: the gap of the token that
it puts first. ``run_check`` with ``control=True`` puts it in the
program's place, through the same comparison and limit.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import traffic as traffic_mod
from bench import window as win


def served(w) -> List[Dict]:
    """Every request that received a token inside the window, with its
    prompt and every token it was served."""
    out = []
    for t in w.tracks:
        if any(win.inside(s, w.w0, w.w1) for s, _ in t.deliveries):
            q = w.reqs[t.rid]
            out.append(dict(rid=t.rid, prompt=np.asarray(q.prompt, np.int32),
                            tokens=list(map(int, q.out))))
    return out


def sample(cands: List[Dict], seed: int, n: int) -> List[Dict]:
    if not cands:
        return []
    order = sorted(cands, key=lambda c: (-len(c["tokens"]), c["rid"]))
    rest = order[1:]
    rng = traffic_mod.rng_for(seed, "check")
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [order[0]] + [rest[i] for i in sorted(pick)]


def _positions(c: Dict):
    """(padded input ids, positions whose logits predict the served
    tokens, the served tokens)."""
    from bench.reference import common as C
    prompt, toks = c["prompt"], np.asarray(c["tokens"], np.int32)
    seq = np.concatenate([prompt, toks[:-1]])
    ids = np.zeros((C.pad_to(len(seq)),), np.int32)
    ids[:len(seq)] = seq
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    return ids, pos, toks


def gaps(ref, params, hf: Dict, c: Dict) -> np.ndarray:
    """Per served token: reference best logit minus the served token's."""
    import jax.numpy as jnp
    from bench.reference import common as C
    ids, pos, toks = _positions(c)
    h = ref.hidden(params, hf, jnp.asarray(ids))[pos]
    best, _, at = C.logit_stats(h, params["embed"]["unemb"],
                                jnp.asarray(toks))
    return np.asarray(best - at)


def control_gaps(ref, params, hf: Dict, c: Dict, fmt: str) -> np.ndarray:
    """Per served position: reference best logit minus the reference's
    logit of the token the control (operands in ``fmt``) puts first."""
    import jax.numpy as jnp
    from bench.reference import common as C
    ids, pos, toks = _positions(c)
    unemb = params["embed"]["unemb"]
    h = ref.hidden(params, hf, jnp.asarray(ids))[pos]
    hc = ref.hidden(params, hf, jnp.asarray(ids), fmt)[pos]
    best, _, _ = C.logit_stats(h, unemb, jnp.asarray(toks))
    _, arg, _ = C.logit_stats(hc, unemb, jnp.asarray(toks), fmt)
    _, _, at = C.logit_stats(h, unemb, arg)
    return np.asarray(best - at)


def run_check(config: Dict, params, seed: int, cands: List[Dict],
              limit: Optional[float] = None, control: bool = False) -> Dict:
    """``correct`` and the numbers compared, each beside its limit. With
    ``control``, the tokens compared are the control's, teacher-forced
    on the same served positions."""
    import importlib
    spec = config["check"]
    ref = importlib.import_module("bench.reference." + config["reference"])
    chosen = sample(cands, seed, spec["requests"])
    widest, n = 0.0, 0
    for c in chosen:
        g = (control_gaps(ref, params, config, c, config["control"])
             if control else gaps(ref, params, config, c))
        widest = max(widest, float(g.max()))
        n += len(g)
    lim = spec["max_logit_gap"] if limit is None else limit
    numbers = dict(max_logit_gap=dict(value=widest, limit=lim),
                   checked_tokens=dict(value=n, limit=spec["min_tokens"]))
    # a configuration whose limit is not set yet is never correct
    correct = (lim is not None and widest <= lim
               and n >= spec["min_tokens"])
    return dict(correct=bool(correct), numbers=numbers, chosen=chosen)
