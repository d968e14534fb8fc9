"""The one traffic generator.  A mix is a JSON file under
``bench/traffic/``; this module turns it and a seed into the requests of
one measured window.

Every seed gets the same arrival times and the same multiset of sizes
and think times; token ids are drawn from the seed.  The arrivals are one
path of a Poisson process, bursts and lulls included, that every run
shares: the gaps at the quantiles of the exponential distribution in one
fixed order.  The sizes and think times, drawn at the quantiles
(i + 1/2)/n of each stated distribution, go to the arrivals in an order
the seed permutes (``order``): every run of ``BLOCK`` consecutive
arrivals holds one value from each of ``BLOCK`` bands of the
distribution, so a seed cannot put the longest requests all early or all
late in the window.  So two seeds offer the same work at the same
moments, and the spread between seeds is the system's, not the
sampler's.

A mix holds:

* ``arrival``: ``{"process": "poisson", "rate_per_s": r}``.  The window
  of ``seconds`` holds n = round(r * seconds) arrivals, with gaps at the
  exponential quantiles, in the order ``ARRIVAL_PATH`` and n fix, scaled
  so that n + 1 gaps span the window.
* Either ``prompt_tokens`` and ``output_tokens`` (one request per
  arrival), or ``session``: one session per arrival, holding
  ``document_tokens`` shared by its ``asks`` requests, each ``document +
  question_tokens`` long and answered in ``output_tokens``; ask k + 1 is
  due ``think_s`` after ask k's last token (a closed loop inside the
  session, open across sessions).
* ``greedy_share`` (default 1): the share of requests decoded greedily;
  the rest sample at ``temperature``.

A distribution is ``{"dist": "lognormal", "median": m, "sigma": s}``,
``{"dist": "uniform", "min": a, "max": b}`` or ``{"dist": "const",
"value": v}``, with optional ``min``/``max`` clips; token counts round to
integers.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_STD_NORMAL = NormalDist()
ARRIVAL_PATH = 0xA77     # seeds the one order of the arrival gaps that every run shares
BLOCK = 8                # places per run of ``order``: one rank from each of 8 bands


def quantile(dist: dict, q: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _STD_NORMAL.inv_cdf(q))
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "const":
        x = dist["value"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(x, dist.get("min", -math.inf)), dist.get("max", math.inf))


def order(n: int, rng, block: int = BLOCK) -> np.ndarray:
    """A seeded order of the ranks 0..n-1 in which every run of ``block``
    consecutive places holds one rank from each of ``block`` bands (the
    lowest ranks, the next, ... as ``np.array_split`` cuts them), and the
    last, shorter run one from each of the larger bands.  Which rank of a
    band goes to which run, and the order inside a run, the seed decides."""
    bands = [b[rng.permutation(b.size)] for b in np.array_split(np.arange(n), block)]
    runs = [np.array([b[i] for b in bands if i < b.size]) for i in range(-(-n // block))]
    return np.concatenate([r[rng.permutation(r.size)] for r in runs])


def draws(dist: dict, n: int, rng, integer: bool = True) -> np.ndarray:
    """n values at the distribution's quantiles, in a seeded order."""
    xs = np.array([quantile(dist, (i + 0.5) / n) for i in range(n)])
    if integer:
        xs = np.rint(xs).astype(np.int64)
    return xs[order(n, rng)]


@dataclasses.dataclass
class Ask:
    """One request of the mix: its prompt, its output length and, for
    the first ask of a session, the time it is due in the window.  Later
    asks are due ``think_s`` after the previous one's last token."""
    prompt: np.ndarray
    n_tokens: int
    temperature: float
    due_s: Optional[float] = None
    think_s: float = 0.0
    session: int = 0
    index: int = 0


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rate_per_s: Optional[float] = None) -> List[List[Ask]]:
    """Sessions of the window, each a list of asks in order.
    ``rate_per_s`` overrides the mix's rate (the knee sweep)."""
    rng = np.random.default_rng([seed, 0x7A11])
    rate = rate_per_s or mix["arrival"]["rate_per_s"]
    if mix["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']['process']!r}")
    n = max(1, round(rate * seconds))
    gaps = np.array([-math.log1p(-(i + 0.5) / (n + 1)) for i in range(n + 1)])
    gaps = gaps[np.random.default_rng([n, ARRIVAL_PATH]).permutation(n + 1)]
    due = np.cumsum(gaps)[:n] * seconds / gaps.sum()

    greedy = mix.get("greedy_share", 1.0)
    temp = mix.get("temperature", 1.0)
    sess = mix.get("session")
    if sess is None:
        P = draws(mix["prompt_tokens"], n, rng)
        O = draws(mix["output_tokens"], n, rng)
        g = draws({"dist": "uniform", "min": 0.0, "max": 1.0}, n, rng, False) < greedy
        return [[Ask(prompt=rng.integers(1, vocab, P[i]).astype(np.int32),
                     n_tokens=int(O[i]), temperature=0.0 if g[i] else temp,
                     due_s=float(due[i]), session=i)] for i in range(n)]

    D = draws(sess["document_tokens"], n, rng)
    A = draws(sess["asks"], n, rng)
    total = int(A.sum())
    Q = draws(sess["question_tokens"], total, rng)
    O = draws(mix["output_tokens"], total, rng)
    T = draws(sess["think_s"], total, rng, integer=False)
    g = draws({"dist": "uniform", "min": 0.0, "max": 1.0}, total, rng, False) < greedy
    out, k = [], 0
    for i in range(n):
        doc = rng.integers(1, vocab, D[i]).astype(np.int32)
        asks = []
        for j in range(A[i]):
            q = rng.integers(1, vocab, Q[k]).astype(np.int32)
            asks.append(Ask(prompt=np.concatenate([doc, q]), n_tokens=int(O[k]),
                            temperature=0.0 if g[k] else temp,
                            due_s=float(due[i]) if j == 0 else None,
                            think_s=float(T[k]), session=i, index=j))
            k += 1
        out.append(asks)
    return out

