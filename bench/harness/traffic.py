"""The one generator every traffic mix goes through.

A mix is a JSON file of parameters under ``bench/traffic/``:

``loop``     ``"closed"`` (``clients`` callers, each sending its next
             request when the last one ends) or ``"open"`` (arrivals at
             ``rate`` per second, sent when due whatever the server does).
``prompt`` / ``answer``  length distributions: ``{"dist": "uniform", "min",
             "max"}`` or ``{"dist": "lognormal", "median", "sigma", "min",
             "max"}``.
``requests`` how many requests the schedule holds (more than a window
             can use).

Every seed gets the same multiset of prompt lengths, answer lengths and
inter-arrival gaps, taken at evenly spaced quantiles of the distributions;
the seed only shuffles their order and draws the token ids.  So runs with
different seeds do the same amount of work in another order.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 1/2) / n`` of ``spec``."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        lo, hi = spec["min"], spec["max"]
        x = lo + q * (hi - lo + 1)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate``/s, at the
    quantiles ``(i + 1/2) / n`` (their mean is ``1/rate`` to within 1/n)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def schedule(mix: dict, seed: int, vocab: int) -> List[Dict]:
    """The requests of one run: ``id``, ``prompt`` (token ids), ``gen``,
    and ``client`` (closed loop: which caller sends it, in order) or
    ``due`` (open loop: seconds after the window opens)."""
    n = int(mix["requests"])
    rng = np.random.default_rng(seed)
    plens = rng.permutation(quantile_lengths(mix["prompt"], n))
    glens = rng.permutation(quantile_lengths(mix["answer"], n))
    reqs = []
    for i in range(n):
        reqs.append({"id": i, "gen": int(glens[i]),
                     "prompt": rng.integers(0, vocab, int(plens[i]))
                     .astype(int).tolist()})
    if mix["loop"] == "closed":
        c = int(mix["clients"])
        for i, r in enumerate(reqs):
            r["client"] = i % c
    elif mix["loop"] == "open":
        due = np.cumsum(rng.permutation(
            exponential_gaps(float(mix["rate"]), n)))
        for r, t in zip(reqs, due):
            r["due"] = float(t)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return reqs


def mean_rate(reqs: List[Dict]) -> float:
    """Arrivals per second of an open-loop schedule."""
    dues = [r["due"] for r in reqs]
    return (len(dues) - 1) / (dues[-1] - dues[0]) if len(dues) > 1 else math.nan
