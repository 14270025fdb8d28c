"""The one traffic generator; each mix is a data file (``traffic/*.json``).

Loops:
  open     requests arrive on a schedule whatever the server does
           (``rate_per_s``, ``arrivals.process`` poisson | gamma with
           ``arrivals.cv``); a ramp of ``ramp_s`` precedes the window.
  closed   ``clients`` callers, each sending its next request when the
           last one finishes; the window opens after ``ramp_s``.
  backlog  ``backlog`` requests queued at t=0; the window opens after
           ``ramp_s``, once the rows are full.

A run's requests come in segments: the ramp, the window and, for the open
loop, the drain, during which arrivals go on (uncounted) so that the
counted requests finish under the load they started in. Every seed gets the
same set of sizes and of inter-arrival gaps in each segment, in another
order: a segment of k requests takes the k quantiles (i + 0.5) / k of each
length distribution, each served at the nearest of the distribution's
levels (``length_levels``), and one fixed draw of the arrival process,
rescaled to the segment's span. So the work of a run does not move with the
seed; the seed orders it and draws the token ids (uniform over the
vocabulary).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

#: the fixed draw that every seed's gaps are taken from
GAPS_SEED = 0


# ---- arrival processes (after the repo's traffic/arrivals.py) -----------
def poisson_arrivals(rate_per_s: float, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    """n arrival times of a Poisson process with the given mean rate."""
    if n <= 0:
        return np.zeros(0)
    return np.cumsum(rng.exponential(1.0 / rate_per_s, n))


def gamma_arrivals(rate_per_s: float, n: int, rng: np.random.Generator,
                   cv: float = 3.0) -> np.ndarray:
    """n arrival times with Gamma inter-arrivals: mean 1/rate, given CV
    (shape 1/cv^2, scale cv^2/rate; cv=1 is exponential)."""
    if n <= 0:
        return np.zeros(0)
    if cv <= 0:
        raise ValueError("cv must be positive")
    return np.cumsum(rng.gamma(1.0 / (cv * cv), (cv * cv) / rate_per_s, n))


def _gaps(arr: dict, rate: float, n: int) -> np.ndarray:
    rng = np.random.default_rng(GAPS_SEED)
    if arr["process"] == "poisson":
        t = poisson_arrivals(rate, n, rng)
    elif arr["process"] == "gamma":
        t = gamma_arrivals(rate, n, rng, arr["cv"])
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    return np.diff(np.concatenate([[0.0], t]))


# ---- lengths ------------------------------------------------------------
def _quantiles(d: dict, u: np.ndarray) -> np.ndarray:
    """Quantiles of the length distribution, clipped to [min, max]."""
    if d["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        v = d["median"] * np.exp(d["sigma"] * z)
    elif d["dist"] == "uniform":
        v = d["min"] + u * (d["max"] - d["min"])
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(v, d["min"], d["max"])


def length_levels(d: dict) -> np.ndarray:
    """The lengths a distribution is served at: the medians of ``levels``
    equal-probability strata, and the clip ends ``min`` and ``max``. A few
    levels keep the number of distinct shapes the server meets (prompt
    lengths, pages per request) small enough to compile them all before the
    window; the ends keep the clipped tails in the traffic."""
    k = d["levels"]
    mids = np.rint(_quantiles(d, (np.arange(k) + 0.5) / k))
    return np.unique(np.concatenate([[d["min"]], mids, [d["max"]]])).astype(
        np.int64)


def segment_lengths(d: dict, k: int) -> np.ndarray:
    """The lengths of a segment of k requests, in ascending order: the k
    quantiles (i + 0.5) / k, each at the nearest level (in log length)."""
    if k <= 0:
        return np.zeros(0, np.int64)
    lv = length_levels(d)
    v = _quantiles(d, (np.arange(k) + 0.5) / k)
    near = np.abs(np.log(v)[:, None] - np.log(lv)[None, :]).argmin(1)
    return lv[near]


@dataclass
class Req:
    rid: int
    prompt: np.ndarray               # int32 token ids
    max_new: int
    arrival_s: Optional[float]       # scheduled (open loop); None = on send
    counted: bool = False            # open loop: arrives inside the window


def segments(traffic: dict, seconds: float):
    """[(start s, span s, requests, counted)] of one run, in send order."""
    loop = traffic["loop"]
    if loop == "open":
        r, ramp = traffic["rate_per_s"], traffic["ramp_s"]
        drain = traffic["drain_cap_s"]
        return [(0.0, ramp, round(r * ramp), False),
                (ramp, seconds, round(r * seconds), True),
                (ramp + seconds, drain, round(r * drain), False)]
    if loop == "closed":
        return [(None, None, traffic["pool"], None)]
    if loop == "backlog":
        return [(None, None, traffic["backlog"], None)]
    raise ValueError(f"unknown loop {loop!r}")


def generate(traffic: dict, seed: int, seconds: float,
             vocab: int) -> List[Req]:
    """The requests of one run, in send order."""
    rng = np.random.default_rng(seed)
    reqs = []
    for start, span, k, counted in segments(traffic, seconds):
        plen = rng.permutation(segment_lengths(traffic["prompt_len"], k))
        mnew = rng.permutation(segment_lengths(traffic["max_new"], k))
        arrival = [None] * k
        if start is not None and k:
            g = rng.permutation(_gaps(traffic["arrivals"],
                                      traffic["rate_per_s"], k))
            c = np.cumsum(g) - g / 2          # midpoints: inside the span
            arrival = [float(t) for t in start + span * c / g.sum()]
        for i in range(k):
            toks = rng.integers(0, vocab, int(plen[i]), dtype=np.int32)
            reqs.append(Req(len(reqs), toks, int(mnew[i]), arrival[i],
                            bool(counted)))
    return reqs


def shapes(traffic: dict):
    """Every (prompt length, max_new) pair any run of this mix can send."""
    return [(int(p), int(m)) for p in length_levels(traffic["prompt_len"])
            for m in length_levels(traffic["max_new"])]
