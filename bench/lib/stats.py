"""Percentiles and the end-to-end metrics of a run.

TPOT of a request is (finish - first token) / (new tokens - 1);
``tpot_p50_ms`` and ``tpot_p90_ms`` are percentiles of it over the counted
requests, ``tpot_mean_ms`` the decode seconds of all of them over all their
tokens after the first.
"""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """q-th percentile (0..100), linear between order statistics."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    r = (v.size - 1) * q / 100.0
    lo, hi = math.floor(r), math.ceil(r)
    return float(v[lo] + (v[hi] - v[lo]) * (r - lo))


def latencies(rec):
    """(TTFT seconds, TPOT seconds) of the counted requests that finished;
    TTFT from the scheduled send to the first streamed token, TPOT as
    (finish - first) / (new tokens - 1)."""
    ttft, tpot = [], []
    for rid in rec.counted:
        if rid not in rec.finish:
            continue
        ttft.append(rec.first[rid] - rec.sent[rid])
        n = rec.n_new[rid]
        if n > 1:
            tpot.append((rec.finish[rid] - rec.first[rid]) / (n - 1))
    return ttft, tpot


def end_to_end(rec, setup_s: float) -> dict:
    _, tpot = latencies(rec)
    done = [rid for rid in rec.counted
            if rid in rec.finish and rec.n_new[rid] > 1]
    decode_s = sum(rec.finish[rid] - rec.first[rid] for rid in done)
    steps = sum(rec.n_new[rid] - 1 for rid in done)
    return {"tpot_p50_ms": 1e3 * percentile(tpot, 50),
            "tpot_p90_ms": 1e3 * percentile(tpot, 90),
            "tpot_mean_ms": 1e3 * decode_s / steps if steps else float("nan"),
            "setup_s": setup_s}
