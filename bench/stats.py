"""End-to-end numbers from the wall stamps of one run.

A request's times are taken from when it was due to be sent (its send
time on the lead-in's wall clock).  A request that was never served has
no first token: it misses every limit and counts in every tail as
infinitely late.
"""
from __future__ import annotations

import math

import numpy as np


def pct(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in [0, 100]); inf entries stay inf."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    k = max(int(math.ceil(q / 100.0 * v.size)) - 1, 0)
    return float(v[k])


def ttft(send, stamps) -> np.ndarray:
    """First-token wall time minus send time per request (inf if unserved).

    ``stamps``: per request, the wall times its tokens came back (or
    None)."""
    return np.array([s[0] - t if s else math.inf
                     for t, s in zip(send, stamps)], np.float64)


def gaps(stamps) -> np.ndarray:
    """All gaps between consecutive tokens of all requests."""
    parts = [np.diff(np.asarray(s, np.float64)) for s in stamps
             if s and len(s) > 1]
    return np.concatenate(parts) if parts else np.zeros(0)


def attainment(send, stamps, slo, tbt) -> float:
    """Percent of requests whose first token came by send + SLO and whose
    every gap between tokens stayed within the TBT limit."""
    if len(send) == 0:
        return float("nan")
    ok = 0
    for t, s, b, g in zip(send, stamps, slo, tbt):
        if not s or s[0] - t > b:
            continue
        if len(s) > 1 and np.diff(np.asarray(s)).max() > g:
            continue
        ok += 1
    return 100.0 * ok / len(send)


def tokens_in(stamps, t0: float, t1: float) -> int:
    """Tokens of any request delivered in [t0, t1)."""
    n = 0
    for s in stamps:
        if s:
            a = np.asarray(s)
            n += int(((a >= t0) & (a < t1)).sum())
    return n
