"""Arithmetic the metric readers share: percentiles with failures counted as
missing, and per-request latencies from the run's event log."""
from __future__ import annotations

import math
from typing import Iterable, List

INF = float("inf")


def pct(values: Iterable[float], p: float) -> float:
    """Nearest-rank ``p``-th percentile: the smallest value with at least
    ``p`` percent of the sample at or below it.  Infinite entries (failed
    requests) sort last, so they count as missing every limit."""
    v = sorted(values)
    if not v:
        return math.nan
    k = max(1, math.ceil(p / 100.0 * len(v)))
    return v[min(k, len(v)) - 1]


def ttfts_ms(run) -> List[float]:
    """Time from each request's due time to the host holding its first
    token; a request that did not finish counts as infinite."""
    return [(r.first_s - r.due_s) * 1e3 if r.finished else INF
            for r in run.requests]


def tpots_ms(run) -> List[float]:
    """Per request with two or more tokens: (last token time - first token
    time) / (tokens - 1), every burst stall and interleaved prefill chunk
    included; a request that did not finish counts as infinite."""
    out = []
    for r in run.requests:
        if r.max_new < 2:
            continue
        out.append((r.last_s - r.first_s) * 1e3 / (r.n_out - 1)
                   if r.finished else INF)
    return out


def queue_waits_ms(run) -> List[float]:
    """Due time to admission into a slot; never admitted counts infinite."""
    return [(r.admit_s - r.due_s) * 1e3 if r.admit_s is not None else INF
            for r in run.requests]


def finite(x: float, cap: float) -> float:
    """``x``, or ``cap`` (a finite lower bound) where ``x`` is infinite, so
    a result line stays valid JSON when requests failed."""
    return x if math.isfinite(x) else cap
