"""The one traffic generator: reads a mix's parameters and a cell's load and
turns them into the requests of a run.

A mix file (``bench/traffic/<mix>.json``) gives the prompt and output
length distributions, the arrival process and ``schedule_seed``.  The cell
file gives the load: ``rate_rps`` of an open loop.

Every seed gets the same schedule: lengths and gaps are stratified
quantiles of their distributions (quantile ``(i + 0.5) / n`` for
``i < n``), put in an order drawn once from the mix's ``schedule_seed``.
The run's ``--seed`` draws what the requests say: their token ids, uniform
over the vocabulary (and the weights, ``weights.py``).  So two seeds serve
different contents on the same timeline of sizes and arrivals.  An order
drawn per seed would move which request is long and when; with some forty
requests in a window, that alone spreads the latency tails by more than
half from seed to seed (``PERF.md``).
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    rid: int
    due_s: float            # seconds after the window opens
    prompt_len: int
    max_new: int


def stratified_lognormal(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal with the given median and
    sigma, rounded and clipped to ``[min, max]``."""
    from scipy.stats import norm
    q = (np.arange(n) + 0.5) / n
    v = spec["median"] * np.exp(spec["sigma"] * norm.ppf(q))
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def stratified_gaps(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the inter-arrival distribution, with
    mean 1.  ``gamma`` with coefficient of variation ``cv`` (shape 1/cv^2;
    cv 1 is Poisson arrivals)."""
    from scipy.stats import gamma
    if spec["dist"] != "gamma":
        raise ValueError(f"unknown arrival distribution {spec['dist']!r}")
    shape = 1.0 / spec["cv"] ** 2
    q = (np.arange(n) + 0.5) / n
    g = gamma.ppf(q, a=shape)
    return g / g.mean()


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def plan(mix: dict, cell: dict, seconds: float) -> List[Planned]:
    """The requests of one run, the same for every seed:
    ``round(rate * seconds)`` requests whose due times span the window."""
    if mix["arrivals"]["loop"] != "open":
        raise ValueError(f"unknown loop {mix['arrivals']['loop']!r}")
    r = _rng(mix["schedule_seed"], 1)
    n = max(1, int(round(cell["rate_rps"] * seconds)))
    pl = stratified_lognormal(mix["prompt"], n)
    nl = stratified_lognormal(mix["output"], n)
    gaps = stratified_gaps(mix["arrivals"], n)
    pl, nl, gaps = r.permutation(pl), r.permutation(nl), r.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds
                                                           / gaps.sum())
    return [Planned(i, float(due[i]), int(pl[i]), int(nl[i]))
            for i in range(n)]


def prompt_tokens(seed: int, reqs: List[Planned], vocab: int) -> dict:
    """Token ids of every planned prompt, uniform over ``[0, vocab)``."""
    r = _rng(seed, 2)
    return {q.rid: r.integers(0, vocab, size=q.prompt_len,
                              dtype=np.int32).tolist() for q in reqs}


def bounds(mix: dict) -> tuple:
    """(longest prompt, longest output) the mix can produce."""
    return int(mix["prompt"]["max"]), int(mix["output"]["max"])
