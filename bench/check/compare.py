"""Which served requests are compared, and the numbers compared.

After the window has closed and the engine is freed, a sample of the
finished requests, drawn from the seed and always holding the longest one,
is fed through the plain reference: each request's prompt and the tokens
the engine served (first token from the prefill-chunk program, the rest
from the decode bursts).  Each served token has a gap: how far its
reference logit lies below the reference's best logit at its position (0
when it is the reference's argmax).  Greedy serving at the configuration's
precision flips near-ties and leaves small gaps; a wrong token, a stale
cache or a lower precision leaves more and wider ones.  The cell names
which statistics of the gaps are held to which limit (``check`` in its
file).

This is ``chip_smoke.py``'s teacher-forced decode check (prompt + emitted
tokens through a plain path, every token held to the reference's best),
with two changes: the reference is independent of the program (float32,
HIGHEST precision, ``reference.py``), and the limits are set from measured
readings of sound runs and of the float8 control (``PERF.md``) instead of
being derived in the run.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import reference


def sample(served: Dict[int, tuple], seed: int, tokens: int) -> List[int]:
    """Request ids to compare: the longest (prompt + served) first, then
    others in an order drawn from ``seed`` until ``tokens`` served tokens
    are covered.  ``served`` maps rid -> (prompt, served tokens)."""
    if not served:
        return []
    rids = sorted(served)
    longest = max(rids, key=lambda r: (len(served[r][0]) + len(served[r][1]),
                                       r))
    rest = [r for r in rids if r != longest]
    order = np.random.default_rng([int(seed) % (1 << 64), 3]).permutation(
        len(rest))
    out, n = [longest], len(served[longest][1])
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += len(served[rest[i]][1])
    return out


def compare(params, config: dict, served: Dict[int, tuple], rids: List[int],
            control: bool = False, keep: bool = False) -> dict:
    """Score the sampled requests; returns the gap statistics
    (``summary``), each request's widest gap, and with ``control`` the
    control's statistics under ``control_*``.  With ``keep``, also every
    token's gap and control gap per request (``tokens_by_rid``)."""
    spec = reference.Spec.from_config(config)
    worst, gaps, ctrl, kept = {}, [], [], {}
    for rid in rids:
        prompt, toks = served[rid]
        r = reference.score(params, spec, prompt, toks, control=control)
        g = np.where(np.isfinite(r["gap"]), r["gap"], np.inf)
        worst[rid] = float(g.max())
        gaps.append(g)
        if control:
            ctrl.append(np.asarray(r["control_gap"]))
        if keep:
            kept[rid] = {k: np.asarray(v) for k, v in r.items()}
    out = {**summary(gaps), "per_request": worst}
    if control:
        out.update({"control_" + k: v for k, v in summary(ctrl).items()})
    if keep:
        out["tokens_by_rid"] = kept
    return out


def summary(gaps: List[np.ndarray]) -> dict:
    """Statistics of the gaps of the compared requests (one array per
    request): the widest, the mean, the 95th and 99th percentiles over all
    tokens, the median over requests of each request's mean, and the share
    of tokens that are the reference's argmax.  No token scored reads as an
    infinite gap."""
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    if not g.size:
        return {"max_gap": float("inf"), "tokens": 0,
                "argmax_share": float("nan"), "mean_gap": float("inf"),
                "p95_gap": float("inf"), "p99_gap": float("inf"),
                "request_median_gap": float("inf")}
    return {"max_gap": float(g.max()), "tokens": int(g.size),
            "argmax_share": float(np.mean(g == 0)),
            "mean_gap": float(g.mean()),
            "p95_gap": float(np.quantile(g, 0.95)),
            "p99_gap": float(np.quantile(g, 0.99)),
            "request_median_gap": float(np.median(
                [a.mean() for a in gaps if a.size]))}
