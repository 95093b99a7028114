"""What the readers of the program's own spans share.

The engine records a span around every ``step()`` and each of its phases,
and marks each request's stay in its queue (``src/repro/launch/spans.py``;
``finalize()`` returns them as ``stats["spans"]``, records
``(id, name, start_ns, end_ns, parent, attrs)`` on ``perf_counter_ns``).
The profiler's clock differs from the host's by a constant: the span
``bench.window`` starts at ``run.trace.t0`` on the profiler's clock and at
``run.span[0]`` seconds on the host's, so a host time plus ``shift_ns``
is a time on the device trace.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from itertools import accumulate
from typing import Dict, List, Optional

ID, NAME, START, END, PARENT = range(5)
STEP = "engine.step"


def records(run) -> Optional[list]:
    """The engine's records of a traced run, or None where there are none
    (an untraced run, a program without spans) or where the ring no longer
    reaches back to the traced interval's start."""
    if run.trace is None or run.span is None:
        return None
    recs = run.stats.get("spans")
    if not recs:
        return None
    if run.stats.get("spans_dropped", 0) and recs[0][END] > run.span[0] * 1e9:
        return None
    return recs


def shift_ns(run) -> float:
    """Profiler time minus host time."""
    return run.trace.t0 - run.span[0] * 1e9


class _Busy:
    """Device-busy intervals (sorted, disjoint) of one device, for the
    busy time inside any interval."""

    def __init__(self, intervals):
        self.a = [a for a, _ in intervals]
        self.b = [b for _, b in intervals]
        self.cum = [0] + list(accumulate(b - a for a, b in intervals))

    def _upto(self, x: float) -> float:
        i = bisect.bisect_right(self.a, x)
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(self.b[i - 1], x) - self.a[i - 1]

    def idle(self, x: float, y: float) -> float:
        return (y - x) - (self._upto(y) - self._upto(x)) if y > x else 0.0


def _attribute(rec, kids, busy: _Busy, sh: float, lo: float, hi: float,
               out: Dict[str, float]) -> None:
    """Device-idle time inside ``rec`` (clipped to ``[lo, hi]``), each
    piece to the innermost span around it: the part no child covers is
    ``rec``'s own."""
    a, b = max(rec[START] + sh, lo), min(rec[END] + sh, hi)
    if b <= a:
        return
    cur = a
    for c in kids.get(rec[ID], ()):
        ca, cb = max(c[START] + sh, cur), min(c[END] + sh, b)
        if cb <= ca:
            continue
        out[rec[NAME]] += busy.idle(cur, ca)
        _attribute(c, kids, busy, sh, ca, cb, out)
        cur = cb
    out[rec[NAME]] += busy.idle(cur, b)


def idle_by_phase(run) -> Optional[Dict[str, float]]:
    """Nanoseconds in the traced interval in which the device ran no
    operation while the host was inside ``engine.step``, by the innermost
    ``engine.*`` span around them (a step's own time outside its phases is
    ``engine.step``'s); averaged over the devices traced."""
    recs = records(run)
    if recs is None:
        return None
    kids: Dict[int, List] = defaultdict(list)
    steps = []
    for r in recs:
        if r[NAME] == STEP:
            steps.append(r)
        elif r[PARENT] is not None:
            kids[r[PARENT]].append(r)
    for v in kids.values():
        v.sort(key=lambda r: r[START])
    if not steps:
        return None
    sh, tv = shift_ns(run), run.trace
    out: Dict[str, float] = defaultdict(float)
    for dev in tv.devices:
        busy = _Busy(tv.busy[dev])
        for s in steps:
            _attribute(s, kids, busy, sh, tv.t0, tv.t1, out)
    return {k: v / len(tv.devices) for k, v in out.items()}
