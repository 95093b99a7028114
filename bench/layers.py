"""What the per-layer metrics share: the served work inside a traced run's
profiled interval, reconstructed from the event log.

The interval ``run.span`` opens at a step boundary halfway through the
window, once the queue has had time to build, and closes at a later step
boundary at which no prompt is half prefilled.  A request admitted inside
it had its whole prompt prefilled inside it, and a decode token delivered
inside it was computed inside it (the host receives a burst's tokens when
the burst has ended).  Prefill chunks of a prompt admitted before the
interval ran partly outside it; their device time counts and their work
does not, so a share of a peak reads low there, never high.
"""
from __future__ import annotations


def inside(run):
    """The traced interval, or None for an untraced run."""
    return run.span if run.trace is not None else None


def prefilled(run):
    """Requests admitted and prefilled inside the traced interval."""
    a, b = run.span
    return [r for r in run.requests
            if r.admit_s is not None and r.first_s is not None
            and a <= r.admit_s and r.first_s <= b]
