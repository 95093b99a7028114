"""Scheduler: 75th percentile of due time to admission into a slot (the
engine's ``admit`` record), over the requests due in the traced interval."""
from bench import layers
from bench.stats import finite, pct, queue_waits_ms


def read(run):
    if layers.inside(run) is None:
        return None
    a, b = run.span
    waits = [w for w, r in zip(queue_waits_ms(run), run.requests)
             if a <= r.due_s <= b]
    return finite(pct(waits, 75), run.cell.spec["drain_cap_s"] * 1e3) \
        if waits else None
