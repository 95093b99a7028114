"""Scheduler: 75th percentile of the engine's own queue wait (its
``engine.queued`` marks, from the request entering the queue to its
admission into a slot) over the requests enqueued in the traced
interval.  One still queued when the interval closes counts up to the
close, so the profiler's stop after it never enters."""
from bench import program_spans
from bench.program_spans import END, NAME, START
from bench.stats import pct


def read(run):
    recs = program_spans.records(run)
    if recs is None:
        return None
    a, b = (t * 1e9 for t in run.span)
    waits = [min(r[END], b) - r[START] for r in recs
             if r[NAME] == "engine.queued" and a <= r[START] <= b]
    return pct(waits, 75) * 1e-6 if waits else None
