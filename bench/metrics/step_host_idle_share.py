"""Scheduler: the share of the traced interval in which the device ran no
operation while the host was inside the engine's ``step()`` (its
``engine.step`` spans, shifted onto the profiler's clock, less the
device-busy intervals).  The rest of the idle time is outside ``step()``:
arrivals awaited, requests handed over."""
from bench import program_spans


def read(run):
    phases = program_spans.idle_by_phase(run)
    if phases is None:
        return None
    return 100.0 * sum(phases.values()) / (run.trace.t1 - run.trace.t0)
