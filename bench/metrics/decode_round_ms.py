"""Model step: device time of the burst programs in the traced interval
over the decode rounds they ran (per ``step()`` call, the most decode
tokens any one row received from its burst)."""
from collections import defaultdict

from bench import layers


def read(run):
    if layers.inside(run) is None:
        return None
    a, b = run.span
    per_step = defaultdict(int)
    for r in run.requests:
        for t, n, stp, first in r.deliveries:
            if not first and a <= t <= b:
                per_step[stp] = max(per_step[stp], n)
    rounds = sum(per_step.values())
    dev = run.trace.module_seconds("burst")
    if dev <= 0 or rounds <= 0:
        return None
    return 1e3 * dev / rounds
