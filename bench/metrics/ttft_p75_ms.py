"""75th percentile of time to first token over every request due in the
window (failed = infinite): the highest percentile with ten samples beyond
it among the chat cell's 41 requests."""
from bench.stats import finite, pct, ttfts_ms


def read(run):
    return finite(pct(ttfts_ms(run), 75), run.cell.spec["drain_cap_s"] * 1e3)
