"""Median time from a request's due time to the host holding its first
token, over every request due in the window (failed = infinite)."""
from bench.stats import finite, pct, ttfts_ms


def read(run):
    return finite(pct(ttfts_ms(run), 50), run.cell.spec["drain_cap_s"] * 1e3)
