"""75th percentile over requests of (last token time - first token time) /
(tokens - 1): every burst stall and interleaved prefill chunk counts."""
from bench.stats import finite, pct, tpots_ms


def read(run):
    return finite(pct(tpots_ms(run), 75), run.cell.spec["drain_cap_s"] * 1e3)
