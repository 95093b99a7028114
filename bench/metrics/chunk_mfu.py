"""Model step: the FLOPs of the prefill chunk waves the engine ran inside
the traced interval, over the prefill-chunk programs' device time in the
interval at the chip's bf16 peak.  A wave's FLOPs are its ``engine.prefill``
span's ``tokens`` x the linear FLOPs of a token (projections and FFN of
every layer) plus its ``pairs`` (the (query, key) pairs its rows attend) x
the FLOPs of a pair.  A wave whose span straddles either edge of the
interval counts in the device time only, so the share reads low there,
never high.  A program whose prefill spans carry no ``pairs`` reads
nothing."""
from bench import program_spans
from bench.program_spans import END, NAME, START

ATTRS = 5                       # the record's attribute dict


def read(run):
    recs = program_spans.records(run)
    if recs is None:
        return None
    waves = [r for r in recs
             if r[NAME] == "engine.prefill" and "pairs" in r[ATTRS]]
    dev = run.trace.module_seconds("prefill_chunk")
    if not waves or dev <= 0:
        return None
    a, b = (t * 1e9 for t in run.span)
    s = run.shape
    flops = sum(r[ATTRS]["tokens"] * s.linear_flops_per_token()
                + r[ATTRS]["pairs"] * s.attn_pair_flops()
                for r in waves if a <= r[START] and r[END] <= b)
    return 100.0 * flops / (dev * run.peak["bf16_flops_per_s"])
