"""Model step: the share of the expert weights the decode bursts read.
The sum of the ``experts_read`` attribute of the ``engine.burst`` spans
that start inside the traced interval, over ``experts x layers x rounds``
of the same spans (each burst's own ``rounds``).  A program whose bursts
carry no ``experts_read`` reads nothing."""
from bench import program_spans
from bench.program_spans import NAME, START

ATTRS = 5                       # the record's attribute dict


def read(run):
    recs = program_spans.records(run)
    if recs is None or not run.shape.experts:
        return None
    a, b = (t * 1e9 for t in run.span)
    read_, rounds = 0, 0
    for r in recs:
        attrs = r[ATTRS]
        if (r[NAME] == "engine.burst" and a <= r[START] <= b
                and "experts_read" in attrs):
            read_ += attrs["experts_read"]
            rounds += attrs["rounds"]
    if rounds == 0:
        return None
    return 100.0 * read_ / (run.shape.experts * run.shape.layers * rounds)
