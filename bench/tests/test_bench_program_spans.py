"""The readers of the program's own spans (``step_host_idle_share``,
``admit_wait_p75_ms``) on a synthetic trace whose answers are known, and
on a slice of a trace recorded on a TPU v5e with the engine's spans
(``fixtures/spans-qwen3moe-chat.json.gz``: ``collect``'s events, the
engine's records, and the host-clock interval of the slice's
``bench.window``)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, program_spans, trace

FIX = Path(__file__).resolve().parent / "fixtures" / \
    "spans-qwen3moe-chat.json.gz"
SHIFT = 5_000_000_000           # profiler clock minus host clock, ns


def _run(events, recs, span, dropped=0):
    return SimpleNamespace(trace=trace.TraceView(events), span=span,
                           stats={"spans": recs, "spans_dropped": dropped})


def _synthetic():
    """A 100 us window on the profiler's clock (host 0..100 us); the
    device busy 0-10, 30-40 and 70-100 us; two steps on the host, the
    first with a burst (prepare, dispatch, readback) and bookkeeping."""
    us = 1000
    ops = [[0, "%fusion.1 bf16[8]", (SHIFT + a * us), (b - a) * us]
           for a, b in ((0, 10), (30, 40), (70, 100))]
    events = {"ops": ops, "modules": [],
              "spans": [["bench.window", SHIFT, 100 * us]]}
    recs = [
        (1, "engine.admission", 0, 5 * us, 0, {}),
        (3, "engine.burst.prepare", 5 * us, 12 * us, 2, {}),
        (4, "engine.burst.dispatch", 12 * us, 14 * us, 2, {}),
        (5, "engine.burst.readback", 14 * us, 45 * us, 2, {}),
        (2, "engine.burst", 5 * us, 45 * us, 0, {"rounds": 2}),
        (6, "engine.burst.bookkeeping", 45 * us, 50 * us, 0, {}),
        (0, "engine.step", 0, 55 * us, None, {}),
        (7, "engine.queued", -30 * us, 60 * us, None, {"rid": 0}),
        (8, "engine.queued", 20 * us, 25 * us, None, {"rid": 1}),
        (9, "engine.queued", 40 * us, 200 * us, None, {"rid": 2}),
        (10, "engine.step", 80 * us, 95 * us, None, {}),
    ]
    return events, recs, (0.0, 100e-6)


def test_idle_by_phase_splits_the_idle_inside_steps():
    events, recs, span = _synthetic()
    run = _run(events, recs, span)
    got = program_spans.idle_by_phase(run)
    us = 1000
    # idle 10-30 and 40-70 us; inside the first step (0-55) that is
    # 10-12 prepare, 14-30 readback, 40-45 readback, 45-50 bookkeeping,
    # 50-55 the step's own; the second step (80-95) is all busy
    assert got == pytest.approx({
        "engine.admission": 0, "engine.burst.prepare": 2 * us,
        "engine.burst.dispatch": 2 * us, "engine.burst.readback": 21 * us,
        "engine.burst": 0, "engine.burst.bookkeeping": 5 * us,
        "engine.step": 5 * us})
    share = harness.metric_reader("step_host_idle_share")(run)
    assert share == pytest.approx(35.0)
    assert share <= 100 * (1 - run.trace.busy_s / run.trace.window_s)


def test_admit_wait_counts_requests_enqueued_inside_up_to_the_close():
    events, recs, span = _synthetic()
    # rid 0 was enqueued before the interval; rid 2 is still queued at its
    # close (100 us) and counts 60 us; rid 1 waited 5 us
    got = harness.metric_reader("admit_wait_p75_ms")(
        _run(events, recs, span))
    assert got == pytest.approx(0.060)


@pytest.mark.parametrize("name", ["step_host_idle_share",
                                  "admit_wait_p75_ms"])
def test_readers_stay_silent_without_spans(name):
    events, recs, span = _synthetic()
    read = harness.metric_reader(name)
    assert read(_run(events, [], span)) is None            # the parent
    assert read(SimpleNamespace(trace=None, span=None,
                                stats={"spans": recs})) is None
    # the ring dropped records: its oldest closed 5 us in, after the
    # interval's start, so what it lost may have overlapped the interval
    assert read(_run(events, recs, span, dropped=3)) is None
    # an interval opening at 5 us lost only what closed before it
    assert read(_run(events, recs, (5e-6, 100e-6), dropped=3)) is not None


@pytest.fixture(scope="module")
def recorded():
    fx = trace.load(str(FIX))
    events = {k: fx[k] for k in ("ops", "modules", "spans")}
    return _run(events, fx["program_spans"], tuple(fx["span"]))


def test_recorded_bursts_bracket_their_programs(recorded):
    """On the profiler's clock, each burst program the device ran lies
    between its burst's dispatch and the end of its readback, to within
    the profiler's own placement of device events against host events:
    the v5e trace puts a burst program up to 0.59 ms before the host
    dispatched it (18 bursts of the run this slice comes from)."""
    run, tol = recorded, 1_000_000
    sh = program_spans.shift_ns(run)
    recs = run.stats["spans"]
    kids = {}
    for r in recs:
        kids.setdefault(r[program_spans.PARENT], {})[r[1]] = r
    # the slice holds two whole bursts (and the tail of one before it)
    pairs = [(kids[r[0]]["engine.burst.dispatch"][2] + sh,
              kids[r[0]]["engine.burst.readback"][3] + sh)
             for r in recs if r[1] == "engine.burst"
             and "engine.burst.dispatch" in kids.get(r[0], {})]
    assert len(pairs) == 2
    tv = run.trace
    mods = [m for m in tv.events["modules"] if m[1] == "jit_burst"
            and tv.t0 <= m[2] and m[2] + m[3] <= tv.t1]
    assert mods and pairs
    for m in mods:
        assert any(a - tol <= m[2] and m[2] + m[3] <= b + tol
                   for a, b in pairs), m


def test_recorded_step_idle_is_part_of_the_idle(recorded):
    run = recorded
    share = harness.metric_reader("step_host_idle_share")(run)
    idle = 100 * (1 - run.trace.busy_s / run.trace.window_s)
    assert 0 < share <= idle
    phases = program_spans.idle_by_phase(run)
    assert sum(phases.values()) == pytest.approx(
        share / 100 * (run.trace.t1 - run.trace.t0))
