"""Percentile and rate arithmetic on a synthetic event log."""
import math
from types import SimpleNamespace

import pytest

from bench import harness, stats

INF = float("inf")


def _req(rid, due, first, last, n, max_new, finished=True, admit=None,
         deliveries=None):
    r = harness.Req(rid, due, 100, max_new)
    r.first_s, r.last_s, r.n_out, r.finished = first, last, n, finished
    r.admit_s = admit
    r.deliveries = deliveries or []
    return r


def _run(reqs, t_open=0.0, seconds=10.0):
    return SimpleNamespace(requests=reqs, t_open=t_open,
                           t_close=t_open + seconds, seconds=seconds)


@pytest.mark.parametrize("p,want", [(50, 5), (90, 9), (100, 10), (1, 1)])
def test_nearest_rank(p, want):
    assert stats.pct(range(1, 11), p) == want


def test_failed_requests_count_as_missing():
    vals = list(range(1, 10)) + [INF]
    assert stats.pct(vals, 90) == 9          # one failure beyond p90
    assert stats.pct(vals + [INF], 90) == INF    # two: p90 itself fails


def test_ttft_tpot_and_queue_wait_from_the_log():
    reqs = [_req(0, 1.0, 1.2, 2.2, 11, 11, admit=1.1),
            _req(1, 2.0, 2.5, 2.5, 1, 1, admit=2.4),
            _req(2, 3.0, 3.3, None, 4, 10, finished=False, admit=None)]
    run = _run(reqs)
    assert stats.ttfts_ms(run) == pytest.approx([200.0, 500.0, INF])
    # request 1 has one token and no gaps; request 2 failed
    assert stats.tpots_ms(run) == pytest.approx([100.0, INF])
    assert stats.queue_waits_ms(run) == pytest.approx([100.0, 400.0, INF])


def test_finite_keeps_a_lower_bound():
    assert stats.finite(INF, 60e3) == 60e3
    assert stats.finite(3.0, 60e3) == 3.0
    assert math.isnan(stats.pct([], 50))


def test_metric_readers_on_a_synthetic_run():
    reqs = [_req(i, float(i), i + 0.1 * (i + 1), i + 1.0, 5, 5,
                 admit=i + 0.05, deliveries=[(i + 0.1 * (i + 1), 1, 2 * i, True),
                                             (i + 1.0, 4, 2 * i + 1, False)])
            for i in range(10)]
    run = _run(reqs, seconds=10.0)
    run.cell = SimpleNamespace(spec={"drain_cap_s": 60})
    run.trace = run.span = None
    assert harness.metric_reader("ttft_p50_ms")(run) == pytest.approx(500.0)
    assert harness.metric_reader("ttft_p75_ms")(run) == pytest.approx(800.0)
    # per-layer readers need a traced interval
    assert harness.metric_reader("queue_wait_p75_ms")(run) is None
    run.trace = SimpleNamespace(module_seconds=lambda role: 0.8)
    run.span, run.engine = (0.0, 10.5), {"slots": 2, "chunk": 128}
    assert harness.metric_reader("queue_wait_p75_ms")(run) == \
        pytest.approx(50.0)
    # each request got 4 decode tokens from a burst of its own step:
    # 40 rounds in 0.8 s of burst programs
    assert harness.metric_reader("decode_round_ms")(run) == \
        pytest.approx(20.0)
