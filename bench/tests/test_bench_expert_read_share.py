"""``expert_read_share`` on synthetic span records: the ``experts_read``
of the bursts that start inside the traced interval over experts x layers
x their rounds, and nothing from a program whose bursts lack it."""
from types import SimpleNamespace

import pytest

from bench import harness, trace
from bench.work import Shape

SHIFT = 5_000_000_000           # profiler clock minus host clock, ns
US = 1000


def _run(burst_attrs):
    """A 100 us window; bursts at 5, 50 and (outside it) 150 us."""
    events = {"ops": [[0, "%fusion.1 bf16[8]", SHIFT, 10 * US]],
              "modules": [], "spans": [["bench.window", SHIFT, 100 * US]]}
    recs = [(i, "engine.burst", t * US, (t + 20) * US, None, dict(a))
            for i, (t, a) in enumerate(zip((5, 50, 150), burst_attrs))]
    recs.append((9, "engine.step", 0, 200 * US, None, {}))
    shape = Shape(layers=10, d=64, heads=4, kv_heads=2, head_dim=16,
                  vocab=256, experts=128, top_k=8, d_expert=32)
    return SimpleNamespace(trace=trace.TraceView(events), span=(0.0, 100e-6),
                           stats={"spans": recs, "spans_dropped": 0},
                           shape=shape)


def test_share_of_the_experts_the_bursts_in_the_interval_read():
    run = _run([{"live": 8, "rounds": 2, "experts_read": 800},
                {"live": 8, "rounds": 3, "experts_read": 1400},
                {"live": 8, "rounds": 64, "experts_read": 81920}])
    got = harness.metric_reader("expert_read_share")(run)
    # (800 + 1400) / (128 experts x 10 layers x 5 rounds); the burst past
    # the close does not count
    assert got == pytest.approx(100.0 * 2200 / 6400)


def test_silent_where_the_bursts_carry_no_count():
    run = _run([{"live": 8, "rounds": 2}, {"live": 8, "rounds": 3},
                {"live": 8, "rounds": 4}])
    assert harness.metric_reader("expert_read_share")(run) is None
