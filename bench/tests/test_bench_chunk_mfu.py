"""``chunk_mfu`` on synthetic span records: the FLOPs of the prefill waves
whose spans lie inside the traced interval over the prefill-chunk
programs' device time there at the peak; waves across an edge count in
the device time only; nothing untraced or without ``pairs``."""
from types import SimpleNamespace

import pytest

from bench import harness, trace
from bench.work import Shape

SHIFT = 5_000_000_000           # profiler clock minus host clock, ns
US = 1000
PEAK = {"bf16_flops_per_s": 1e15}
SHAPE = Shape(layers=2, d=64, heads=4, kv_heads=2, head_dim=16, vocab=256,
              d_ff=128)


def _run(wave_attrs, traced=True):
    """A 100 us window holding 40 us of ``jit_chunk_step``; waves from -5
    (across the open), 10, 40 and 90 (across the close) us."""
    events = {"ops": [[0, "%fusion.1 bf16[8]", SHIFT, 10 * US]],
              "modules": [[0, "jit_chunk_step", SHIFT + t * US, 10 * US]
                          for t in (-5, 12, 42, 95)]
              + [[0, "jit_burst", SHIFT + 60 * US, 20 * US]],
              "spans": [["bench.window", SHIFT, 100 * US]]}
    recs = [(i, "engine.prefill", t * US, (t + 15) * US, 9, dict(a))
            for i, (t, a) in enumerate(zip((-5, 10, 40, 90), wave_attrs))]
    recs.append((9, "engine.step", -10 * US, 200 * US, None, {}))
    return SimpleNamespace(trace=trace.TraceView(events) if traced else None,
                           span=(0.0, 100e-6), peak=PEAK, shape=SHAPE,
                           stats={"spans": recs, "spans_dropped": 0})


WAVES = [{"offset": 0, "rows": 2, "tokens": 32, "pairs": 528},
         {"offset": 16, "rows": 2, "tokens": 16, "pairs": 344},
         {"offset": 0, "rows": 1, "tokens": 10, "pairs": 55},
         {"offset": 10, "rows": 1, "tokens": 6, "pairs": 81}]


def test_flops_of_the_waves_inside_over_the_chunk_programs_time():
    got = harness.metric_reader("chunk_mfu")(_run(WAVES))
    # by hand: a token's projections and SwiGLU in each of 2 layers are
    # 2 x (4*16*64 (q) + 2 * 2*16*64 (k, v) + 4*16*64 (o) + 3*64*128)
    # = 2 x 36864 FLOPs a layer; a pair's QK^T and PV are 4 x 4 heads x 16
    # per layer
    tok = 2 * 2 * (4096 + 4096 + 4096 + 24576)
    pair = 4 * 2 * 4 * 16
    flops = (16 + 10) * tok + (344 + 55) * pair
    # device time inside the window: 5 us of the wave at -5, 10 + 10, and
    # 5 of the one at 95; the burst does not count
    assert got == pytest.approx(100.0 * flops / (30e-6 * 1e15))


def test_silent_untraced_or_without_pairs():
    assert harness.metric_reader("chunk_mfu")(_run(WAVES, False)) is None
    old = [{k: v for k, v in a.items() if k != "pairs"} for a in WAVES]
    assert harness.metric_reader("chunk_mfu")(_run(old)) is None
