"""The trace reduction on a slice of a trace recorded on a TPU v5e
(qwen3moe-chat: one prefill-chunk program, one two-round burst, the next
chunk), kept as ``fixtures/trace-qwen3moe-chat.json.gz`` in ``collect``'s
format."""
from pathlib import Path

import pytest

from bench import trace

FIX = Path(__file__).resolve().parent / "fixtures" / "trace-qwen3moe-chat.json.gz"


@pytest.fixture(scope="module")
def tv():
    return trace.TraceView(trace.load(str(FIX)))


def test_finds_both_programs_by_name(tv):
    assert tv.module_seconds("burst") == pytest.approx(0.04696, rel=1e-3)
    # two chunk programs, the second cut at the slice's end
    assert 0.060 < tv.module_seconds("prefill_chunk") < 0.0935


def test_finds_both_pallas_kernels(tv):
    assert tv.kernel_seconds("flash_prefill") > 0
    assert tv.kernel_seconds("decode_attn") > 0
    # kernels run inside the programs
    assert tv.kernel_seconds("flash_prefill") < tv.module_seconds(
        "prefill_chunk")
    assert tv.kernel_seconds("decode_attn") < tv.module_seconds("burst")


def test_busy_is_a_union_inside_the_window(tv):
    assert tv.window_s == pytest.approx(0.150065, rel=1e-4)
    assert 0.5 * tv.window_s < tv.busy_s < tv.window_s
    # container ops (while loops) would double count; leaf ops do not
    assert all(not r[1].startswith(trace.CONTAINERS) for r in tv.leaf)


def test_breakdown_lists(tv):
    ops = tv.top_ops(10)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
    assert any("(decode_attn)" in name for name, _ in tv.top_ops(50))
    gaps = tv.idle_gaps(10)
    assert len(gaps) == 10 and all(g > 0 for _, g in gaps)
    assert {label for label, _ in gaps} <= {"bench.step", "bench.adopt",
                                           "bench.wait", "bench.drain",
                                           "outside any bench span"}
    assert sum(g for _, g in tv.idle_gaps(10**6)) == pytest.approx(
        tv.window_s - tv.busy_s, rel=1e-6)


def test_op_label_keeps_name_and_type():
    hlo = ("%fusion.213 = bf16[16385,2048]{1,0:T(8,128)(2,1)} fusion("
           "bf16[16385,2048]{1,0} %broadcast_in_dim.176), kind=kCustom")
    assert trace.op_label(hlo) == "%fusion.213 bf16[16385,2048]"
