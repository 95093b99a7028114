"""FLOPs and bytes the served work requires, pinned for one known shape
per configuration; they follow from lengths and published shapes only."""
import inspect
import json
from pathlib import Path

import pytest

from bench import peaks, work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
QWEN = work.Shape.from_config(json.loads(
    (CONFIGS / "qwen3-moe-30b-a3b.json").read_text()))


def test_shapes_read_the_published_widths():
    assert (QWEN.layers, QWEN.d, QWEN.heads, QWEN.kv_heads, QWEN.head_dim,
            QWEN.experts, QWEN.top_k, QWEN.d_expert) == (
        10, 2048, 32, 4, 128, 128, 8, 768)


def test_qwen3_prefill_counts_top_k_experts_only():
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    ffn = 8 * 3 * 2048 * 768 + 2048 * 128
    per_tok = 2 * 10 * (attn + ffn)
    assert QWEN.linear_flops_per_token() == per_tok
    want = 512 * per_tok + 512 * 513 / 2 * 4 * 10 * 32 * 128 \
        + 2 * 2048 * 151936
    assert work.prefill_flops(QWEN, 512) == pytest.approx(want, rel=1e-12)
    assert work.prefill_flops(QWEN, 512) == pytest.approx(6.0464e11,
                                                          rel=1e-3)


def test_qwen3_flash_prefill_bytes_read_the_live_prefix_per_chunk():
    # a 300-token prompt in 128-token chunks: queries and output of every
    # token once, K/V of the live prefix (128, 256, 300) once per chunk
    f, b = work.flash_prefill_work(QWEN, 300, 128)
    assert f == 300 * 301 / 2 * 4 * 10 * 32 * 128
    q_row = 32 * 128 * 2
    kv_row = 2 * 4 * 128 * 2
    assert b == 10 * (300 * 2 * q_row + (128 + 256 + 300) * kv_row)


def test_counts_do_not_depend_on_chunking_blocks_or_capacity():
    # attention FLOPs are the causal pairs, whatever the chunk size
    f128, b128 = work.flash_prefill_work(QWEN, 3000, 128)
    f1024, b1024 = work.flash_prefill_work(QWEN, 3000, 1024)
    assert f128 == f1024
    # smaller chunks re-read the live prefix more often
    assert b128 > b1024
    # no function takes a kernel block size or an expert capacity
    for fn in (work.prefill_flops, work.flash_prefill_work):
        params = set(inspect.signature(fn).parameters)
        assert not params & {"bq", "bk", "block", "capacity", "cap"}


def test_roofline_picks_the_binding_bound():
    v5e = peaks.peak("TPU v5 lite")
    t, bound = work.roofline_seconds(197e12, 1.0, v5e)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.roofline_seconds(1.0, 819e9, v5e)
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
