"""The traffic generator: one schedule of sizes and arrivals for every
seed, token ids drawn from the seed, lengths clipped as the mix files
state and matching the published means they cite."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import generator

BENCH = Path(__file__).resolve().parents[1]
CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
OPEN = {"rate_rps": 2.0}


def test_same_seed_same_plan_and_tokens():
    a = generator.plan(CHAT, OPEN, 30.0)
    b = generator.plan(CHAT, OPEN, 30.0)
    assert a == b
    assert (generator.prompt_tokens(2**33 + 5, a, 1000)
            == generator.prompt_tokens(2**33 + 5, b, 1000))


def test_seeds_share_the_schedule_not_the_tokens():
    plan = generator.plan(CHAT, OPEN, 30.0)
    assert (generator.prompt_tokens(1, plan, 1000)
            != generator.prompt_tokens(2, plan, 1000))
    # the mix's schedule_seed orders one stratified multiset
    other = generator.plan(dict(CHAT, schedule_seed=2), OPEN, 30.0)
    assert [p.prompt_len for p in plan] != [p.prompt_len for p in other]
    for key in ("prompt_len", "max_new"):
        assert sorted(getattr(p, key) for p in plan) == sorted(
            getattr(p, key) for p in other)


def test_lengths_clip_to_the_mix():
    plan = generator.plan(CHAT, {"rate_rps": 20.0}, 60.0)
    for key, spec in (("prompt_len", CHAT["prompt"]),
                      ("max_new", CHAT["output"])):
        v = [getattr(p, key) for p in plan]
        assert spec["min"] <= min(v) and max(v) <= spec["max"]
    # the long tail is really there: the upper clip is reached
    assert max(p.prompt_len for p in plan) == CHAT["prompt"]["max"]
    # as the ShareGPT benchmark keeps requests: prompt + output <= 2048
    assert max(p.prompt_len + p.max_new for p in plan) <= 2048


@pytest.mark.parametrize("key,spec", [("prompt_mean", "prompt"),
                                      ("output_mean", "output")])
def test_lengths_have_the_published_means(key, spec):
    v = generator.stratified_lognormal(CHAT[spec], 20000)
    assert v.mean() == pytest.approx(CHAT["published"][key], rel=0.005)


def test_open_loop_due_times_span_the_window():
    plan = generator.plan(CHAT, OPEN, 30.0)
    due = [p.due_s for p in plan]
    assert len(plan) == 60
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30.0
    gaps = np.diff(due)
    # Poisson arrivals: the gaps' spread is about their mean
    assert 0.7 < gaps.std() / gaps.mean() < 1.3


def test_token_ids_cover_the_vocabulary_range():
    plan = generator.plan(CHAT, OPEN, 10.0)
    toks = generator.prompt_tokens(4, plan, 50)
    flat = [t for p in plan for t in toks[p.rid]]
    assert all(len(toks[p.rid]) == p.prompt_len for p in plan)
    assert min(flat) == 0 and max(flat) == 49
