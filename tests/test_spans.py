"""Host spans of the serving engine (``launch/spans.py``).

Contract under test:

  * the recorder: nested spans carry their parent's id, attributes set
    inside a span land on its record, ``mark`` records a span that does
    not nest, the ring keeps the newest ``capacity`` records and counts
    the rest as dropped;
  * the engine: ``start()`` resets the recorder; every ``step()`` is one
    ``engine.step`` span whose phases nest inside it in order; the burst
    spans' ``rounds`` sum to the run's decode rounds (and carry the
    ``experts_read`` counter only where the model has experts); a
    prefill wave's ``pairs`` counts the (query, key) pairs its rows
    attend; every request gets
    one ``engine.queued``, ``engine.first_token`` and ``engine.finish``
    mark, in that order; ``ReplicatedEngine`` keeps one list per replica.
"""
from collections import Counter, defaultdict

import pytest

from conftest import cached_model
from repro.launch.engine import ContinuousEngine, ReplicatedEngine, Request
from repro.launch.spans import Span, Spans


def test_nesting_parents_and_late_attributes():
    rec = Spans()
    with rec.span("outer", rows=2) as outer:
        with rec.span("inner") as inner:
            inner.set(rounds=3)
        with rec.span("inner2"):
            pass
    got = {r.name: r for r in rec.records()}
    assert [r.name for r in rec.records()] == ["inner", "inner2", "outer"]
    assert got["outer"].parent is None
    assert got["inner"].parent == got["inner2"].parent == got["outer"].id
    assert got["inner"].attrs == {"rounds": 3}
    assert got["outer"].attrs == {"rows": 2}
    o, i = got["outer"], got["inner"]
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    assert outer.seconds == pytest.approx((o.end_ns - o.start_ns) * 1e-9)
    assert all(isinstance(r, Span) for r in rec.records())


def test_mark_does_not_nest():
    rec = Spans()
    with rec.span("step"):
        rec.mark("queued", 10, 20, rid=7)
    m = rec.records()[0]
    assert (m.name, m.start_ns, m.end_ns, m.parent, m.attrs) == (
        "queued", 10, 20, None, {"rid": 7})


def test_ring_keeps_the_newest_and_counts_the_dropped():
    rec = Spans(capacity=4)
    for i in range(10):
        rec.mark("m", i, i + 1)
    assert [r.start_ns for r in rec.records()] == [6, 7, 8, 9]
    assert rec.dropped == 6
    with rec.span("open"):
        assert rec.dropped == 6           # an open span is not dropped
    rec.reset()
    assert rec.records() == [] and rec.dropped == 0


def _requests(vocab):
    import numpy as np
    rng = np.random.RandomState(3)
    lens, budgets, arrivals = (8, 20, 32, 13, 5), (4, 9, 3, 7, 5), \
        (0, 0, 0, 2, 5)
    return [Request(rid=i, tokens=rng.randint(0, vocab, size=n).tolist(),
                    max_new=m, arrival=a)
            for i, (n, m, a) in enumerate(zip(lens, budgets, arrivals))]


@pytest.fixture(scope="module")
def served():
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    reqs = _requests(model.cfg.vocab)
    eng = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16)
    eng.run(reqs[:1])                   # a first run the second must reset
    fin, stats = eng.run(reqs)
    return reqs, eng, stats


PHASES = ("engine.admission", "engine.prefill", "engine.burst",
          "engine.burst.bookkeeping")


def test_every_step_is_a_span_with_its_phases_in_order(served):
    _, eng, stats = served
    recs = stats["spans"]
    assert stats["spans_dropped"] == 0
    ids = [r.id for r in recs]
    assert len(set(ids)) == len(ids) and min(ids) == 0   # reset by start()
    by_id = {r.id: r for r in recs}
    kids = defaultdict(list)
    for r in recs:
        if r.parent is not None:
            kids[r.parent].append(r)
    steps = [r for r in recs if r.name == "engine.step"]
    assert steps and all(r.parent is None for r in steps)
    assert eng.spans.records()[-1].name == "engine.step"
    for s in steps:
        ks = sorted(kids[s.id], key=lambda r: r.start_ns)
        names = [k.name for k in ks]
        # admission first; prefill waves before the burst; bookkeeping
        # straight after it
        assert names[0] == "engine.admission"
        assert set(names) <= set(PHASES)
        order = [PHASES.index(n) for n in names]
        assert order == sorted(order)
        if "engine.burst" in names:
            i = names.index("engine.burst")
            assert names[i + 1] == "engine.burst.bookkeeping"
        for a, b in zip(ks, ks[1:]):
            assert a.end_ns <= b.start_ns
        for k in ks:
            assert s.start_ns <= k.start_ns <= k.end_ns <= s.end_ns
    for r in recs:
        sub = sorted(kids[r.id], key=lambda k: k.start_ns)
        if r.name == "engine.burst":
            assert [k.name for k in sub] == [
                "engine.burst.prepare", "engine.burst.dispatch",
                "engine.burst.readback"]
            assert set(r.attrs) == {"live", "rounds"}     # a dense model
        elif r.name == "engine.prefill":
            assert [k.name for k in sub] == ["engine.prefill.readback"]
            assert set(r.attrs) == {"offset", "rows", "tokens", "pairs"}
            assert 0 < r.attrs["tokens"] <= r.attrs["rows"] * eng.chunk
        elif r.name == "engine.admission":
            assert set(r.attrs) == {"pending", "admitted", "preempted"}
        if r.parent is not None:
            assert by_id[r.parent].start_ns <= r.start_ns


def test_burst_rounds_sum_to_decode_rounds(served):
    _, _, stats = served
    bursts = [r for r in stats["spans"] if r.name == "engine.burst"]
    assert len(bursts) == stats["bursts"]
    assert sum(r.attrs["rounds"] for r in bursts) == stats["decode_rounds"]
    admits = [r for r in stats["spans"] if r.name == "engine.admission"]
    assert sum(r.attrs["admitted"] for r in admits) == 5


def test_prefill_pairs_count_each_rows_keys(served):
    # two rows admitted together (prompts 20 and 28, chunk 16): the wave
    # at offset 16 carries pieces of 4 and 12 tokens, each reading the
    # 16 positions before it and itself causally
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    eng = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16)
    _, stats = eng.run([Request(rid=i, tokens=[1 + i] * n, max_new=2)
                        for i, n in enumerate((20, 28))])
    waves = {(r.attrs["offset"], r.attrs["rows"]): r.attrs
             for r in stats["spans"] if r.name == "engine.prefill"}
    assert waves[(0, 2)]["pairs"] == 2 * (16 * 17 // 2)
    assert waves[(16, 2)]["tokens"] == 4 + 12
    assert waves[(16, 2)]["pairs"] == (4 * 16 + 4 * 5 // 2) + (
        12 * 16 + 12 * 13 // 2)
    # the served fixture's waves: every piece reads at least its offset
    _, eng, stats = served
    for r in stats["spans"]:
        if r.name == "engine.prefill":
            a = r.attrs
            assert a["pairs"] >= a["tokens"] * (a["offset"] + 1)


def test_moe_bursts_count_the_experts_read():
    # on the CPU a MoE layer runs the einsum over every expert, so a
    # burst reads E experts in each layer of each round
    model, params = cached_model("qwen3-moe-30b-a3b", paged_kv=True,
                                 page_size=16)
    eng = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16)
    _, stats = eng.run(_requests(model.cfg.vocab)[:2])
    bursts = [r for r in stats["spans"] if r.name == "engine.burst"]
    per_round = model.cfg.moe.n_experts * model.cfg.n_layers
    assert bursts
    for r in bursts:
        assert set(r.attrs) == {"live", "rounds", "experts_read"}
        assert r.attrs["experts_read"] == per_round * r.attrs["rounds"]


def test_one_queue_mark_per_request_then_first_token_then_finish(served):
    reqs, _, stats = served
    marks = [r for r in stats["spans"] if r.name in (
        "engine.queued", "engine.first_token", "engine.finish")]
    assert Counter(r.name for r in marks) == {
        "engine.queued": 5, "engine.first_token": 5, "engine.finish": 5}
    by = defaultdict(dict)
    for r in marks:
        assert r.parent is None
        by[r.attrs["rid"]][r.name] = r
    assert sorted(by) == [r.rid for r in reqs]
    for m in by.values():
        q, f, e = (m["engine.queued"], m["engine.first_token"],
                   m["engine.finish"])
        assert q.start_ns <= q.end_ns == f.start_ns <= f.end_ns \
            == e.start_ns <= e.end_ns


def test_replicated_engine_keeps_spans_per_replica():
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    reqs = _requests(model.cfg.vocab)
    fleet = ReplicatedEngine(model, params, replicas=2, slots=2,
                             max_len=48, chunk=16)
    _, stats = fleet.run(reqs)
    assert len(stats["spans"]) == 2
    for eng, recs in zip(fleet.engines, stats["spans"]):
        assert recs and [r.id for r in recs] == [
            r.id for r in eng.spans.records()]
        assert sum(r.name == "engine.queued" for r in recs) == len(
            [e for e in fleet.partition(reqs)[eng.replica_id]])
    assert all("spans" not in s for s in stats["replicas"])
    assert stats["spans_dropped"] == 0
