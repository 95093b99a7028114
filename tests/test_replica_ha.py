"""Replica-level fault tolerance: failure injection, live-request
migration, and the crash-consistent request journal.

Contract under test (the HA half of the serving story):

  * replica loss is SURVIVABLE with token-bit-identical results — a
    killed replica (``ReplicaLostError`` through burst dispatch, device
    memory gone) force-reingests its in-flight requests onto a survivor
    from host-side emitted tokens; a hung replica (missed heartbeats,
    memory still readable) migrates them as CRC-verified swap-blob
    continuations when ``migrate="swap"``;
  * migration composes with every robustness feature it rides over —
    ``no_degrade`` victims stay bit-exact through a degrading swap
    store, mid-escalation victims keep their precision rung on the
    surviving replica;
  * swap payloads carry pool provenance (``SwapBlobTag``): a foreign
    blob (dtype or page-size mismatch) is REFUSED with ``ValueError``
    instead of silently reinterpreting page bytes;
  * the journal makes a FULL fleet loss recoverable: a restarted run
    replays every unfinished request from its last journaled token and
    finishes with bit-parity; ``RequestJournal.load`` drops (and
    truncates) a crash-torn tail line but hard-errors on mid-file
    corruption; two independent recovery runs from the same journal are
    identical;
  * ``run_with_restarts`` resets every replica's monitors per attempt
    and its exhaustion diagnostics name the replica behind each failed
    attempt;
  * the ``"session"`` trace flavor emits multi-turn conversations over
    a growing shared prefix, and the HA soak over it drains to zero
    stuck requests through a replica kill.
"""
import shutil

import numpy as np
import pytest

import jax

from repro.launch import mesh as meshmod
from repro.launch.engine import (ContinuousEngine, ReplicatedEngine,
                                 Request, synthetic_trace)
from repro.launch.journal import RequestJournal
from repro.models.paged import SwapBlobTag, check_blob_tag
from repro.train.fault import (ReplicaFaultPlan, ReplicaLostError,
                               ServeFaultPlan, run_with_restarts)


@pytest.fixture(scope="module")
def setup():
    from conftest import cached_model
    return cached_model("gemma2-9b", paged_kv=True, page_size=16)


def _toks(fin):
    return {f.rid: list(f.tokens) for f in fin}


def _queue(vocab):
    """Eight mixed requests over two arrival waves — enough work per
    replica that a burst-1 kill lands mid-run with residents in flight."""
    return synthetic_trace(8, 4, 16, 8, vocab)


def _long_queue(vocab, n=4, no_degrade_rid=None):
    """Long-budget residents: every row is mid-decode for several bursts,
    so a hang finds swappable K/V pages to migrate."""
    rng = np.random.RandomState(3)
    return [Request(rid=i, tokens=rng.randint(0, vocab, size=6).tolist(),
                    max_new=14, arrival=0,
                    no_degrade=(i == no_degrade_rid))
            for i in range(n)]


def _fleet(setup, **kw):
    model, params = setup
    kw.setdefault("replicas", 2)
    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 8)
    kw.setdefault("burst_cap", 4)
    return ReplicatedEngine(model, params, **kw)


@pytest.fixture(scope="module")
def baseline(setup):
    """Unfailed 2-replica fleet over the kill queue: the parity oracle."""
    model, _ = setup
    reqs = _queue(model.cfg.vocab)
    ml = max(r.prompt_len + r.max_new for r in reqs)
    fin, stats = _fleet(setup, max_len=ml).run(reqs)
    assert stats["ha_kills"] == stats["ha_migrations"] == 0
    return reqs, ml, _toks(fin)


# ---------------------------------------------------------------------------
# failure injection + migration parity
# ---------------------------------------------------------------------------
def test_kill_reingest_migration_parity(setup, baseline):
    reqs, ml, base = baseline
    plan = ReplicaFaultPlan(replica=0, at_burst=1, mode="kill")
    fleet = _fleet(setup, max_len=ml, migrate="reingest",
                   replica_fault=plan)
    fin, st = fleet.run(reqs)
    assert _toks(fin) == base
    assert [f.rid for f in fin] == [r.rid for r in reqs]
    assert st["ha_kills"] == 1 and st["ha_hangs"] == 0
    assert st["ha_migrations"] >= 1
    assert st["ha_migrated_reingest"] == st["ha_migrations"]
    assert st["ha_migrated_swap"] == 0
    assert st["heartbeats"][0]["status"] == "dead"
    assert st["heartbeats"][1]["status"] == "live"
    assert any(k == "kill" for k, _ in plan.events)


def test_kill_under_swap_mode_falls_back_to_reingest(setup, baseline):
    """A killed replica's device memory is GONE: even with
    ``migrate="swap"`` requested, evacuation must re-ingest from
    host-side emitted tokens — and still hit token parity."""
    reqs, ml, base = baseline
    plan = ReplicaFaultPlan(replica=0, at_burst=1, mode="kill")
    fleet = _fleet(setup, max_len=ml, migrate="swap", preempt="swap",
                   replica_fault=plan)
    fin, st = fleet.run(reqs)
    assert _toks(fin) == base
    assert st["ha_kills"] == 1 and st["ha_migrations"] >= 1
    assert st["ha_migrated_swap"] == 0
    assert st["ha_migrated_reingest"] == st["ha_migrations"]


def test_hang_swap_blob_migration_parity(setup):
    """A hung replica's pages are still readable: residents travel as
    tagged swap blobs into the survivor's pool, bit-identically."""
    model, _ = setup
    reqs = _long_queue(model.cfg.vocab)
    ml = 6 + 14
    base, _ = _fleet(setup, max_len=ml, preempt="swap").run(reqs)
    plan = ReplicaFaultPlan(replica=0, at_burst=2, mode="hang")
    fleet = _fleet(setup, max_len=ml, preempt="swap", migrate="swap",
                   hang_patience=1, replica_fault=plan)
    fin, st = fleet.run(reqs)
    assert _toks(fin) == _toks(base)
    assert st["ha_hangs"] == 1 and st["ha_kills"] == 0
    assert st["ha_migrated_swap"] >= 1
    assert st["heartbeats"][0]["status"] == "dead"
    assert st["heartbeats"][0]["missed"] >= 1


def test_no_degrade_victim_stays_exact_through_migration(setup):
    """The quality-sensitive opt-out survives migration: a ``no_degrade``
    request on the hung replica migrates through a DEGRADING (fp8) swap
    store yet matches the solo un-preempted run bit-for-bit."""
    model, params = setup
    import jax.numpy as jnp
    reqs = _long_queue(model.cfg.vocab, no_degrade_rid=0)
    ml = 6 + 14
    plan = ReplicaFaultPlan(replica=0, at_burst=2, mode="hang")
    fleet = _fleet(setup, max_len=ml, preempt="swap", migrate="swap",
                   degrade_fmt="fp8", hang_patience=1, replica_fault=plan)
    fin, st = fleet.run(reqs)
    assert st["ha_hangs"] == 1 and st["ha_migrations"] >= 1
    g = jax.jit(lambda p, t: model.generate(
        p, t, gen_len=14, max_len=ml)[0])
    solo = np.asarray(g(params, jnp.asarray(
        reqs[0].tokens, jnp.int32)[None]))[0].tolist()
    f0 = next(f for f in fin if f.rid == 0)
    assert f0.tokens == solo
    assert not f0.degraded
    assert all(len(f.tokens) == r.max_new for r, f in zip(reqs, fin))


def test_mid_escalation_victim_keeps_rung(setup):
    """A request that escalated its KV rung before the failure keeps the
    rung on the surviving replica (``_QEntry.esc_level`` rides the
    migration) — tokens match the unfailed escalating fleet."""
    from conftest import cached_model
    from repro.core.policy import EscalationPolicy
    model, params = cached_model("gemma2-9b", policy="fp32",
                                 paged_kv=True, page_size=16)
    rng = np.random.RandomState(5)
    reqs = [Request(rid=i, tokens=rng.randint(
                0, model.cfg.vocab, size=12).tolist(), max_new=16)
            for i in range(4)]
    mk = lambda fault: ReplicatedEngine(
        model, params, replicas=2, slots=2, max_len=30, chunk=8,
        burst_cap=4, migrate="reingest", replica_fault=fault,
        escalate=EscalationPolicy(of_threshold=4),
        fault_plan=ServeFaultPlan(overflow_at=(2,),
                                  overflow_scale=65536.0))
    base, bst = mk(None).run(reqs)
    assert bst["escalations"] >= 1
    plan = ReplicaFaultPlan(replica=0, at_burst=3, mode="hang")
    fin, st = mk(plan).run(reqs)
    assert _toks(fin) == _toks(base)
    assert st["ha_hangs"] == 1 and st["ha_migrations"] >= 1
    assert st["escalations"] >= 1
    assert {f.rid: f.escalated for f in fin} == \
           {f.rid: f.escalated for f in base}


# ---------------------------------------------------------------------------
# swap-blob provenance
# ---------------------------------------------------------------------------
def test_blob_tag_unit():
    ok = SwapBlobTag(replica=0, dtype="bfloat16", page=16)
    check_blob_tag(ok, dtype="bfloat16", page=16)
    check_blob_tag(None, dtype="bfloat16", page=16)    # legacy untagged
    # replica provenance alone is NOT foreign — migration is the point
    check_blob_tag(ok._replace(replica=7), dtype="bfloat16", page=16)
    with pytest.raises(ValueError, match="foreign swap blob"):
        check_blob_tag(ok._replace(dtype="float32"),
                       dtype="bfloat16", page=16)
    with pytest.raises(ValueError, match="foreign swap blob"):
        check_blob_tag(ok._replace(page=8), dtype="bfloat16", page=16)


def test_adopt_refuses_foreign_blob(setup):
    """End-to-end: an evacuated swap blob whose tag disagrees with the
    receiving pool's layout is refused at ``adopt`` time."""
    model, _ = setup
    reqs = _long_queue(model.cfg.vocab)
    fleet = _fleet(setup, max_len=20, preempt="swap")
    parts = fleet.partition(reqs)
    e0, e1 = fleet.engines
    e0.start(parts[0])
    e1.start(parts[1])
    for _ in range(3):
        e0.step()
    entries = e0.evacuate(readable=True, mode="swap")
    blob = next(e for e in entries
                if e.resume is not None and e.resume.blobs is not None)
    good = blob.resume.tag
    assert isinstance(good, SwapBlobTag) and good.replica == 0
    blob.resume.tag = good._replace(page=good.page * 2)
    with pytest.raises(ValueError, match="foreign swap blob"):
        e1.adopt([blob])
    blob.resume.tag = good._replace(replica=7)     # same layout: adoptable
    assert e1.adopt([blob]) == 1
    while e1.step():
        pass
    res, _ = e1.finalize()
    assert len(res[blob.req.rid].tokens) == blob.req.max_new


# ---------------------------------------------------------------------------
# the crash-consistent journal
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def crashed_journal(setup, tmp_path_factory):
    """A single-replica journaled fleet killed mid-run: no survivor, so
    the loss re-raises — the on-disk journal is the only memory.
    Returns (journal path, queue, unfailed single-engine oracle)."""
    model, params = setup
    reqs = synthetic_trace(6, 2, 16, 8, model.cfg.vocab)
    ml = max(r.prompt_len + r.max_new for r in reqs)
    eng = ContinuousEngine(model, params, slots=2, max_len=ml, chunk=8,
                           burst_cap=2)
    base, _ = eng.run(reqs)
    path = tmp_path_factory.mktemp("ha") / "journal.jsonl"
    jr = RequestJournal(str(path))
    plan = ReplicaFaultPlan(replica=0, at_burst=2, mode="kill")
    fleet = _fleet(setup, replicas=1, max_len=ml, burst_cap=2,
                   migrate="reingest", replica_fault=plan, journal=jr)
    with pytest.raises(ReplicaLostError, match="replay the journal"):
        fleet.run(reqs)
    jr.close()
    counts = RequestJournal.load(str(path)).counts()
    assert counts["replica_lost"] == 1
    assert counts.get("finish", 0) < len(reqs)      # the crash lost work
    return path, reqs, ml, _toks(base)


def test_restart_replays_journal_to_parity(setup, crashed_journal,
                                           tmp_path):
    """``run_with_restarts`` over the journaled fleet: attempt 1 dies,
    attempt 2 replays the journal and finishes every request with
    tokens identical to the run that never crashed."""
    path, reqs, ml, base = crashed_journal
    p = tmp_path / "journal.jsonl"
    shutil.copy(path, p)
    jr = RequestJournal.load(str(p))
    plan = ReplicaFaultPlan(replica=0, at_burst=2, mode="kill")
    fleet = _fleet(setup, replicas=1, max_len=ml, burst_cap=2,
                   migrate="reingest", replica_fault=plan,
                   journal=jr).bind(reqs)
    runner, restarts = run_with_restarts(lambda: fleet, max_restarts=2)
    assert runner is fleet and restarts == 1
    # every request now has a finish record: a further recovery run
    # answers entirely from the journal, re-serving nothing
    fin, st = fleet.run()
    assert _toks(fin) == base
    assert jr.counts()["replay"] >= 1
    assert st["decode_rounds"] == 0


def test_two_recovery_runs_are_identical(setup, crashed_journal,
                                         tmp_path):
    """Recovery is deterministic: two independent engines replaying
    copies of the same crashed journal emit identical streams — and both
    match the unfailed oracle."""
    path, reqs, ml, base = crashed_journal
    outs = []
    for tag in ("a", "b"):
        p = tmp_path / f"journal_{tag}.jsonl"
        shutil.copy(path, p)
        jr = RequestJournal.load(str(p))
        fleet = _fleet(setup, replicas=1, max_len=ml, burst_cap=2,
                       migrate="reingest", journal=jr)
        fin, st = fleet.run(reqs)
        assert st["journal_replayed"] >= 1
        outs.append(_toks(fin))
        jr.close()
    assert outs[0] == outs[1] == base


def test_journal_torn_tail_dropped_and_truncated(tmp_path):
    p = tmp_path / "j.jsonl"
    jr = RequestJournal(str(p))
    jr.append("admit", rid=0)
    jr.append("tokens", rid=0, toks=[1, 2])
    jr.close()
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"kind":"tok')                     # crash mid-append
    j2 = RequestJournal.load(str(p))
    assert [r["kind"] for r in j2.records] == ["admit", "tokens"]
    assert j2.emitted(0) == [1, 2]
    # the torn bytes are gone from the FILE too: appending after
    # recovery must not concatenate onto a half-written line
    j2.append("tokens", rid=0, toks=[3])
    j2.close()
    j3 = RequestJournal.load(str(p))
    assert j3.emitted(0) == [1, 2, 3]


def test_journal_whole_record_without_newline_is_torn(tmp_path):
    """A parseable last line whose newline never landed is the same
    lost append quantum — dropped, so the next append cannot corrupt."""
    p = tmp_path / "j.jsonl"
    jr = RequestJournal(str(p))
    jr.append("tokens", rid=0, toks=[1])
    jr.close()
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"kind":"tokens","rid":0,"toks":[9]}')   # no newline
    j2 = RequestJournal.load(str(p))
    assert j2.emitted(0) == [1]


def test_journal_midfile_corruption_is_hard_error(tmp_path):
    p = tmp_path / "j.jsonl"
    jr = RequestJournal(str(p))
    jr.append("admit", rid=0)
    jr.append("finish", rid=0, toks=[1])
    jr.close()
    lines = p.read_text().splitlines()
    p.write_text(lines[0] + "\n" + "NOT JSON\n" + lines[1] + "\n")
    with pytest.raises(ValueError, match="corrupt at byte"):
        RequestJournal.load(str(p))


# ---------------------------------------------------------------------------
# supervisor + topology satellites (no model needed)
# ---------------------------------------------------------------------------
def test_run_with_restarts_attempt_log_names_replica():
    made = []

    class Fleet:
        def __init__(self):
            self.resets = 0

        def reset_monitors(self):
            self.resets += 1

        def run(self):
            raise ReplicaLostError("replica 1 killed at burst 3",
                                   replica=1, burst=3)

    def mk():
        f = Fleet()
        made.append(f)
        return f

    with pytest.raises(ReplicaLostError) as ei:
        run_with_restarts(mk, max_restarts=1)
    log = ei.value.attempt_log
    assert [(a, t, r) for a, t, r, _ in log] == \
           [(0, "ReplicaLostError", 1), (1, "ReplicaLostError", 1)]
    assert all("burst 3" in msg for _, _, _, msg in log)
    assert [f.resets for f in made] == [1, 1]       # fresh monitors per
                                                    # attempt, every time


def test_replica_meshes_meshless():
    assert meshmod.replica_meshes(None, 3) == [None, None, None]
    with pytest.raises(ValueError, match="replica count"):
        meshmod.replica_meshes(None)
    with pytest.raises(ValueError, match="replica count"):
        meshmod.replica_meshes(None, 0)


def test_replicated_engine_validation():
    with pytest.raises(ValueError, match="swap|reingest"):
        ReplicatedEngine(None, None, replicas=1, migrate="teleport")


# ---------------------------------------------------------------------------
# the session trace flavor + the HA soak over it
# ---------------------------------------------------------------------------
def test_session_trace_growing_shared_prefix():
    n, slots, plen, gen = 12, 3, 16, 16
    reqs = synthetic_trace(n, slots, plen, gen, 5000, flavor="session")
    assert [r.rid for r in reqs] == list(range(n))
    worst = plen + 2 * (gen // 4 + max(1, plen // 4))
    assert max(r.prompt_len for r in reqs) <= worst
    for s in range(n // 3):
        turns = reqs[3 * s:3 * s + 3]
        for a, b in zip(turns, turns[1:]):
            # turn t re-sends turn t-1's whole conversation as prefix
            assert list(b.tokens[:a.prompt_len]) == list(a.tokens)
            assert b.prompt_len >= a.prompt_len + a.max_new + 1
            assert b.arrival >= a.arrival + a.max_new
        assert [t.priority for t in turns] == [0, 0, 1]
        assert all(t.no_degrade == (s % 5 == 3) for t in turns)
    # deterministic: the HA soak replays it bit-identically
    assert synthetic_trace(n, slots, plen, gen, 5000,
                           flavor="session") == reqs
    with pytest.raises(ValueError, match="chat|soak|session"):
        synthetic_trace(4, 2, 8, 8, 100, flavor="bogus")


def test_ha_soak_session_drains_through_kill(setup):
    """The HA soak: a multi-turn session trace served by a 2-replica
    fleet with a journal, one replica killed mid-run — every request
    (including later turns of the victim's sessions) drains to its full
    budget on the survivor with the tokens of the unfailed fleet, nothing
    stuck, everything journaled."""
    model, _ = setup
    reqs = synthetic_trace(10, 3, 16, 16, model.cfg.vocab,
                           flavor="session")
    ml = max(r.prompt_len + r.max_new for r in reqs)
    jr = RequestJournal()
    plan = ReplicaFaultPlan(replica=1, at_burst=2, mode="kill")
    fleet = _fleet(setup, slots=3, max_len=ml, burst_cap=2,
                   migrate="reingest", replica_fault=plan, journal=jr)
    fin, st = fleet.run(reqs)
    assert len(fin) == len(reqs)
    assert [f.rid for f in fin] == [r.rid for r in reqs]
    assert all(len(f.tokens) == r.max_new for r, f in zip(reqs, fin))
    assert st["ha_kills"] == 1 and st["ha_migrations"] >= 1
    assert st["heartbeats"][1]["status"] == "dead"
    c = jr.counts()
    assert c["finish"] == len(reqs)
    assert c.get("migrate", 0) == st["ha_migrations"]
    assert c["replica_lost"] == 1
    base, _ = _fleet(setup, slots=3, max_len=ml, burst_cap=2,
                     migrate="reingest").run(reqs)
    assert _toks(fin) == _toks(base)
