"""Continuous-batching serving engine: admission, chunked prefill, bursts,
preemption, backpressure and transprecision graceful degradation.

The serving-loop half of the repo's energy-proportionality story.  PR 1-4
made every LAYER of the stack length-proportional — per-row ``kv_len``
vectors prune each sequence's attention walk, the paged pool makes HBM
scale with live tokens, EOS freezing stops a finished row's outputs — but
the LOOP still paid batch-max cost everywhere: generation was a fixed-trip
scan that kept stepping EOS-frozen rows to ``max_new_tokens``, a finished
row's pages stayed live until the whole batch exited, and new requests
waited for a full batch teardown.  This module closes that gap:

  * **Admission** — a host-side loop over a request queue.  A finished
    row's pages go back to the ``PageAllocator`` the round it finishes
    (``decode_burst`` exits the compiled loop that round), and the freed
    slot is refilled from the queue mid-generation.  Admission reuses the
    traced per-row write-index/``kv_len``/block-table plumbing, so slot
    churn never retraces: ONE compiled burst program serves the whole run.
  * **Chunked prefill** — an admitted prompt is consumed in fixed-width
    chunks through the paged flash read path
    (``Model.prefill_chunk``/``flash_attention(block_table=)``), one chunk
    per round, interleaved with single-round decode bursts so ongoing
    streams are never stalled behind a long new prompt.
  * **Page accounting** — pages are allocated LAZILY (prompt pages at
    admission, one page per row as its length crosses a page boundary), so
    the allocator's ``peak_live`` high-water mark tracks the sum of live
    sequence lengths, not ``slots x max_len``.  Admission reserves each
    request's worst case (``num_pages(prompt + budget)``) against the pool.

Overload is HANDLED, not assumed away (the FPnew stance: when resources
are tight, drop to a cheaper operating point instead of failing):

  * **Priorities + deadlines** — ``Request.priority`` orders admission
    (higher first; FIFO within a class), ``Request.deadline`` (a round
    number) bumps an at-risk request's effective priority and is
    accounted per request at finish (``Finished.deadline_miss``).
  * **Preemption** — when ``try_alloc`` fails or a higher-priority
    request can't fit, the weakest resident row is evicted: its pages are
    freed and it re-enters the queue.  ``preempt="free"`` re-ingests the
    victim's prompt + already-emitted tokens through the chunked-prefill
    path on resume (chunk boundaries are invisible, so a resumed row's
    remaining tokens are bit-identical to an un-preempted run);
    ``preempt="swap"`` copies its live K/V pages to a host-side numpy
    store instead and restores them on re-admission (no recompute).
  * **Degradation before shedding** — with ``degrade_fmt`` set (e.g.
    ``"fp8"``), a swapped victim's pages are stored in that format's
    native container on the host and widened back on resume — the paper's
    transprecision knob as a graceful-degradation axis.  It is tracked
    per row (``Finished.degraded``) and quality-sensitive requests refuse
    it via ``Request.no_degrade`` (they swap at full width).  When the
    pool itself is already fp8 (policy ``tp_bf16_kv8``), the round-trip
    is value-exact.
  * **Shedding with backoff** — a queue entry that cannot be placed is
    not allowed to block the loop: it is deferred with jittered
    exponential backoff (deterministic per rid/attempt) and retried.
  * **Fault injection + watchdog** — a ``ServeFaultPlan`` deterministically
    injects page-pool exhaustion episodes, slow-burst stragglers (flagged
    by a ``StragglerMonitor``) and NaN-poisoned logits inside the compiled
    burst (masked-and-counted, or fail-fast ``PoisonedLogitsError``);
    a ``ServeWatchdog`` turns a livelocked loop or a non-progressing
    burst into a clean ``EngineStuckError`` instead of a hang.
  * **Flag-driven precision escalation** — with an ``EscalationPolicy``,
    every cache write carries FPnew-style IEEE exception telemetry: the
    burst accumulates per-row OF/UF flag counts from the write-side CONV
    stage (saturating casts keep overflowed values finite, so logits never
    poison), and when a row's pressure crosses the policy threshold the
    scheduler escalates its KV format one ladder rung (fp8 -> fp16 -> ...)
    via the free-and-reingest path — the inverse of degradation, refusable
    per request (``Request.no_escalate``) and deferred under page pressure.
  * **SDC-checked swap** — every swapped-out page payload carries a CRC32
    computed at swap-out; swap-in verifies it, and a corrupted payload
    (bit flips in host memory — silent data corruption) is detected 100%
    of the time and recovered by falling back to free-and-reingest, which
    recomputes the K/V instead of restoring damaged bytes.

Dead-slot discipline (why idle/prefilling/finished slots are safe): every
row writes decode K/V only through its OWN table row, and a cache slot
becomes live for attention only AFTER the real token write to it — so
garbage writes (idle slots parked at ``max_len - 1``, frozen rows, pad
tails of prefill chunks) land either on the reserved scratch page or on
dead slots that real writes overwrite before any mask lets them be read.

The driver is deliberately host-side Python: admission, page churn,
preemption and fault release happen at burst boundaries, between compiled
steps, never inside them — the same boundary the ``PageAllocator``
already lives at.

Replica-level fault tolerance rides the same boundary.  ``run`` is a thin
wrapper over a re-entrant ``start()`` / ``step()`` / ``finalize()`` state
machine, so a fleet host (``ReplicatedEngine``) can interleave replicas
one scheduler iteration at a time and react to a replica dying MID-RUN:

  * **Failure injection** — a ``ReplicaFaultPlan`` deterministically
    kills a replica at a chosen burst (``ReplicaLostError`` raised
    through the burst dispatch: device memory gone) or hangs it (the
    replica stops stepping; the fleet's heartbeat view declares it dead
    after missed beats, device memory still readable).
  * **Live-request migration** — a dead replica's residents are captured
    by the SAME preemption machinery (``evacuate``): swap-to-host page
    payloads (CRC32-verified, tagged with their pool's provenance) become
    portable continuation blobs a survivor ``adopt``s into its own
    disjoint pool, with free-and-reingest as the fallback when the
    victim's pages are unreachable — so a migrated request's remaining
    tokens are bit-identical to the unfailed run.
  * **Crash-consistent journal** — with a ``launch/journal.py``
    ``RequestJournal`` attached, every admission, per-burst emitted-token
    delta, preempt/migrate/escalation event and completion is recorded
    AFTER it happened; a full restart (``train.fault.run_with_restarts``)
    replays unfinished requests from their last journaled token through
    the reingest resume path, bit-parity with the unfailed run.

``python -m repro.launch.serve --continuous`` drives this end to end.
"""
from __future__ import annotations

import dataclasses
import time
import zlib
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core.policy import EscalationPolicy
from ..train.fault import (EngineStuckError, PoisonedLogitsError,
                           ReplicaFaultPlan, ReplicaLostError,
                           ServeFaultPlan, ServeWatchdog, StragglerMonitor)
from .spans import Spans


def _crc_blobs(blobs: list) -> list:
    """Per-layer (crc32(k), crc32(v)) checksums of swap payloads."""
    return [(zlib.crc32(k.tobytes()), zlib.crc32(v.tobytes()))
            for k, v in blobs]


@dataclasses.dataclass(frozen=True)
class Request:
    """One queued generation request.

    ``arrival`` is in DECODE ROUNDS (the engine's logical clock): the
    request becomes visible to admission once that many rounds have run —
    a deterministic stand-in for wall-clock arrival traces.  ``priority``
    orders admission and picks preemption victims (higher wins; FIFO
    within a class).  ``deadline`` is an absolute round number: finishing
    after it counts a deadline miss, and a request that can no longer
    make it gains one effective priority level (SLO-at-risk boost).
    ``no_degrade`` marks a quality-sensitive request that refuses the
    fp8 swap-store degradation (it is swapped at full width instead).
    ``no_escalate`` refuses flag-driven KV-precision escalation (a
    latency-sensitive request that prefers saturated-but-cheap KV over a
    reingest pause keeps its admission rung).  ``spec_k`` caps this
    request's speculative draft depth below the engine's (``None`` =
    engine default) and ``no_speculate`` opts the request out of
    drafting entirely — it still rides the speculative burst program,
    but with a per-row cap of 0 its every round is plain greedy decode."""
    rid: int
    tokens: Sequence[int]          # prompt token ids (>= 1)
    max_new: int                   # generation budget incl. the first token
    arrival: int = 0
    priority: int = 0
    deadline: Optional[int] = None
    no_degrade: bool = False
    no_escalate: bool = False
    spec_k: Optional[int] = None
    no_speculate: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.tokens)


@dataclasses.dataclass
class Finished:
    """A served request: ``tokens`` holds the generated ids (first token
    included; a ``stop_token`` hit keeps the stop as the last element).
    The robustness trail rides along: how often the row was preempted or
    shed-deferred, whether its swapped K/V was format-degraded, and
    whether it met its deadline."""
    rid: int
    prompt_len: int
    tokens: List[int]
    admit_round: int
    finish_round: int
    slot: int
    preemptions: int = 0
    sheds: int = 0
    degraded: bool = False
    deadline: Optional[int] = None
    deadline_miss: bool = False
    escalated: int = 0             # final escalation-ladder level (0 = base)


@dataclasses.dataclass
class _Resume:
    """A preempted request's continuation state.  ``blobs`` present: the
    swap-to-host path (per-layer (k, v) page payloads covering ``written``
    tokens, possibly stored in the degrade format).  ``blobs`` absent:
    the free-and-reingest path — the prompt plus all but the last emitted
    token are re-fed through chunked prefill, and the last emitted token
    is re-fed through the normal decode round, so every K/V byte and
    every subsequent sample reproduces the un-preempted run.
    ``checksums`` (swap path): per-layer CRC32 pairs computed at swap-out,
    verified before swap-in — a mismatch means the host payload was
    silently corrupted, and the engine falls back to reingest.
    ``tag`` (swap path): the payload's pool provenance
    (``models.paged.SwapBlobTag``) — checked against the receiving pool
    before any cross-replica install."""
    emitted: List[int]
    blobs: Optional[list]
    written: int
    degraded: bool
    checksums: Optional[list] = None
    tag: Optional[object] = None


@dataclasses.dataclass
class _QEntry:
    """Queue bookkeeping around a Request: backoff gate, shed/preempt
    counters and (after a preemption) the resume state.  ``esc_level`` /
    ``esc_pressure`` persist the request's escalation rung and accumulated
    OF/UF flag pressure across preemptions (the rung is a property of the
    REQUEST, not the slot it happens to occupy).  ``enqueued_ns`` /
    ``admitted_ns`` / ``first_ns`` (``perf_counter_ns``) are when the
    request first entered a queue (``start()`` or ``adopt()``), was first
    admitted, and had its first token on the host: the ends of its
    ``engine.queued``, ``engine.first_token`` and ``engine.finish``
    marks."""
    req: Request
    not_before: int
    sheds: int = 0
    preemptions: int = 0
    degraded: bool = False
    resume: Optional[_Resume] = None
    esc_level: int = 0
    esc_pressure: tuple = (0, 0)
    esc_refused: bool = False
    enqueued_ns: Optional[int] = None
    admitted_ns: Optional[int] = None
    first_ns: Optional[int] = None


def _finished_from_record(rec: dict) -> Finished:
    """Rebuild a ``Finished`` from its journal ``finish`` record — the
    restart path for a request that completed before the crash (its
    tokens need no re-serving)."""
    return Finished(
        rid=rec["rid"], prompt_len=rec.get("prompt_len", 0),
        tokens=list(rec["toks"]),
        admit_round=rec.get("admit_round", 0),
        finish_round=rec.get("finish_round", 0),
        slot=rec.get("slot", -1),
        preemptions=rec.get("preemptions", 0),
        sheds=rec.get("sheds", 0),
        degraded=bool(rec.get("degraded", False)),
        deadline=rec.get("deadline"),
        deadline_miss=bool(rec.get("deadline_miss", False)),
        escalated=rec.get("escalated", 0))


def synthetic_trace(n_req: int, slots: int, prompt_len: int, gen: int,
                    vocab: int, seed: int = 2,
                    flavor: str = "chat") -> List[Request]:
    """Deterministic workloads for the continuous-batching A/B and the
    robustness soak.

    ``flavor="chat"`` (default, unchanged): the mixed-length /
    mixed-budget / mixed-arrival heavy tail of benchmarks/serve_decode.py
    — every 8th request in the first 3/4 of the queue is LONG (budget
    ``gen``); the rest cycle short budgets (``gen/16``, ``gen/8``,
    ``gen/4``).  Prompt lengths cycle 1/4..4/4 of ``prompt_len``.
    Arrivals: the first ``slots`` requests at round 0, then clumps of
    four every ``gen/16`` rounds.

    ``flavor="soak"``: the overload scenario — arrivals in bursts of
    eight (far more than ``slots``), every 5th request a LONG document
    (full ``prompt_len``), every 4th a long budget, priorities mixed over
    {0,1,2}, deadlines on the priority-2 tier (tight enough to bind under
    faults), and every 11th request quality-sensitive (``no_degrade``).
    Driven with a constrained page pool + a ``ServeFaultPlan``, this is
    the trace that must drain to completion with zero stuck requests.

    ``flavor="session"``: multi-turn chat — requests group into sessions
    of up to three turns over a GROWING shared prefix: turn ``t``'s
    prompt is turn ``t-1``'s prompt + its (simulated) answer + a fresh
    user chunk, and turn ``t`` arrives only after turn ``t-1``'s budget
    could have drained.  Worst-case prompt length is therefore
    ``prompt_len + 2 * (gen // 4 + max(1, prompt_len // 4))`` — size
    ``max_len`` accordingly.  This is the trace the HA soak migrates:
    a session's later turns re-enter the queue carrying real shared
    history, so a killed replica's in-flight turn must resume elsewhere
    mid-conversation."""
    rng = np.random.RandomState(seed)
    fr_len = (0.25, 0.5, 0.75, 1.0)
    shorts = (gen // 16, gen // 8, gen // 4)
    reqs = []
    if flavor == "session":
        step_gap = max(2, gen // 8)
        rid = s = 0
        while rid < n_req:
            base_len = max(1, int(prompt_len * fr_len[s % 4]))
            hist = rng.randint(0, vocab, size=base_len).tolist()
            arrival = (s // max(1, slots)) * step_gap
            for t in range(min(3, n_req - rid)):
                budget = max(2, shorts[(s + t) % 3])
                reqs.append(Request(
                    rid=rid, tokens=list(hist), max_new=budget,
                    arrival=arrival, priority=(1 if t == 2 else 0),
                    no_degrade=(s % 5 == 3)))
                rid += 1
                # the turn's simulated answer + the next user message
                # extend the shared prefix the following turn re-sends
                hist += rng.randint(0, vocab, size=budget).tolist()
                hist += rng.randint(0, vocab,
                                    size=max(1, prompt_len // 4)).tolist()
                arrival += budget + step_gap
            s += 1
        return reqs
    if flavor == "soak":
        for i in range(n_req):
            plen = (prompt_len if i % 5 == 0
                    else max(1, int(prompt_len * fr_len[i % 4])))
            budget = gen if i % 4 == 0 else max(2, shorts[i % 3])
            arrival = (i // 8) * max(2, gen // 8)
            pri = 2 if i % 7 == 3 else (1 if i % 3 == 0 else 0)
            deadline = (arrival + 4 * budget + 2 * max(2, gen // 8)
                        if pri == 2 else None)
            reqs.append(Request(
                rid=i, tokens=rng.randint(0, vocab, size=plen).tolist(),
                max_new=budget, arrival=arrival, priority=pri,
                deadline=deadline, no_degrade=(i % 11 == 7)))
        return reqs
    if flavor != "chat":
        raise ValueError(f"flavor must be chat|soak|session, got {flavor!r}")
    for i in range(n_req):
        is_long = (i % 8 == 0) and i < (3 * n_req) // 4
        budget = gen if is_long else max(2, shorts[i % 3])
        plen = max(1, int(prompt_len * fr_len[i % 4]))
        arrival = (0 if i < slots
                   else ((i - slots) // 4 + 1) * max(2, gen // 16))
        reqs.append(Request(
            rid=i, tokens=rng.randint(0, vocab, size=plen).tolist(),
            max_new=budget, arrival=arrival))
    return reqs


_FAR = 1 << 30          # "no deadline" sort key


class ContinuousEngine:
    """Continuous-batching scheduler over ``slots`` paged batch rows.

    The model must be paged (``cfg.paged_kv``; attention-mixer archs
    only).  Requests must satisfy ``prompt_len + max_new <= max_len`` and
    ``max_new >= 1``.  Greedy by default; ``temperature``/``top_k``/
    ``top_p`` enable sampling with one PRNG key threaded deterministically
    through every sampling site (same queue -> same tokens).
    ``repetition_penalty``/``presence_penalty`` apply the same seen-token
    discounts as ``Model.generate`` (the count histograms ride the burst
    carry; the host re-seeds them across bursts and preemptions).

    Robustness knobs: ``preempt`` picks the eviction mechanism
    (``"free"`` re-ingests on resume, ``"swap"`` round-trips live pages
    through a host-side numpy store); ``degrade_fmt`` stores swapped
    pages in a narrow format (fp8) unless the request opted out;
    ``shed=False`` restores head-of-line blocking admission (no backoff
    deferrals); ``fault_plan`` injects deterministic faults; the
    watchdog aborts cleanly (``EngineStuckError``) after
    ``watchdog_patience`` loop iterations without progress."""

    def __init__(self, model, params, *, slots: int, max_len: int,
                 chunk: int = 32, n_pages: Optional[int] = None,
                 stop_token: Optional[int] = None, temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 seed: int = 0, burst_cap: int = 64,
                 prefill_rounds: int = 2, admit_wave: int = 2, mesh=None,
                 repetition_penalty: Optional[float] = None,
                 presence_penalty: Optional[float] = None,
                 preempt: str = "free",
                 degrade_fmt: Optional[str] = None,
                 shed: bool = True, shed_base: int = 2, shed_cap: int = 64,
                 min_resident: int = 2,
                 fault_plan: Optional[ServeFaultPlan] = None,
                 watchdog_patience: int = 200,
                 escalate: Optional[EscalationPolicy] = None,
                 spec_k: int = 0,
                 draft_repeats: Optional[int] = None,
                 draft_policy=None,
                 replica_id: int = 0,
                 replica_fault: Optional[ReplicaFaultPlan] = None,
                 journal=None):
        import functools

        import jax
        import jax.numpy as jnp

        from ..models.paged import PageAllocator, num_pages
        from ..models.transformer import (apply_penalties, caches_with_table,
                                          init_caches, sample_token,
                                          sanitize_logits)

        cfg = model.cfg
        if not cfg.paged_kv:
            raise ValueError("ContinuousEngine requires cfg.paged_kv "
                             "(admission allocates pages, not batch rows)")
        why = cfg.paged_unsupported_reason()
        if why is not None:
            raise ValueError(f"continuous batching is unsupported for "
                             f"{cfg.name}: {why} cannot page its cache")
        if preempt not in ("free", "swap"):
            raise ValueError(f"preempt must be free|swap, got {preempt!r}")
        assert slots >= 1 and chunk >= 1 and burst_cap >= 1
        self.model, self.params, self.mesh = model, params, mesh
        self.slots, self.max_len, self.chunk = slots, max_len, chunk
        self.page = cfg.page_size
        self.max_pages = num_pages(max_len, self.page)
        self.n_pages = (slots * self.max_pages + 1 if n_pages is None
                        else n_pages)
        self.stop_token = stop_token
        self.temperature, self.top_k, self.top_p = temperature, top_k, top_p
        self.seed, self.burst_cap = seed, burst_cap
        self.prefill_rounds = prefill_rounds
        self.admit_wave = max(1, admit_wave)
        self.repetition_penalty = repetition_penalty
        self.presence_penalty = presence_penalty
        self._use_pen = ((repetition_penalty is not None
                          and repetition_penalty != 1.0)
                         or (presence_penalty is not None
                             and presence_penalty != 0.0))
        self.preempt_mode = preempt
        self.degrade_fmt = degrade_fmt
        self._swap_dtype = None
        if degrade_fmt is not None:
            from ..models.attention import kv_swap_dtype
            self._swap_dtype = kv_swap_dtype(degrade_fmt)
        self.shed, self.shed_base, self.shed_cap = shed, shed_base, shed_cap
        self.min_resident = max(0, min_resident)
        self.fault_plan = fault_plan
        self.watchdog_patience = watchdog_patience
        # replica-level fault tolerance: identity in the fleet, the kill
        # plan consulted at every burst dispatch, the shared request
        # journal, and the pool-provenance fields swap blobs are tagged
        # with (models.paged.SwapBlobTag)
        self.replica_id = int(replica_id)
        self.replica_fault = replica_fault
        self.journal = journal
        from ..models.attention import kv_store_dtype
        self._pool_dtype = np.dtype(kv_store_dtype(model.policy))
        self.escalate = escalate
        self._esc_fmts = None
        if escalate is not None:
            if not isinstance(escalate, EscalationPolicy):
                raise TypeError(f"escalate must be an EscalationPolicy, "
                                f"got {type(escalate).__name__}")
            from ..models.attention import kv_store_dtype
            pool_dt = np.dtype(kv_store_dtype(model.policy))
            if model.policy.kv_fmt is not None or pool_dt != np.float32:
                raise ValueError(
                    f"escalation needs an f32 KV pool with no kv_fmt (the "
                    f"write path snaps each row to its OWN ladder rung "
                    f"inside a shared wide container); policy "
                    f"{model.policy.name!r} stores KV as {pool_dt}")
            self._esc_fmts = escalate.formats
        self.spec_k = int(spec_k)
        self.draft_repeats = draft_repeats
        if draft_policy is not None and isinstance(draft_policy, str):
            from ..core.policy import get_policy
            draft_policy = get_policy(draft_policy)
        self.draft_policy = draft_policy
        if self.spec_k:
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            model.speculate_check()
            if temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only (acceptance is "
                    "defined against the verify argmax); temperature "
                    f"{temperature} would change the sampled stream")
            if self._use_pen:
                raise ValueError(
                    "speculative decoding does not compose with "
                    "repetition/presence penalties yet: the verify chunk "
                    "scores k+1 positions against ONE histogram snapshot, "
                    "so mid-chunk accepts would see stale counts")
        self._num_pages = num_pages
        self._jnp, self._jax = jnp, jax

        self.alloc = PageAllocator(self.n_pages)
        self.scratch = self.alloc.alloc(1)[0]      # dead-write sink, forever
        self._table = np.full((slots, self.max_pages), self.scratch,
                              np.int32)
        self._table_dev = jnp.asarray(self._table)
        self._table_dirty = False
        self.caches = init_caches(cfg, slots, max_len, model.policy,
                                  page_table=self._table,
                                  n_pages=self.n_pages)
        # placement: with a mesh, pin the params (Megatron col/row rules
        # over the model axis) and the paged pools (head-sharded) to the
        # mesh's devices up front — a one-device replica sub-mesh lands
        # its whole engine on THAT device, not the default one, and the
        # jitted burst/chunk programs keep those shardings through every
        # donated carry instead of rediscovering them per dispatch.
        # batch_axes=() because ONE engine is one data replica: its slots
        # never batch-shard, and its block tables stay host-managed and
        # model-replicated.  Params already in these shardings (e.g.
        # ``Model.init(key, mesh=...)``) are not copied.
        self.tp = (mesh.shape["model"]
                   if mesh is not None
                   and "model" in getattr(mesh, "axis_names", ()) else 1)
        if mesh is not None:
            from ..models.sharding import cache_specs, named, param_specs
            self.params = jax.device_put(
                params, named(mesh, param_specs(params,
                                                model_size=self.tp)))
            self.caches = jax.device_put(
                self.caches,
                named(mesh, cache_specs(cfg, self.caches, batch=slots,
                                        mesh=mesh, batch_axes=())))
        # per-slot host state (the scheduler's view; device state mirrors
        # it through the traced burst arguments)
        self.pos = np.full((slots,), max_len - 1, np.int32)
        self.lens = np.zeros((slots,), np.int32)
        self.done = np.ones((slots,), bool)
        self.limit = np.zeros((slots,), np.int32)
        self.tok = np.zeros((slots, 1), np.int32)
        self._req: List[Optional[Request]] = [None] * slots
        self._entry: List[Optional[_QEntry]] = [None] * slots
        self._owned: List[List[int]] = [[] for _ in range(slots)]
        self._prog = np.zeros((slots,), np.int32)   # prefill progress
        self._emitted: List[List[int]] = [[] for _ in range(slots)]
        # tokens chunked prefill consumes: the prompt, or on a reingest
        # resume the prompt + previously emitted tokens (minus the last)
        self._ingest: List[List[int]] = [[] for _ in range(slots)]
        self._resume_tok: List[Optional[int]] = [None] * slots
        self._admit_round = np.zeros((slots,), np.int32)
        self._cnt = (np.zeros((slots, model.vocab_out), np.int32)
                     if self._use_pen else None)
        # numerical-health state: each slot's escalation-ladder rung and
        # its accumulated OF/UF write-flag pressure (host mirror of the
        # telemetry the burst carries back)
        self.kv_levels = np.zeros((slots,), np.int32)
        self.flag_pressure = np.zeros((slots, 2), np.int64)
        # per-slot speculative draft cap (min(engine spec_k, request
        # spec_k); 0 = plain decode row inside the speculative batch)
        self._spec_rows = np.zeros((slots,), np.int32)
        self._pending: List[_QEntry] = []
        self._held: List[int] = []      # fault-plan page grab
        self._release_at: Optional[int] = None
        # run state (armed by start(), advanced by step(), closed by
        # finalize() — attributes, not locals, so a fleet host can
        # interleave replicas one step at a time)
        self._results: Dict[int, Finished] = {}
        self._requests: List[Request] = []
        self._counters: Dict[str, int] = {}
        self._round_no = self._decode_rounds = 0
        self._occ_accum = self._bursts = 0
        self._key = None
        # diagnostics: set to a dict to capture, per request id, the
        # last-prompt-token logits its first token was sampled from (after
        # the non-finite guard and penalties) — parity checks against
        # ``Model.generate``; None skips the device-to-host copy
        self.prompt_logits: Optional[Dict[int, np.ndarray]] = None
        # host spans of every step() phase and per-request marks
        # (launch/spans.py), reset by start() with the counters
        self.spans = Spans()
        self.reset_monitors()

        use_pen = self._use_pen
        rp, pp = repetition_penalty, presence_penalty
        esc_fmts = self._esc_fmts
        ovf_scale = float(getattr(fault_plan, "overflow_scale", 1.0)
                          if fault_plan is not None else 1.0)

        def burst(params, caches, table, state, counts, key):
            # ONE packed [10, B] int32 upload carries the whole scheduler
            # state (tok, pos, lens, limit, done, n_max, watch, poison,
            # kv_levels, ovf_round) and the table is installed inside the
            # compiled region — per-burst host->device traffic stays 2-3
            # small transfers, independent of model size
            caches = caches_with_table(caches, table)
            esc_kw = ({} if esc_fmts is None else
                      dict(esc_fmts=esc_fmts, kv_levels=state[8],
                           ovf_at=state[9, 0], ovf_scale=ovf_scale))
            r = model.decode_burst(
                params, state[0][:, None], caches, state[1], state[2],
                state[4] != 0, state[3], max_len=max_len,
                out_width=burst_cap, n_max=state[5, 0],
                exit_on_finish=state[6, 0], stop_token=stop_token,
                temperature=temperature, top_k=top_k, top_p=top_p,
                key=key, mesh=mesh,
                counts=counts if use_pen else None,
                repetition_penalty=rp, presence_penalty=pp,
                poison_at=state[7, 0], guard=True, **esc_kw)
            out, n, tok, caches, pos, lens, done, key = r[:8]
            bad = r[8]
            fl = (r[-2] if esc_fmts is not None
                  else jnp.zeros((slots, 2), jnp.int32))
            # the experts read ride the state readback (row 4, column 0):
            # no transfer of their own
            n_read = jnp.zeros_like(pos).at[0].set(r[-1])
            return (out, n,
                    jnp.stack([tok[:, 0], pos, lens, done.astype(jnp.int32),
                               n_read]),
                    caches, key, bad, fl)

        spec_k_, dr_, dpol_ = self.spec_k, draft_repeats, self.draft_policy

        def spec_burst(params, caches, table, state, counts, key):
            # the speculative twin: state grows row 10 (per-row draft
            # caps) and the packed-contiguous out layout means the host
            # accounting below consumes it exactly like the plain burst
            caches = caches_with_table(caches, table)
            esc_kw = ({} if esc_fmts is None else
                      dict(esc_fmts=esc_fmts, kv_levels=state[8],
                           ovf_at=state[9, 0], ovf_scale=ovf_scale))
            r = model.speculate_burst(
                params, state[0][:, None], caches, state[1], state[2],
                state[4] != 0, state[3], spec_k=spec_k_,
                draft_repeats=dr_, k_rows=state[10], max_len=max_len,
                out_width=burst_cap * (spec_k_ + 1), n_max=state[5, 0],
                exit_on_finish=state[6, 0], stop_token=stop_token,
                key=key, mesh=mesh, guard=True, poison_at=state[7, 0],
                draft_policy=dpol_, **esc_kw)
            out, n, tok, caches, pos, lens, done, key = r[:8]
            bad = r[8]
            fl = (r[9] if esc_fmts is not None
                  else jnp.zeros((slots, 2), jnp.int32))
            return (out, n,
                    jnp.stack([tok[:, 0], pos, lens, done.astype(jnp.int32)]),
                    caches, key, bad, fl, r[-1])

        # donate the caches operand: the page pools flow through every
        # burst/chunk as pure carries and the host never reuses the
        # pre-call object, so XLA aliases them in place instead of
        # holding two full pools across each dispatch
        self._burst = jax.jit(spec_burst if self.spec_k else burst,
                              donate_argnums=(1,))
        self._sample = functools.partial(
            sample_token, temperature=temperature, top_k=top_k, top_p=top_p)
        self._with_table = caches_with_table
        self._sanitize = sanitize_logits
        self._pen = functools.partial(apply_penalties,
                                      repetition_penalty=rp,
                                      presence_penalty=pp)
        self._chunk_fns: Dict[tuple, object] = {}

    # -- helpers ----------------------------------------------------------
    def reset_monitors(self) -> None:
        """Fresh watchdog + straggler-monitor state.  Called at engine
        construction, at every ``run()`` start, and by
        ``train.fault.run_with_restarts`` before each restart attempt —
        a restarted run must not inherit a pre-crash straggler EWMA (it
        would mis-flag warm-up bursts) or stale watchdog stall counts."""
        self.watchdog = ServeWatchdog(self.watchdog_patience)
        self.monitor = StragglerMonitor()

    def _chunk_fn(self, off: int, m: int, capture: bool = False):
        """Jitted prefill chunk for an ``m``-slot admission wave at static
        offset ``off`` (offsets step in multiples of ``self.chunk``, waves
        are at most ``slots`` wide, so few programs ever compile; slot
        indices, chunk lengths, tables and count histograms are traced —
        admission never retraces).  Folds the wave's first-token sampling
        into the same dispatch: the returned [m] tokens are each row's
        sample off its last live chunk position (only meaningful for a row
        whose final chunk this is), guarded against non-finite logits and
        penalized like every other sampling site.  ``capture`` (the
        ``prompt_logits`` diagnostic) compiles a twin that also returns
        those [m, V] logits; the serving program never outputs them."""
        fn = self._chunk_fns.get((off, m, capture))
        if fn is None:
            model, sample, mesh = self.model, self._sample, self.mesh
            with_table = self._with_table
            sanitize, pen, use_pen = self._sanitize, self._pen, self._use_pen
            esc_fmts, jnp = self._esc_fmts, self._jnp
            named_scope = self._jax.named_scope

            def chunk_step(params, caches, table, t, meta, counts, key):
                caches = with_table(caches, table)
                esc_kw = ({} if esc_fmts is None else
                          dict(esc_fmts=esc_fmts, kv_levels=meta[2]))
                r = model.prefill_chunk(
                    params, t, caches, q_offset=off, row=meta[0],
                    chunk_lens=meta[1], mesh=mesh, **esc_kw)
                lg, caches = r[0], r[1]
                fl = (r[2] if esc_fmts is not None
                      else jnp.zeros((t.shape[0], 2), jnp.int32))
                with named_scope("sample"):
                    lgv, bad = sanitize(lg[:, -1])
                    if use_pen:
                        lgv = pen(lgv, counts)
                    tok = sample(lgv, key)
                out = (tok, bad, caches, fl)
                return out + (lgv,) if capture else out

            fn = self._jax.jit(chunk_step, donate_argnums=(1,))
            self._chunk_fns[(off, m, capture)] = fn
        return fn

    def _reserved_pages(self) -> int:
        """Worst-case pages of every admitted-but-unfinished request —
        the admission guard that keeps lazy mid-burst allocation from
        failing in steady state (injected exhaustion can still race it;
        ``try_alloc`` is the ground truth and preemption the recovery).
        With speculation on, every resident row's verify chunk writes up
        to ``spec_k`` slots past its budget (dead until accepted), so
        the worst case grows by the draft lookahead."""
        return sum(self._num_pages(r.prompt_len + r.max_new + self.spec_k,
                                   self.page)
                   for r in self._req if r is not None)

    def _ensure_pages(self, b: int, last_idx: int) -> bool:
        """Lazily allocate slot ``b``'s pages covering token slots up to
        ``last_idx`` (inclusive) — the live-length-proportional part.
        Returns False when the pool can't supply them (pressure: the
        caller preempts a victim or slot ``b`` itself and retries)."""
        want = min(last_idx, self.max_len - 1) // self.page + 1
        while len(self._owned[b]) < want:
            got = self.alloc.try_alloc(1)
            if got is None:
                return False
            self._table[b, len(self._owned[b])] = got[0]
            self._owned[b].append(got[0])
            self._table_dirty = True
        return True

    def _table_device(self):
        """Device copy of the block table, re-uploaded only when the host
        table changed (admission, lazy page allocs, recycling)."""
        if self._table_dirty:
            self._table_dev = self._jnp.asarray(self._table)
            self._table_dirty = False
        return self._table_dev

    def _prompt_hist(self, b: int) -> None:
        """Seed slot ``b``'s penalty histogram: prompt + already-emitted
        tokens (resume) — exactly the count state an un-preempted
        ``generate`` carry would hold at this point."""
        if not self._use_pen:
            return
        v = self._cnt.shape[1]
        seen = list(self._req[b].tokens) + list(self._emitted[b])
        self._cnt[b] = np.bincount(np.asarray(seen, np.int64) % v,
                                   minlength=v).astype(np.int32)

    # -- priorities, deadlines, victims -----------------------------------
    def _pending_need(self, e: _QEntry) -> int:
        """Pages an entry needs AT ADMISSION (its resume/prompt length)."""
        if e.resume is not None:
            if e.resume.blobs is not None:
                return self._num_pages(e.resume.written, self.page)
            n = e.req.prompt_len + len(e.resume.emitted) - 1
            return self._num_pages(max(1, n), self.page)
        return self._num_pages(e.req.prompt_len, self.page)

    def _eff_pending(self, e: _QEntry, round_no: int) -> int:
        """Effective priority of a queued entry: its class, +1 when its
        deadline can no longer absorb any further waiting (SLO at risk)."""
        p = e.req.priority
        if e.req.deadline is not None:
            emitted = len(e.resume.emitted) if e.resume is not None else 0
            chunks = -(-e.req.prompt_len // self.chunk)
            need = (e.req.max_new - emitted) + chunks
            if round_no + need >= e.req.deadline:
                p += 1
        return p

    def _eff_resident(self, b: int, round_no: int) -> int:
        """Effective priority of a resident row (deadline-at-risk rows
        get the same +1 boost, protecting them from preemption)."""
        r = self._req[b]
        p = r.priority
        if r.deadline is not None:
            if self.done[b]:        # still prefilling
                rem = len(self._ingest[b]) - int(self._prog[b])
                need = r.max_new + -(-max(0, rem) // self.chunk)
            else:
                need = int(self.limit[b]) - int(self.pos[b]) + 1
            if round_no + need >= r.deadline:
                p += 1
        return p

    def _victims_for(self, eff: int, round_no: int, exclude=()):
        """Resident rows preemptible by effective priority ``eff``,
        weakest first (anti-thrash: rows resident < ``min_resident``
        rounds are protected).  Ties prefer the row donating the most
        pages, then the lowest slot (deterministic)."""
        cands = [b for b in range(self.slots)
                 if self._req[b] is not None and b not in exclude
                 and round_no - int(self._admit_round[b]) >= self.min_resident
                 and self._eff_resident(b, round_no) < eff]
        return sorted(cands, key=lambda b: (self._eff_resident(b, round_no),
                                            -len(self._owned[b]), b))

    def _backoff(self, e: _QEntry, round_no: int, counters: dict) -> None:
        """Shed: defer the entry with jittered exponential backoff —
        deterministic in (seed, rid, attempt), so replays are exact."""
        delay = min(self.shed_cap, self.shed_base * (2 ** min(e.sheds, 16)))
        rng = np.random.RandomState(
            (self.seed * 1000003 + e.req.rid * 9973 + e.sheds * 97)
            & 0x7FFFFFFF)
        e.not_before = round_no + delay + int(rng.randint(0, max(1, delay)))
        e.sheds += 1
        counters["shed_events"] += 1
        if self.fault_plan is not None:
            self.fault_plan.note("shed", round=round_no, rid=e.req.rid,
                                 until=e.not_before)

    # -- preemption / swap ------------------------------------------------
    def _paged_leaves(self, caches):
        from ..models.paged import PagedKVCache
        jax = self._jax
        return [c for c in jax.tree.leaves(
                    caches, is_leaf=lambda x: isinstance(x, PagedKVCache))
                if isinstance(c, PagedKVCache)]

    def _swap_out(self, caches, ids: List[int], degrade: bool):
        """Copy the live content of ``ids`` pages (every paged layer) to
        host numpy — in the degrade format's container when allowed.
        Returns ``(blobs, nbytes, checksums)``; the CRC32s are computed
        HERE, before the payload sits in host memory, so any later bit
        flip (injected or real) is detectable at swap-in."""
        jnp = self._jnp
        idx = jnp.asarray(ids, jnp.int32)
        blobs, nbytes = [], 0
        for c in self._paged_leaves(caches):
            ax = c.k_pool.ndim - 4          # page axis (stacked adds [R,...])
            k = np.asarray(jnp.take(c.k_pool, idx, axis=ax))
            v = np.asarray(jnp.take(c.v_pool, idx, axis=ax))
            if degrade:
                k = k.astype(self._swap_dtype)
                v = v.astype(self._swap_dtype)
            blobs.append((k, v))
            nbytes += k.nbytes + v.nbytes
        return blobs, nbytes, _crc_blobs(blobs)

    @staticmethod
    def _flip_bit(blobs: list, rid: int) -> None:
        """Deterministic single-bit corruption of a swap payload (SDC
        injection): byte and bit indices derive from the rid alone, so a
        plan replays to the identical corruption."""
        k, _ = blobs[0]
        flat = np.array(k, copy=True).view(np.uint8).reshape(-1)
        flat[(rid * 2654435761) % flat.size] ^= np.uint8(1 << (rid % 8))
        blobs[0] = (flat.view(k.dtype).reshape(k.shape), blobs[0][1])

    def _swap_in(self, caches, blobs: list, ids: List[int]):
        """Write swapped page payloads back into the pools at the victim's
        NEW page ids (the table already maps them), widening from the
        swap-store dtype to the pool dtype."""
        from ..models.paged import PagedKVCache
        jax, jnp = self._jax, self._jnp
        idx = jnp.asarray(ids, jnp.int32)
        it = iter(blobs)

        def one(c):
            if not isinstance(c, PagedKVCache):
                return c
            k, v = next(it)
            ax = c.k_pool.ndim - 4
            sel = (slice(None),) * ax + (idx,)
            kp = c.k_pool.at[sel].set(jnp.asarray(k).astype(c.k_pool.dtype))
            vp = c.v_pool.at[sel].set(jnp.asarray(v).astype(c.v_pool.dtype))
            return PagedKVCache(kp, vp, c.block_table)

        return jax.tree.map(one, caches,
                            is_leaf=lambda x: isinstance(x, PagedKVCache))

    def _preempt(self, b: int, round_no: int, caches, counters: dict,
                 reason: str, force_reingest: bool = False):
        """Evict resident row ``b``: capture its continuation (swap-out or
        reingest state), free its pages and slot, and re-queue it —
        immediately re-admissible, but only where it fits.
        ``force_reingest`` bypasses the swap path even in swap mode — an
        ESCALATING row must recompute its K/V at the wider rung, not
        restore the narrow saturated bytes the telemetry just condemned."""
        e = self._entry[b]
        req = self._req[b]
        e.preemptions += 1
        counters["preemptions"] += 1
        e.esc_level = int(self.kv_levels[b])
        e.esc_pressure = (int(self.flag_pressure[b, 0]),
                          int(self.flag_pressure[b, 1]))
        if (not self.done[b] and self.preempt_mode == "swap"
                and not force_reingest):
            written = int(self.lens[b])
            keep = self._owned[b][:self._num_pages(written, self.page)]
            degrade = self.degrade_fmt is not None and not req.no_degrade
            blobs, nbytes, sums = self._swap_out(caches, keep, degrade)
            if (self.fault_plan is not None
                    and self.fault_plan.take_corrupt()):
                self._flip_bit(blobs, req.rid)
                counters["sdc_injected"] += 1
                self.fault_plan.note("sdc_inject", round=round_no,
                                     rid=req.rid, slot=b)
            from ..models.paged import SwapBlobTag
            e.resume = _Resume(emitted=list(self._emitted[b]), blobs=blobs,
                               written=written, degraded=degrade,
                               checksums=sums,
                               tag=SwapBlobTag(replica=self.replica_id,
                                               dtype=str(self._pool_dtype),
                                               page=self.page))
            if degrade:
                e.degraded = True
                counters["degraded"] += 1
            counters["preempt_swap"] += 1
            counters["swap_out_bytes"] += nbytes
        elif self._emitted[b]:
            e.resume = _Resume(emitted=list(self._emitted[b]), blobs=None,
                               written=0, degraded=False)
            counters["preempt_reingest"] += 1
        else:
            e.resume = None         # mid-prefill: restart from the prompt
            counters["preempt_restart"] += 1
        mode = ("swap" if e.resume is not None
                and e.resume.blobs is not None else "reingest")
        if self.fault_plan is not None:
            self.fault_plan.note("preempt", round=round_no, rid=req.rid,
                                 slot=b, reason=reason, mode=mode)
        if self.journal is not None:
            self.journal.append("preempt", rid=req.rid,
                                replica=self.replica_id, round=round_no,
                                reason=reason, mode=mode)
        self.alloc.free(self._owned[b])
        self._owned[b] = []
        self._table[b, :] = self.scratch
        self._table_dirty = True
        self._req[b], self._entry[b] = None, None
        self._emitted[b], self._ingest[b] = [], []
        self._prog[b], self._resume_tok[b] = 0, None
        self.pos[b], self.lens[b] = self.max_len - 1, 0
        self.done[b], self.limit[b] = True, 0
        self.kv_levels[b], self.flag_pressure[b] = 0, 0
        self._spec_rows[b] = 0
        if self._use_pen:
            self._cnt[b] = 0
        e.not_before = max(e.not_before, round_no)
        self._pending.append(e)
        return caches

    # -- admission --------------------------------------------------------
    def _admit_one(self, e: _QEntry, b: int, pages: List[int],
                   round_no: int, caches, counters: dict):
        """Install entry ``e`` into free slot ``b`` with its admission
        pages, restoring resume state (swap-in or reingest plumbing).

        Swap-in is SDC-checked: the payload's CRC32s are recomputed and
        compared against the swap-out checksums first.  A mismatch never
        reaches the pool — the resume falls back to free-and-reingest
        (recompute), which needs exactly the pages already allocated here
        (``lens == prompt + emitted - 1`` is the engine invariant, so the
        swap and reingest page needs coincide) and reproduces the
        un-preempted run bit for bit."""
        req = e.req
        if e.admitted_ns is None:
            e.admitted_ns = time.perf_counter_ns()
            self.spans.mark("engine.queued", e.enqueued_ns, e.admitted_ns,
                            rid=req.rid)
        self._table[b, :len(pages)] = pages
        self._table_dirty = True
        self._owned[b] = pages
        self._req[b], self._entry[b] = req, e
        self._admit_round[b] = round_no
        self._resume_tok[b] = None
        k = 0
        if self.spec_k and not req.no_speculate:
            k = (self.spec_k if req.spec_k is None
                 else max(0, min(self.spec_k, req.spec_k)))
        self._spec_rows[b] = k
        self.kv_levels[b] = e.esc_level
        self.flag_pressure[b] = np.asarray(e.esc_pressure, np.int64)
        rs, e.resume = e.resume, None
        if rs is not None and rs.blobs is not None:
            # provenance gate before any pool write: a payload whose tag
            # mismatches this pool's (dtype, page) must never install
            from ..models.paged import check_blob_tag
            check_blob_tag(rs.tag, dtype=self._pool_dtype, page=self.page)
        if (rs is not None and rs.blobs is not None
                and rs.checksums is not None
                and _crc_blobs(rs.blobs) != rs.checksums):
            counters["sdc_detected"] += 1
            counters["sdc_reingest"] += 1
            if self.fault_plan is not None:
                self.fault_plan.note("sdc_detect", round=round_no,
                                     rid=req.rid, slot=b)
            rs.blobs, rs.checksums = None, None
        if rs is None:
            self._ingest[b] = list(req.tokens)
            self._prog[b] = 0
            self._emitted[b] = []
        elif rs.blobs is not None:
            caches = self._swap_in(caches, rs.blobs, pages)
            self._emitted[b] = list(rs.emitted)
            self._ingest[b] = []
            self._prog[b] = np.int32(req.prompt_len)
            self.tok[b, 0] = rs.emitted[-1]
            self.pos[b] = self.lens[b] = rs.written
            self.limit[b] = req.prompt_len + req.max_new - 1
            self.done[b] = False
            counters["resumed"] += 1
        else:
            self._ingest[b] = list(req.tokens) + list(rs.emitted[:-1])
            self._prog[b] = 0
            self._emitted[b] = list(rs.emitted)
            self._resume_tok[b] = rs.emitted[-1]
            counters["resumed"] += 1
        self._prompt_hist(b)
        if self.journal is not None:
            self.journal.append("admit", rid=req.rid,
                                replica=self.replica_id, round=round_no,
                                slot=b, resumed=rs is not None,
                                emitted=len(self._emitted[b]))
        return caches

    def _admission(self, round_no: int, caches, counters: dict):
        """One admission pass: visible entries in (effective priority,
        deadline, arrival, rid) order; a candidate that doesn't fit may
        preempt strictly-weaker residents (degrading/swapping them rather
        than dropping anything), else it is shed with backoff — never
        blocking the entries behind it."""
        admitted = 0
        vis = [e for e in self._pending if e.not_before <= round_no]
        vis.sort(key=lambda e: (
            -self._eff_pending(e, round_no),
            e.req.deadline if e.req.deadline is not None else _FAR,
            e.req.arrival, e.req.rid))
        for e in vis:
            req = e.req
            worst = self._num_pages(
                req.prompt_len + req.max_new + self.spec_k, self.page)
            need = self._pending_need(e)

            def fits():
                free_slots = [b for b in range(self.slots)
                              if self._req[b] is None]
                ok = (bool(free_slots)
                      and self._reserved_pages() + worst <= self.n_pages - 1
                      and self.alloc.n_free >= need)
                return free_slots[0] if ok else None

            b = fits()
            if b is None:
                eff = self._eff_pending(e, round_no)
                for v in self._victims_for(eff, round_no):
                    caches = self._preempt(v, round_no, caches, counters,
                                           reason="pressure")
                    b = fits()
                    if b is not None:
                        break
                if b is None:
                    # shed ONLY under resource pressure (pages short while
                    # a slot sits free): a backoff there keeps the loop
                    # live.  All-slots-busy is NOT pressure — the entry
                    # just waits for the burst's wave-exit to free a slot,
                    # uncapped bursts intact (the PR-5 steady state).
                    if self.shed and any(self._req[s] is None
                                         for s in range(self.slots)):
                        self._backoff(e, round_no, counters)
                    continue
            pages = self.alloc.try_alloc(need)
            if pages is None:       # raced an injected hold: treat as shed
                if self.shed:
                    self._backoff(e, round_no, counters)
                continue
            self._pending.remove(e)
            caches = self._admit_one(e, b, pages, round_no, caches, counters)
            admitted += 1
        return admitted, caches

    # -- finish -----------------------------------------------------------
    def _finish(self, b: int, round_no: int, results: dict) -> None:
        """Page recycling: the slot's pages go back to the allocator the
        round its request finishes; the table row falls back to scratch
        and the slot is immediately admissible.  Deadline accounting and
        the robustness trail land on the Finished record here."""
        req = self._req[b]
        e = self._entry[b]
        self.spans.mark("engine.finish", e.first_ns or e.admitted_ns,
                        time.perf_counter_ns(), rid=req.rid)
        fin = Finished(
            rid=req.rid, prompt_len=req.prompt_len,
            tokens=list(self._emitted[b]),
            admit_round=int(self._admit_round[b]), finish_round=round_no,
            slot=b, preemptions=e.preemptions, sheds=e.sheds,
            degraded=e.degraded, deadline=req.deadline,
            deadline_miss=(req.deadline is not None
                           and round_no > req.deadline),
            escalated=int(self.kv_levels[b]))
        results[req.rid] = fin
        if self.journal is not None:
            self.journal.append(
                "finish", rid=req.rid, replica=self.replica_id,
                prompt_len=fin.prompt_len, toks=fin.tokens,
                admit_round=fin.admit_round, finish_round=fin.finish_round,
                slot=fin.slot, preemptions=fin.preemptions, sheds=fin.sheds,
                degraded=fin.degraded, deadline=fin.deadline,
                deadline_miss=fin.deadline_miss, escalated=fin.escalated)
        self.alloc.free(self._owned[b])
        self._owned[b] = []
        self._table[b, :] = self.scratch
        self._table_dirty = True
        self._req[b], self._entry[b] = None, None
        self._emitted[b], self._ingest[b] = [], []
        self._resume_tok[b] = None
        self.pos[b], self.lens[b] = self.max_len - 1, 0
        self.done[b], self.limit[b] = True, 0
        self.kv_levels[b], self.flag_pressure[b] = 0, 0
        self._spec_rows[b] = 0
        if self._use_pen:
            self._cnt[b] = 0

    # -- escalation -------------------------------------------------------
    def _maybe_escalate(self, active, round_no: int, caches, counters: dict):
        """Flag-pressure check after a burst: any live row whose OF or UF
        pressure crossed its threshold moves one rung up the ladder via a
        forced free-and-reingest (the saturated narrow-format bytes are
        exactly what the flags condemned — recompute, don't swap them
        back).  Refusable per request; deferred while the free list is
        shorter than the policy's ``min_free_pages`` (an escalating row
        re-prefills its whole history — the worst moment to fight
        admission for pages)."""
        esc = self.escalate
        plan = self.fault_plan
        for b in active:
            if self._req[b] is None or self.done[b]:
                continue                    # finished/evicted this round
            lvl = int(self.kv_levels[b])
            of, uf = (int(self.flag_pressure[b, 0]),
                      int(self.flag_pressure[b, 1]))
            if of < esc.of_threshold and uf < esc.uf_threshold:
                continue
            if lvl >= esc.top():
                continue                    # already at the widest rung
            e = self._entry[b]
            if self._req[b].no_escalate:
                if not e.esc_refused:
                    e.esc_refused = True
                    counters["esc_refused"] += 1
                continue
            if self.alloc.n_free < esc.min_free_pages:
                counters["esc_deferred"] += 1
                continue
            rid = self._req[b].rid
            caches = self._preempt(b, round_no, caches, counters,
                                   reason="escalate", force_reingest=True)
            e.esc_level = lvl + 1
            e.esc_pressure = (0, 0)
            counters["escalations"] += 1
            if plan is not None:
                plan.note("escalate", round=round_no, rid=rid, slot=b,
                          level=lvl + 1, of=of, uf=uf)
            if self.journal is not None:
                self.journal.append("escalate", rid=rid,
                                    replica=self.replica_id, round=round_no,
                                    level=lvl + 1)
        return caches

    # -- the serving state machine ----------------------------------------
    #
    # ``run`` = ``start`` + ``step`` until drained + ``finalize``.  The
    # split exists for the fleet host: ``ReplicatedEngine`` interleaves
    # replicas one ``step`` at a time, so a replica can die (or hang)
    # mid-run while its survivors keep stepping — ``evacuate``/``adopt``
    # then move the victim's in-flight requests over.
    def start(self, requests: Sequence[Request]) -> None:
        """Validate + enqueue ``requests`` and arm the run state.  With a
        non-empty journal attached (a restart), unfinished requests
        re-enter the queue seeded to resume from their last journaled
        token — the free-and-reingest path, so the recovery run's tokens
        are bit-identical to the run that never crashed — and finished
        ones are answered straight from their ``finish`` records."""
        jax = self._jax
        for r in requests:
            if r.prompt_len < 1 or r.max_new < 1:
                raise ValueError(f"request {r.rid}: empty prompt or budget")
            if r.prompt_len + r.max_new + self.spec_k > self.max_len:
                hint = (f" (+{self.spec_k} speculative lookahead: the "
                        f"verify chunk writes spec_k slots past the "
                        f"budget)" if self.spec_k else "")
                raise ValueError(
                    f"request {r.rid}: prompt {r.prompt_len} + budget "
                    f"{r.max_new}{hint} exceeds max_len {self.max_len}")
            worst = self._num_pages(r.prompt_len + r.max_new + self.spec_k,
                                    self.page)
            if worst > self.n_pages - 1:
                raise ValueError(
                    f"request {r.rid} can never fit the pool: needs "
                    f"{worst} pages, pool has {self.n_pages - 1} "
                    f"(+1 scratch)")
        self._requests = list(requests)
        self._results = {}
        self.alloc.reset_peak()
        plan = self.fault_plan
        if plan is not None:
            plan.reset()
        self._held, self._release_at = [], None
        self.reset_monitors()
        self.spans.reset()
        self._counters = {k: 0 for k in (
            "preemptions", "preempt_swap", "preempt_reingest",
            "preempt_restart", "resumed", "degraded", "swap_out_bytes",
            "shed_events", "poisoned_rounds", "nonfinite_prefill",
            "stragglers", "faults_exhaust", "faults_slow",
            "escalations", "esc_deferred", "esc_refused",
            "sdc_injected", "sdc_detected", "sdc_reingest",
            "spec_rounds", "spec_emitted",
            "migrated_in", "journal_replayed")}
        self._key = jax.random.key(self.seed)
        self._round_no = self._decode_rounds = 0
        self._occ_accum = self._bursts = 0
        jr = self.journal
        pend: List[_QEntry] = []
        for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            e = _QEntry(req=r, not_before=r.arrival)
            if jr is not None and jr.records:
                fr = jr.finish_record(r.rid)
                if fr is not None:
                    self._results[r.rid] = _finished_from_record(fr)
                    continue
                em = jr.emitted(r.rid)
                if em:
                    whole = (len(em) >= r.max_new
                             or (self.stop_token is not None
                                 and em[-1] == self.stop_token))
                    if whole:
                        # the crash fell between the final tokens record
                        # and its finish record: the stream is complete,
                        # only the completion fact is missing — recover
                        # it instead of re-serving a finished request
                        self._results[r.rid] = Finished(
                            rid=r.rid, prompt_len=r.prompt_len,
                            tokens=list(em), admit_round=0,
                            finish_round=0, slot=-1)
                        jr.append("finish", rid=r.rid,
                                  replica=self.replica_id,
                                  prompt_len=r.prompt_len, toks=list(em),
                                  recovered=True)
                        continue
                    e.resume = _Resume(emitted=list(em), blobs=None,
                                       written=0, degraded=False)
                    e.not_before = 0        # arrived before the crash
                    self._counters["journal_replayed"] += 1
                    jr.append("replay", rid=r.rid,
                              replica=self.replica_id, from_tok=len(em))
            pend.append(e)
        now = time.perf_counter_ns()
        for e in pend:
            e.enqueued_ns = now
        self._pending = pend

    def has_work(self) -> bool:
        """Queued or resident requests remain (the run-loop condition)."""
        return bool(self._pending
                    or any(r is not None for r in self._req))

    def _diag(self) -> dict:
        return {"round": self._round_no,
                "replica": self.replica_id,
                "pending": [(e.req.rid, e.not_before, e.sheds)
                            for e in self._pending],
                "resident": [r.rid for r in self._req if r is not None],
                "pool": self.alloc.stats(),
                "held_pages": len(self._held),
                "counters": dict(self._counters)}

    # -- migration (the fleet host's dead-replica API) --------------------
    def evacuate(self, *, readable: bool = True,
                 mode: str = "swap") -> List[_QEntry]:
        """Capture EVERY in-flight and queued request as portable queue
        entries.  Residents leave through the normal preemption capture:
        with the victim's device memory ``readable`` (a hang) and
        ``mode="swap"`` their live K/V pages travel as tagged swap blobs
        a survivor installs into its own pool; otherwise (a kill — pages
        unreachable — or ``mode="reingest"``) the continuation is the
        emitted-token list and the survivor recomputes K/V by
        free-and-reingest.  Either way the migrated request's remaining
        tokens are bit-identical to the unfailed run.  Queued entries
        drain as-is (their backoff clocks re-base on the receiver)."""
        force = (not readable) or mode != "swap"
        for b in range(self.slots):
            if self._req[b] is not None:
                self.caches = self._preempt(
                    b, self._round_no, self.caches, self._counters,
                    reason="migrate", force_reingest=force)
        out, self._pending = self._pending, []
        return out

    def adopt(self, entries: Sequence[_QEntry]) -> int:
        """Enqueue another replica's evacuated entries into THIS engine.
        Swap payloads are provenance-checked against the receiving pool
        first (``models.paged.check_blob_tag``): a foreign blob —
        dtype or page-size mismatch — raises ``ValueError`` instead of
        silently reinterpreting page bytes.  Adopted entries become
        admissible immediately on the receiver's round clock and then
        compose with its priority/deadline/backpressure scheduling like
        any preempted-and-requeued local request."""
        from ..models.paged import check_blob_tag
        n = 0
        now = time.perf_counter_ns()
        for e in entries:
            if e.resume is not None and e.resume.blobs is not None:
                check_blob_tag(e.resume.tag, dtype=self._pool_dtype,
                               page=self.page)
            e.not_before = self._round_no
            if e.enqueued_ns is None:
                e.enqueued_ns = now
            self._pending.append(e)
            self._counters["migrated_in"] += 1
            if self.journal is not None:
                self.journal.append(
                    "migrate", rid=e.req.rid, to=self.replica_id,
                    mode=("swap" if e.resume is not None
                          and e.resume.blobs is not None else "reingest"),
                    emitted=(len(e.resume.emitted)
                             if e.resume is not None else 0))
            n += 1
        return n

    def step(self) -> bool:
        """ONE scheduler iteration: fault release -> admission -> prefill
        chunks -> at most one decode burst -> finish/escalate accounting.
        Returns ``has_work()`` — False once drained.  Raises
        ``ReplicaLostError`` at the burst dispatch when this replica's
        ``replica_fault`` kill is due (the simulated device loss).

        Each call is one ``engine.step`` span; its phases are child spans
        (``engine.admission``, ``engine.prefill`` with its
        ``engine.prefill.readback``, ``engine.burst`` with its
        ``prepare`` / ``dispatch`` / ``readback``,
        ``engine.burst.bookkeeping``, ``engine.escalate``)."""
        if not self.has_work():
            return False
        with self.spans.span("engine.step"):
            self._step()
        return self.has_work()

    def _step(self) -> None:
        jnp, jax = self._jnp, self._jax
        plan = self.fault_plan
        counters = self._counters
        watchdog, monitor = self.watchdog, self.monitor
        spans = self.spans
        key = self._key
        progress = 0

        # -- fault plan: release expired holds, fire due injections -------
        if self._held and self._round_no >= self._release_at:
            self.alloc.free(self._held)
            if plan is not None:
                plan.note("exhaust_release", round=self._round_no,
                          pages=len(self._held))
            self._held, self._release_at = [], None
        if plan is not None and not self._held:
            dur = plan.take_exhaustion(self._round_no)
            if dur is not None:
                grab = self.alloc.n_free
                self._held = self.alloc.alloc(grab) if grab else []
                self._release_at = self._round_no + max(1, dur)
                counters["faults_exhaust"] += 1
                plan.note("exhaust", round=self._round_no, pages=grab,
                          until=self._release_at)

        # -- admission: place queue entries (preempt/degrade/shed) --------
        preempted = counters["preemptions"]
        with spans.span("engine.admission",
                        pending=len(self._pending)) as admission:
            admitted, self.caches = self._admission(
                self._round_no, self.caches, counters)
            admission.set(admitted=admitted,
                          preempted=counters["preemptions"] - preempted)
        progress += admitted

        # -- one prefill chunk per admitting slot, same-offset slots
        #    batched into one call (the t=0 admission wave especially)
        prefilling = [b for b in range(self.slots)
                      if self._req[b] is not None and self.done[b]]
        waves: Dict[int, List[int]] = {}
        for b in prefilling:
            waves.setdefault(int(self._prog[b]), []).append(b)
        for off, rows in sorted(waves.items()):
            with spans.span("engine.prefill", offset=off,
                            rows=len(rows)) as prefill:
                m = len(rows)
                buf = np.zeros((m, self.chunk), np.int32)
                meta = np.zeros((3, m), np.int32)   # rows/chunk lens/levels
                meta[0] = rows
                for i, b in enumerate(rows):
                    piece = self._ingest[b][off:off + self.chunk]
                    buf[i, :len(piece)] = piece
                    meta[1, i] = len(piece)
                    meta[2, i] = self.kv_levels[b]
                # (query, key) pairs the wave attends: each row's piece
                # reads the ``off`` positions before it and itself causally
                lens = meta[1].astype(np.int64)
                prefill.set(tokens=int(lens.sum()),
                            pairs=int((lens * off
                                       + lens * (lens + 1) // 2).sum()))
                if self.temperature > 0.0:
                    key, sk = jax.random.split(key)
                    self._key = key
                else:
                    sk = key
                cnts = (jnp.asarray(self._cnt[rows]) if self._use_pen
                        else None)
                capture = self.prompt_logits is not None
                tok0, badp, self.caches, flp, *lgs = self._chunk_fn(
                    off, m, capture)(
                    self.params, self.caches, self._table_device(),
                    jnp.asarray(buf), jnp.asarray(meta), cnts, sk)
                with spans.span("engine.prefill.readback"):
                    tok0, badp = np.asarray(tok0), np.asarray(badp)
                if self.escalate is not None:
                    # prefill write flags feed the same per-slot pressure
                    self.flag_pressure[rows] += np.asarray(flp, np.int64)
                progress += 1
                for i, b in enumerate(rows):
                    req = self._req[b]
                    self._prog[b] += int(meta[1, i])
                    if int(self._prog[b]) != len(self._ingest[b]):
                        continue
                    if badp[i]:
                        if plan is not None and plan.mask_poison:
                            counters["nonfinite_prefill"] += 1
                        else:
                            raise PoisonedLogitsError(
                                f"non-finite prefill logits for request "
                                f"{req.rid} (slot {b}, round "
                                f"{self._round_no})")
                    if self._resume_tok[b] is not None:
                        # reingest resume: the re-fed tokens only rebuild
                        # K/V; generation continues from the last emitted
                        # token exactly where the un-preempted run was
                        self.tok[b, 0] = self._resume_tok[b]
                        self._resume_tok[b] = None
                        self.pos[b] = self.lens[b] = len(self._ingest[b])
                        self.limit[b] = req.prompt_len + req.max_new - 1
                        self.done[b] = False
                        continue
                    if capture:
                        self.prompt_logits[req.rid] = np.asarray(lgs[0][i])
                    t0 = int(tok0[i])
                    e = self._entry[b]
                    if e.first_ns is None:
                        e.first_ns = time.perf_counter_ns()
                        spans.mark("engine.first_token", e.admitted_ns,
                                   e.first_ns, rid=req.rid)
                    self._emitted[b] = [t0]
                    if self.journal is not None:
                        self.journal.append("tokens", rid=req.rid,
                                            replica=self.replica_id,
                                            toks=[t0])
                    if self._use_pen:
                        self._cnt[b, t0 % self._cnt.shape[1]] += 1
                    hit_stop = (self.stop_token is not None
                                and t0 == self.stop_token)
                    if hit_stop or req.max_new == 1:
                        self._finish(b, self._round_no, self._results)
                        progress += 1
                    else:
                        self.tok[b, 0] = t0
                        self.pos[b] = self.lens[b] = req.prompt_len
                        self.limit[b] = req.prompt_len + req.max_new - 1
                        self.done[b] = False

        # -- decode burst over every slot ---------------------------------
        active = [b for b in range(self.slots) if not self.done[b]]
        still_prefilling = any(
            self._req[b] is not None and self.done[b]
            for b in range(self.slots))
        n_max = 0
        if active:
            # admission wave: with a deep queue, let up to `admit_wave`
            # finishes accumulate before handing control back — halves
            # scheduler round-trips vs reacting to every single finish.
            # n_max is then capped near the wave-th soonest budget
            # finish so a lone early finisher never waits long.
            wave = (min(self.admit_wave, len(self._pending))
                    if self._pending else 0)
            if still_prefilling:
                # interleave: chunk, a few decode rounds, chunk, ... —
                # ongoing streams advance while a long prompt prefills
                n_max = self.prefill_rounds
            else:
                n_max = self.burst_cap
                if self._pending:
                    till = (min(e.not_before for e in self._pending)
                            - self._round_no)
                    if till > 0:
                        n_max = max(1, min(n_max, till))
                    rem = sorted(int(self.limit[b]) - int(self.pos[b])
                                 + 1 for b in active)
                    k = min(wave, len(rem)) - 1
                    n_max = max(1, min(n_max, rem[k] + 1))
            # page pressure: a failed lazy alloc preempts a weaker
            # resident; if none exists the row itself yields its slot
            look = self.spec_k
            for b in list(active):
                if b not in active:
                    continue
                # each speculative round advances up to spec_k+1
                # tokens and its verify chunk writes spec_k slots
                # past the accepted frontier (dead until accepted)
                tgt = min(int(self.pos[b]) + n_max * (look + 1) - 1
                          + look,
                          int(self.limit[b]) - 1 + look)
                while not self._ensure_pages(b, tgt):
                    vs = self._victims_for(
                        self._eff_resident(b, self._round_no),
                        self._round_no, exclude=(b,))
                    if not vs:
                        self.caches = self._preempt(
                            b, self._round_no, self.caches,
                            counters, reason="pages")
                        active.remove(b)
                        break
                    self.caches = self._preempt(
                        vs[0], self._round_no, self.caches,
                        counters, reason="pages")
                    if vs[0] in active:
                        active.remove(vs[0])
        if active:
            # the simulated device loss fires exactly here — after host
            # scheduling, at the burst dispatch, the boundary where a
            # real accelerator fault would surface
            if (self.replica_fault is not None
                    and self.replica_fault.take_kill(self.replica_id,
                                                     self._bursts)):
                raise ReplicaLostError(
                    f"replica {self.replica_id} lost at burst "
                    f"{self._bursts} (round {self._round_no}): "
                    f"simulated device failure",
                    replica=self.replica_id, burst=self._bursts)
            poison_rel = ovf_rel = -1
            if plan is not None:
                p = plan.next_poison(self._round_no,
                                     self._round_no + int(n_max))
                if p is not None:
                    poison_rel = p - self._round_no
                o = plan.next_overflow(self._round_no,
                                       self._round_no + int(n_max))
                if o is not None:
                    ovf_rel = o - self._round_no
            with spans.span("engine.burst", live=len(active)) as burst:
                if plan is not None:
                    stall = plan.take_slow(self._round_no)
                    if stall > 0.0:
                        counters["faults_slow"] += 1
                        plan.note("slow", round=self._round_no,
                                  seconds=stall)
                        time.sleep(stall)
                with spans.span("engine.burst.prepare"):
                    state = np.zeros((11 if self.spec_k else 10,
                                      self.slots), np.int32)
                    state[0, :] = self.tok[:, 0]
                    state[1], state[2], state[3] = (self.pos, self.lens,
                                                    self.limit)
                    state[4] = self.done
                    state[5, 0], state[6, 0] = n_max, wave
                    state[7, 0] = poison_rel
                    state[8] = self.kv_levels
                    state[9, 0] = ovf_rel
                    if self.spec_k:
                        state[10] = self._spec_rows
                    cnts = jnp.asarray(self._cnt) if self._use_pen else None
                    table, state_in = (self._table_device(),
                                       jnp.asarray(state))
                with spans.span("engine.burst.dispatch"):
                    res = self._burst(self.params, self.caches, table,
                                      state_in, cnts, key)
                with spans.span("engine.burst.readback"):
                    out, n, state_d, self.caches, key2, bad_d, fl_d = res[:7]
                    n = int(n)                    # blocks on the burst
                    new_state = np.array(state_d)
                    if self.spec_k:
                        # packed layout: row b's accepted tokens fill
                        # out[b, :lens-growth]; download up to the widest
                        # row
                        sp = np.asarray(res[7])
                        counters["spec_rounds"] += int(sp[0])
                        counters["spec_emitted"] += int(sp[1])
                        w = int(max(1, (new_state[2] - self.lens).max()))
                        outs = np.asarray(out[:, :w])
                    else:
                        outs = np.asarray(out[:, :n])  # only executed cols
                    bad = np.asarray(bad_d)
                burst.set(rounds=n)
                if self.model.cfg.moe is not None and not self.spec_k:
                    burst.set(experts_read=int(new_state[4, 0]))
            if monitor.record(self._bursts, burst.seconds):
                counters["stragglers"] += 1
            with spans.span("engine.burst.bookkeeping"):
                if bad.sum():
                    if plan is not None and plan.mask_poison:
                        counters["poisoned_rounds"] += int(bad.max())
                        plan.note("poison", round=self._round_no,
                                  rows=np.nonzero(bad)[0].tolist())
                    else:
                        raise PoisonedLogitsError(
                            f"non-finite decode logits at round "
                            f"{self._round_no} (rows "
                            f"{np.nonzero(bad)[0].tolist()}); no "
                            f"masking fault harness is active")
                self.tok = new_state[0][:, None].copy()
                self.pos = new_state[1]
                if self.temperature > 0.0:
                    key = key2
                    self._key = key
                total_ran = 0
                for b in active:
                    # rounds this row actually ran = its live-length growth
                    ran = int(new_state[2][b]) - int(self.lens[b])
                    emitted = [int(t) for t in outs[b, :ran]]
                    self._emitted[b].extend(emitted)
                    if self.journal is not None and emitted:
                        # the per-burst delta is the crash-consistency
                        # quantum: at most one burst of tokens is ever lost,
                        # and greedy determinism regenerates it bit-exactly
                        self.journal.append("tokens", rid=self._req[b].rid,
                                            replica=self.replica_id,
                                            toks=emitted)
                    if self._use_pen and emitted:
                        v = self._cnt.shape[1]
                        np.add.at(self._cnt[b],
                                  np.asarray(emitted, np.int64) % v, 1)
                    self._occ_accum += ran
                    total_ran += ran
                if n > 0 and total_ran == 0:
                    raise EngineStuckError(
                        f"decode burst executed {n} rounds without "
                        f"advancing any of {len(active)} live rows",
                        self._diag())
                if self.escalate is not None:
                    self.flag_pressure += np.asarray(fl_d, np.int64)
                    if plan is not None and 0 <= ovf_rel < n:
                        counters["faults_overflow"] = counters.get(
                            "faults_overflow", 0) + 1
                        plan.note("overflow",
                                  round=self._round_no + ovf_rel,
                                  scale=plan.overflow_scale)
                self.lens = new_state[2]
                self.done = new_state[3].astype(bool)
                self._round_no += n
                self._decode_rounds += n
                self._bursts += 1
                progress += n
                for b in active:
                    if self.done[b]:
                        self._finish(b, self._round_no, self._results)
                        progress += 1
            if self.escalate is not None:
                with spans.span("engine.escalate"):
                    self.caches = self._maybe_escalate(
                        active, self._round_no, self.caches, counters)
        elif still_prefilling:
            self._round_no += 1    # prefill-only round (no decoders yet)
        elif self._pending:
            # idle: jump to the next event — an arrival, a backoff
            # window expiring, or an injected exhaustion releasing
            nxt = [e.not_before for e in self._pending]
            if self._held:
                nxt.append(self._release_at)
            self._round_no = max(self._round_no + 1, min(nxt))
        watchdog.tick(progress > 0, self._diag)

    def finalize(self):
        """Close out a drained (or abandoned) run: release fault-plan
        holds and assemble the stats dict (``self.caches`` is already
        current — it IS the donated burst carry, kept live step to step
        so a crashed run's restart never touches a donated buffer).
        Returns ``(results_by_rid, stats)`` — ``run`` orders the results
        itself; the fleet host merges the dicts across replicas instead
        (a victim's pre-death completions still count)."""
        if self._held:              # plan outlived the queue: tidy up
            self.alloc.free(self._held)
            self._held, self._release_at = [], None
        counters = self._counters
        dl = [f for f in self._results.values() if f.deadline is not None]
        misses = sum(1 for f in dl if f.deadline_miss)
        stats = {
            "rounds": self._round_no,
            "decode_rounds": self._decode_rounds,
            "bursts": self._bursts,
            "occupancy": (self._occ_accum
                          / (self.slots * self._decode_rounds)
                          if self._decode_rounds else 0.0),
            # request-KV pages only: the engine's always-live scratch page
            # (dead-write sink) is bookkeeping, not cache content
            "peak_live_pages": self.alloc.peak_live - 1,
            "n_pages": self.n_pages,
            "fixed_equiv_pages": self.slots * self.max_pages,
            "pages_live_end": self.alloc.n_live - 1,
            "deadline_total": len(dl),
            "deadline_misses": misses,
            "deadline_miss_rate": (misses / len(dl)) if dl else 0.0,
            "straggler_ewma_s": self.monitor.ewma,
            **counters,
            # launch/spans.py records since start(), oldest first
            "spans": self.spans.records(),
            "spans_dropped": self.spans.dropped,
        }
        if self.spec_k:
            lr = counters["spec_rounds"]
            stats["spec_k"] = self.spec_k
            # emitted / (live-row-rounds * chunk width): the bonus token
            # keeps every live row's per-round yield >= 1, so the rate
            # lives in (0, 1] whenever any speculative round ran
            stats["spec_accept_rate"] = (
                counters["spec_emitted"] / (lr * (self.spec_k + 1))
                if lr else 0.0)
        return dict(self._results), stats

    def run(self, requests: Sequence[Request]):
        """Serve ``requests`` to completion.  Returns ``(finished, stats)``
        with ``finished`` in input order and ``stats`` covering rounds,
        mean batch occupancy, the page-pool high-water mark, and the
        robustness counters (preempt/shed/degrade/deadline/fault)."""
        self.start(requests)
        while self.step():
            pass
        res, stats = self.finalize()
        return [res[r.rid] for r in requests], stats


class ReplicatedEngine:
    """Data-parallel engine replicas over a ``(data, model)`` serving mesh
    — or, with ``mesh=None, replicas=N``, a meshless fleet of ``N``
    unsharded replicas (the HA test topology).

    Each ``data`` row of the mesh becomes ONE ``ContinuousEngine`` running
    tensor-parallel attention over its own ``("model",)`` sub-mesh
    (``launch/mesh.py: replica_meshes``), with its OWN ``PageAllocator``
    over a disjoint page pool and its own block tables — replicas share
    no state and no collective, so the data axis is pure throughput.

    The request queue is partitioned host-side: arrivals round-robin over
    replicas in ``(arrival, rid)`` order, so each replica sees the same
    heavy-tail mix and admission waves split ``~1/dp`` per replica.
    ``run`` merges the ``Finished`` records back into input order and
    aggregates stats — counters sum, occupancy is decode-round-weighted,
    and the pool story is ``models.paged.aggregate_stats`` over the
    per-replica allocators (disjoint pools: totals are plain sums).

    The host loop INTERLEAVES replicas one scheduler step at a time
    (each replica owns its devices outright, so on real hardware the
    per-replica loops are embarrassingly parallel; time-slicing them
    here changes wall-clock on a simulated mesh, never tokens or
    accounting) — and that is what makes replica loss survivable
    mid-run:

      * every completed step is a HEARTBEAT; a ``ReplicaFaultPlan`` hang
        makes the victim stop stepping, and after ``hang_patience``
        consecutive missed beats the host declares it dead with device
        memory still readable — its residents evacuate as tagged swap
        blobs (``migrate="swap"``) or emitted-token reingest state;
      * a kill raises ``ReplicaLostError`` through the victim's burst
        dispatch — device memory is GONE, so evacuation always falls
        back to free-and-reingest (host-side emitted tokens survive);
      * evacuated entries are ``adopt``ed round-robin by the surviving
        replicas and finish there with token bits identical to the
        unfailed run; if NO replica survives, the loss re-raises for
        ``train.fault.run_with_restarts`` + the request journal.
    """

    def __init__(self, model, params, *, mesh=None, replicas=None,
                 migrate: str = "swap", hang_patience: int = 3, **kw):
        from .mesh import replica_meshes
        if migrate not in ("swap", "reingest"):
            raise ValueError(f"migrate must be swap|reingest, "
                             f"got {migrate!r}")
        subs = replica_meshes(mesh, replicas)
        self.mesh = mesh
        self.migrate = migrate
        self.hang_patience = max(1, hang_patience)
        self.replica_fault = kw.pop("replica_fault", None)
        self.journal = kw.pop("journal", None)
        self.engines = [ContinuousEngine(model, params, mesh=m,
                                         replica_id=i,
                                         replica_fault=self.replica_fault,
                                         journal=self.journal, **kw)
                        for i, m in enumerate(subs)]
        self._bound: Optional[List[Request]] = None
        self.heartbeats = [{"beats": 0, "missed": 0, "status": "live"}
                           for _ in self.engines]
        self._ha = {k: 0 for k in (
            "ha_kills", "ha_hangs", "ha_migrations",
            "ha_migrated_swap", "ha_migrated_reingest")}

    @property
    def allocators(self):
        return [e.alloc for e in self.engines]

    def reset_monitors(self) -> None:
        """The ``run_with_restarts`` contract, fanned out: every
        replica's watchdog + straggler monitor is rebuilt, and the
        fleet's heartbeat view starts fresh (a restarted fleet has no
        dead replicas — the fault plan decides whether one re-dies)."""
        for e in self.engines:
            e.reset_monitors()
        self.heartbeats = [{"beats": 0, "missed": 0, "status": "live"}
                           for _ in self.engines]
        self._ha = {k: 0 for k in self._ha}

    def bind(self, requests: Sequence[Request]) -> "ReplicatedEngine":
        """Stash a queue so ``run()`` needs no arguments — the shape
        ``run_with_restarts`` drives (its runner contract is a no-arg
        ``run``).  Returns self for factory one-liners."""
        self._bound = list(requests)
        return self

    def partition(self, requests: Sequence[Request]) -> List[List[Request]]:
        """Round-robin split in ``(arrival, rid)`` order — deterministic,
        and each replica's sub-queue preserves the arrival ordering the
        single-engine admission loop expects."""
        parts: List[List[Request]] = [[] for _ in self.engines]
        for i, r in enumerate(sorted(requests,
                                     key=lambda r: (r.arrival, r.rid))):
            parts[i % len(parts)].append(r)
        return parts

    # -- failure handling -------------------------------------------------
    def _survivors(self) -> List[int]:
        return [i for i, h in enumerate(self.heartbeats)
                if h["status"] == "live"]

    def _lose_replica(self, i: int, *, readable: bool, burst: int,
                      why: str) -> None:
        """Declare replica ``i`` dead and migrate its in-flight work.
        ``readable`` says whether the victim's device memory can still be
        swapped out (hang) or is gone (kill — evacuation re-ingests).
        Without survivors the loss re-raises for the restart supervisor;
        the journal then carries every already-emitted token."""
        self.heartbeats[i]["status"] = "dead"
        eng = self.engines[i]
        entries = eng.evacuate(readable=readable, mode=self.migrate)
        if self.journal is not None:
            self.journal.append("replica_lost", replica=i, why=why,
                               burst=burst, evacuated=len(entries))
        alive = self._survivors()
        if not alive:
            raise ReplicaLostError(
                f"replica {i} {why} at burst {burst} and no replica "
                f"survives to adopt its {len(entries)} requests — "
                f"restart and replay the journal",
                replica=i, burst=burst)
        for j, e in enumerate(entries):
            swap = e.resume is not None and e.resume.blobs is not None
            self.engines[alive[j % len(alive)]].adopt([e])
            self._ha["ha_migrations"] += 1
            self._ha["ha_migrated_swap" if swap
                     else "ha_migrated_reingest"] += 1

    # -- the fleet loop ---------------------------------------------------
    def run(self, requests: Optional[Sequence[Request]] = None):
        """Serve ``requests`` (or the ``bind``-ed queue) across all
        replicas, interleaved one step at a time.  Returns
        ``(finished, stats)`` with ``finished`` in input order;
        ``stats["replicas"]`` keeps each replica's own record,
        ``stats["spans"]`` each replica's span list (one list per
        replica, never merged), ``stats["pool"]`` the aggregated
        allocator view, and the
        ``ha_*`` fields + ``stats["heartbeats"]`` the fleet's
        fault-tolerance story."""
        from ..models.paged import aggregate_stats
        if requests is None:
            if self._bound is None:
                raise ValueError("run() needs requests (or bind() first)")
            requests = self._bound
        self.heartbeats = [{"beats": 0, "missed": 0, "status": "live"}
                           for _ in self.engines]
        self._ha = {k: 0 for k in self._ha}
        plan = self.replica_fault
        parts = self.partition(requests)
        for eng, part in zip(self.engines, parts):
            eng.start(part)
        while True:
            stepped = False
            for i, eng in enumerate(self.engines):
                hb = self.heartbeats[i]
                if hb["status"] == "dead" or not eng.has_work():
                    continue
                if plan is not None and plan.hang_due(i, eng._bursts):
                    # the victim stops responding: a missed beat per
                    # fleet sweep, then declared dead — device memory
                    # is still readable, so pages can migrate as blobs
                    hb["missed"] += 1
                    if hb["missed"] == 1:
                        self._ha["ha_hangs"] += 1
                    if hb["missed"] >= self.hang_patience:
                        self._lose_replica(i, readable=True,
                                           burst=eng._bursts, why="hung")
                    stepped = True      # the fleet is still making calls
                    continue
                try:
                    eng.step()
                    hb["beats"] += 1
                    stepped = True
                except ReplicaLostError as err:
                    self._ha["ha_kills"] += 1
                    self._lose_replica(i, readable=False,
                                       burst=err.burst, why="killed")
                    stepped = True
            work = [i for i in self._survivors()
                    if self.engines[i].has_work()]
            if not work:
                break
            if not stepped:     # defensive: nothing can advance
                raise EngineStuckError(
                    "replicated loop made no progress",
                    {"heartbeats": self.heartbeats,
                     "pending": [len(self.engines[i]._pending)
                                 for i in work]})
        results: Dict[int, Finished] = {}
        per = []
        for i, eng in enumerate(self.engines):
            res, st = eng.finalize()
            results.update(res)
            st["replica_status"] = self.heartbeats[i]["status"]
            per.append(st)
        # one span list per replica: each is on its own engine's ids
        spans = [s.pop("spans") for s in per]
        dr = sum(s["decode_rounds"] for s in per)
        stats = {
            "replicas_n": len(self.engines),
            "rounds": max((s["rounds"] for s in per), default=0),
            "decode_rounds": dr,
            "bursts": sum(s["bursts"] for s in per),
            "occupancy": (sum(s["occupancy"] * s["decode_rounds"]
                              for s in per) / dr if dr else 0.0),
            "peak_live_pages": sum(s["peak_live_pages"] for s in per),
            "n_pages": sum(s["n_pages"] for s in per),
            "fixed_equiv_pages": sum(s["fixed_equiv_pages"] for s in per),
            "deadline_total": sum(s["deadline_total"] for s in per),
            "deadline_misses": sum(s["deadline_misses"] for s in per),
            "pool": aggregate_stats(self.allocators),
            "replicas": per,
            "heartbeats": [dict(h) for h in self.heartbeats],
            "spans": spans,
            **self._ha,
        }
        dl = stats["deadline_total"]
        stats["deadline_miss_rate"] = (stats["deadline_misses"] / dl
                                       if dl else 0.0)
        if any("spec_accept_rate" in s for s in per):
            sr = sum(s.get("spec_rounds", 0) for s in per)
            se = sum(s.get("spec_emitted", 0) for s in per)
            k1 = max(s.get("spec_k", 0) for s in per) + 1
            stats["spec_rounds"], stats["spec_emitted"] = sr, se
            stats["spec_accept_rate"] = se / (sr * k1) if sr else 0.0
        for k in per[0] if per else ():
            if k not in stats and isinstance(per[0][k], (int, np.integer)):
                stats[k] = sum(s[k] for s in per)
        return [results[r.rid] for r in requests], stats
