"""Serving launcher: batched prefill + decode.

The decode loop is a single compiled ``lax.scan`` (``Model.generate``) —
one XLA dispatch for the whole generation.

``--ragged`` packs MIXED-length prompts into one right-padded batch (row
``b`` gets a length cycling over 1/4, 1/2, 3/4 and 4/4 of ``--prompt-len``)
and serves it with per-sequence lengths: each row prefills, masks and
decodes at its OWN length, and the Pallas kernels prune each row's KV walk
there instead of paying the longest prompt's grid for every row.
``--stop-token`` enables per-row EOS early-exit: a row that emits the stop
token freezes (its outputs stay the stop token, its live cache stops
growing) while the rest of the batch keeps decoding.

``--paged`` serves from a paged KV cache (``--page-size`` tokens per page):
a shared page pool + per-row block tables instead of contiguous per-row
buffers.  When the batch is uniform, the launcher also runs the
PREFIX-SHARING demo: every row's prompt shares a common first half, the
common pages are allocated ONCE and aliased into every row's table
(``models.paged.build_tables``), and the launcher verifies that prefill
logits and generated tokens are bit-identical to the unshared identity
layout while the pool holds fewer live pages.  With ``--ragged`` the
identity table is used (per-row lengths + paged pool, no sharing demo).

``--continuous`` serves a REQUEST QUEUE through the continuous-batching
engine (``launch/engine.py``) instead of one fixed batch: requests are
admitted into freed batch slots mid-generation, prompts prefill in chunks
through the paged flash read path interleaved with decode rounds, each
row decodes only to its OWN budget (``while_loop`` bursts exit the round
any row finishes), and a finished row's pages return to the allocator
that round.  The queue comes from ``--arrival-trace`` (comma-separated
``arrival:prompt_len:max_new[:priority[:deadline]]`` tuples, arrivals
and deadlines in decode rounds) or defaults to the deterministic
heavy-tail trace of the benchmark (``engine.synthetic_trace``).
Implies ``--paged``; the printout shows per-request admit/finish
rounds, slot occupancy, and the page pool's high-water mark against
the fixed-batch equivalent.

The engine's overload controls are exposed directly: ``--priority``
and ``--deadline-ms`` annotate the queue (milliseconds are converted
to decode rounds via ``--round-ms``, the assumed per-round latency
budget), ``--pool-pages`` constrains the page pool so preemption and
shedding actually engage, ``--preempt free|swap`` picks the eviction
mechanism, ``--degrade-fmt fp8`` stores swapped victims' K/V in fp8 on
the host (transprecision graceful degradation; quality-sensitive
requests refuse it via the trace), ``--no-shed`` restores blocking
admission, and ``--soak`` swaps in the bursty overload trace with
injected faults (``--fault-exhaust/--fault-poison/--fault-slow``) —
the robustness counters (preempted/shed/degraded/deadline-miss) print
after the run.  Non-finite logits abort serving with
``PoisonedLogitsError`` unless a masking fault plan is active — the
solo path enables the same guard via ``generate(guard_nonfinite=)``.

``--speculate K`` (requires ``--continuous``) turns on self-speculative
decoding: every burst round drafts K tokens per row with a cheap pass
(``--draft-layers N`` runs only the first N repeats of the scanned layer
stack; ``--draft-fmt tp_bf16_kv8`` drafts under a narrower precision
policy — the FPnew energy-proportionality move applied to decoding),
then ONE chunk-scoring call at the serving policy verifies all K+1
positions and accepts the longest matching prefix.  Greedy-only: the
accepted stream is bit-identical to plain decode, a wrong draft can
only cost speed, never tokens.  The accept rate prints after the run.

Numerical health (requires ``--policy fp32``, the wide-container pool):
``--escalate fp8,fp16,fp16alt`` turns on flag-driven KV-precision
escalation — every row's K/V is quantized at write time to its current
ladder rung (saturating, so overflow clamps instead of poisoning the
logits) and the per-row IEEE OF/UF flag counts accumulate as pressure;
a row whose overflow pressure crosses ``--escalate-of-threshold`` is
re-ingested one rung wider.  ``--fault-overflow`` scales K/V writes by
``--overflow-scale`` at the listed decode rounds (the write-side twin
of ``--fault-poison``), and ``--fault-corrupt-swap`` flips one bit in
the listed swap-out events' host payloads — the swap-in checksum must
detect each corruption and recover via re-ingest.  ``--burst-cap``
bounds decode-burst length (escalation decisions happen between
bursts, so shorter bursts react faster).

Replica-level fault tolerance: ``--replicas N`` runs a meshless fleet
of N engine replicas over disjoint page pools (``--mesh DP,TP`` is the
placed equivalent), ``--fault-replica R:BURST[:MODE]`` kills (default)
or hangs replica R at its BURST-th compiled burst, ``--migrate
swap|reingest`` picks how a dead replica's in-flight requests move to a
survivor (CRC-verified swap-blob continuations need ``--preempt swap``
and a hang — a kill's device memory is gone, so migration always falls
back to free-and-reingest from host-side emitted tokens), and
``--journal PATH`` appends a crash-consistent JSON-line request journal
that a full restart replays so every unfinished request resumes from
its last journaled token with bit-identical results.  The replica HA
counters (kills/hangs/migrations + per-replica heartbeats) print after
the run.

``python -m repro.launch.serve --arch gemma2-9b --batch 4 --gen 32``
``python -m repro.launch.serve --arch gemma2-9b --ragged --stop-token 13``
``python -m repro.launch.serve --arch gemma2-9b --paged --page-size 16``
``python -m repro.launch.serve --arch gemma2-9b --continuous --slots 4``
``python -m repro.launch.serve --continuous --arrival-trace 0:32:8,2:16:24``
"""
from __future__ import annotations

import argparse
import time


def ragged_lengths(batch: int, prompt_len: int):
    """The mixed-length pack of ``--ragged``: rows cycle over 1/4, 1/2,
    3/4, 4/4 of ``prompt_len`` (clamped to >= 1), longest rows last so the
    printout reads like the padded batch."""
    fracs = (0.25, 0.5, 0.75, 1.0)
    return [max(1, int(prompt_len * fracs[i % len(fracs)]))
            for i in range(batch)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--policy", default="tp_bf16")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--decode-backend", choices=("dense", "pallas", "auto"),
                    default="auto",
                    help="pallas: fused in-kernel KV-dequant decode attention;"
                         " auto (default): pallas off-CPU, dense on CPU")
    ap.add_argument("--prefill-backend", choices=("dense", "pallas", "auto"),
                    default="auto",
                    help="pallas: pruned-grid flash-attention prefill kernel;"
                         " auto (default): pallas off-CPU, dense on CPU")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="> 0 enables sampling (0 = greedy, the default)")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--repetition-penalty", type=float, default=None,
                    help="> 1 discourages re-emitting seen tokens (HF "
                         "semantics; prompt + generated counts)")
    ap.add_argument("--presence-penalty", type=float, default=None,
                    help="> 0 flat-penalizes every seen token (OpenAI "
                         "semantics)")
    ap.add_argument("--seed", type=int, default=0, help="sampling PRNG seed")
    ap.add_argument("--ragged", action="store_true",
                    help="pack mixed-length prompts (1/4..4/4 of "
                         "--prompt-len) into one padded batch and serve "
                         "each row at its own length")
    ap.add_argument("--stop-token", type=int, default=None,
                    help="per-row EOS early-exit: rows freeze after "
                         "emitting this token id")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: shared page pool + per-row block "
                         "tables; uniform batches also run the "
                         "prefix-sharing parity demo")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (the paged decode kernel's KV "
                         "block; use >= 128 on real TPUs)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine: admission queue, "
                         "chunked prefill, per-request budgets, page "
                         "recycling (implies --paged)")
    ap.add_argument("--arrival-trace", default=None,
                    help="comma-separated arrival:prompt_len:max_new"
                         "[:priority[:deadline]] tuples (arrival/deadline "
                         "in decode rounds); default: the benchmark's "
                         "synthetic heavy-tail trace")
    ap.add_argument("--priority", type=int, default=0,
                    help="priority class stamped on default-trace requests "
                         "(higher admits first and preempts lower)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline in milliseconds, converted "
                         "to decode rounds via --round-ms and applied as "
                         "arrival + rounds; missed deadlines are counted "
                         "per request and in the stats")
    ap.add_argument("--round-ms", type=float, default=1.0,
                    help="assumed per-decode-round latency budget used to "
                         "convert --deadline-ms to the engine's round clock")
    ap.add_argument("--shed", dest="shed", action="store_true", default=True,
                    help="defer unplaceable requests with jittered "
                         "exponential backoff instead of blocking (default)")
    ap.add_argument("--no-shed", dest="shed", action="store_false",
                    help="head-of-line blocking admission (no backoff)")
    ap.add_argument("--preempt", choices=("free", "swap"), default="free",
                    help="eviction mechanism under pressure: free pages + "
                         "re-ingest on resume, or swap K/V pages to a "
                         "host-side store and restore them")
    ap.add_argument("--degrade-fmt", default=None,
                    help="store swapped victims' K/V in this format on the "
                         "host (e.g. fp8) — transprecision graceful "
                         "degradation; implies --preempt swap")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="page-pool size override (small pools exercise "
                         "preemption/shedding; default: worst-case fit)")
    ap.add_argument("--soak", action="store_true",
                    help="overload soak: bursty synthetic trace (priorities,"
                         " deadlines, long documents) + injected faults")
    ap.add_argument("--fault-exhaust", default=None,
                    help="comma-separated rounds at which the fault plan "
                         "grabs the whole free page list for a few rounds")
    ap.add_argument("--fault-poison", default=None,
                    help="comma-separated decode rounds whose logits are "
                         "NaN-poisoned inside the burst (masked + counted)")
    ap.add_argument("--fault-slow", default=None,
                    help="comma-separated rounds stalled before their burst "
                         "(straggler injection)")
    ap.add_argument("--escalate", default=None,
                    help="comma-separated KV-format ladder (e.g. "
                         "fp8,fp16,fp16alt): flag-driven precision "
                         "escalation on a fp32 pool — rows quantize K/V "
                         "writes at their rung (saturating) and escalate "
                         "one rung when overflow pressure crosses the "
                         "threshold (requires --policy fp32)")
    ap.add_argument("--escalate-of-threshold", type=int, default=8,
                    help="per-request overflow-flag count that triggers "
                         "escalation one rung up the ladder")
    ap.add_argument("--fault-overflow", default=None,
                    help="comma-separated decode rounds whose K/V writes "
                         "are scaled by --overflow-scale before write-time "
                         "quantization (drives the escalation path)")
    ap.add_argument("--overflow-scale", type=float, default=65536.0,
                    help="multiplier applied to K/V writes at "
                         "--fault-overflow rounds")
    ap.add_argument("--fault-corrupt-swap", default=None,
                    help="comma-separated swap-out event indices (0-based) "
                         "whose host payloads get one bit flipped — the "
                         "swap-in checksum must detect and re-ingest")
    ap.add_argument("--burst-cap", type=int, default=64,
                    help="max decode rounds per compiled burst (escalation "
                         "acts between bursts; smaller reacts faster)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="self-speculative decoding: draft K tokens per "
                         "row with the cheap pass, verify the whole chunk "
                         "at target precision in ONE call, accept the "
                         "longest matching prefix (greedy-only; accepted "
                         "tokens are bit-identical to plain decode)")
    ap.add_argument("--draft-layers", type=int, default=None, metavar="N",
                    help="layer-skip draft: run only the first N repeats "
                         "of the scanned layer pattern in the draft pass "
                         "(default: full depth — the draft is then the "
                         "target model and every proposal is accepted)")
    ap.add_argument("--draft-fmt", default=None, metavar="POLICY",
                    help="precision-policy preset the DRAFT pass runs "
                         "under (e.g. tp_bf16_kv8: fp8 KV reads for "
                         "proposals; verify stays at the serving policy)")
    ap.add_argument("--slots", type=int, default=4,
                    help="batch slots of the continuous engine")
    ap.add_argument("--requests", type=int, default=16,
                    help="request count of the default synthetic trace")
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk width of the continuous engine")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="serving mesh dp,tp: tp-way tensor-parallel "
                         "attention heads + paged KV pools per replica, "
                         "dp data-parallel engine replicas (dp > 1 "
                         "requires --continuous)")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="simulate N host devices (prepends "
                         "--xla_force_host_platform_device_count=N to "
                         "XLA_FLAGS before jax initializes — CPU bring-up "
                         "for --mesh; no effect on real accelerators)")
    ap.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="meshless HA fleet: N unsharded engine replicas "
                         "time-slicing the default device (disjoint page "
                         "pools; the replica topology without --mesh "
                         "placement — requires --continuous)")
    ap.add_argument("--fault-replica", default=None, metavar="R:BURST[:MODE]",
                    help="replica-level fault injection: replica R dies at "
                         "its BURST-th compiled burst; MODE is kill "
                         "(device memory gone, raised through dispatch — "
                         "default) or hang (stops stepping, declared dead "
                         "after missed heartbeats, memory still readable)")
    ap.add_argument("--migrate", choices=("swap", "reingest"),
                    default="swap",
                    help="live-request migration mode when a replica is "
                         "lost: adopt CRC-verified swap-blob continuations "
                         "on a survivor (needs --preempt swap and readable "
                         "victim memory) or free-and-reingest from emitted "
                         "tokens (always available)")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="append-only crash-consistent request journal "
                         "(JSON lines): admissions, per-burst token "
                         "deltas, preemptions, migrations, finishes — "
                         "replayed on engine start so a full restart "
                         "resumes every unfinished request from its last "
                         "journaled token with bit-identical results")
    args = ap.parse_args(argv)
    if args.arrival_trace and not args.continuous:
        args.continuous = True          # a request queue implies the engine
    if args.continuous and args.ragged:
        ap.error("--continuous subsumes --ragged (per-request lengths)")
    pen = (args.repetition_penalty is not None
           or args.presence_penalty is not None)
    if args.speculate:
        if not args.continuous:
            ap.error("--speculate requires --continuous (the draft/verify "
                     "rounds live in the engine's burst program)")
        if args.temperature > 0.0 or pen:
            ap.error("--speculate is greedy-only: temperature and "
                     "penalties would change the verified stream")
    mesh_dims = None
    if args.mesh is not None:
        try:
            dp, tp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            ap.error("--mesh expects DP,TP (e.g. --mesh 2,4)")
        if dp < 1 or tp < 1:
            ap.error(f"--mesh axes must be >= 1, got {dp},{tp}")
        if dp > 1 and not args.continuous:
            ap.error("--mesh with dp > 1 requires --continuous (the data "
                     "axis is engine replication)")
        mesh_dims = (dp, tp)
    if args.replicas is not None:
        if args.replicas < 1:
            ap.error(f"--replicas must be >= 1, got {args.replicas}")
        if not args.continuous:
            ap.error("--replicas requires --continuous (replicas are "
                     "engine instances over the request queue)")
    fault_replica = None
    if args.fault_replica is not None:
        parts = args.fault_replica.split(":")
        if len(parts) not in (2, 3):
            ap.error("--fault-replica expects R:BURST[:MODE] "
                     "(e.g. 0:3 or 1:5:hang)")
        try:
            fr, fb = int(parts[0]), int(parts[1])
        except ValueError:
            ap.error("--fault-replica R and BURST must be integers")
        fmode = parts[2] if len(parts) == 3 else "kill"
        if fmode not in ("kill", "hang"):
            ap.error(f"--fault-replica MODE must be kill|hang, "
                     f"got {fmode!r}")
        if args.replicas is None and (mesh_dims is None
                                      or mesh_dims[0] < 2):
            ap.error("--fault-replica needs a replicated engine "
                     "(--replicas N or --mesh with dp > 1) — a lone "
                     "replica's loss has no survivor to migrate to")
        fault_replica = (fr, fb, fmode)
    if args.devices is not None:
        # must land in the environment BEFORE jax initializes its backend
        import os
        flag = f"--xla_force_host_platform_device_count={args.devices}"
        os.environ["XLA_FLAGS"] = \
            (flag + " " + os.environ.get("XLA_FLAGS", "")).strip()

    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.registry import build_model

    model = build_model(args.arch, policy=args.policy, reduced=args.reduced)
    model = model.with_cfg(decode_backend=args.decode_backend,
                           prefill_backend=args.prefill_backend)
    if args.paged or args.continuous:
        model = model.with_cfg(paged_kv=True, page_size=args.page_size)

    mesh = rmesh = None
    dp = 1
    if mesh_dims is not None:
        from .mesh import make_serving_mesh, replica_meshes
        dp, tp = mesh_dims
        mesh = make_serving_mesh(dp, tp)
        rmesh = replica_meshes(mesh)[0]     # one replica's ("model",) row
        print(f"serving mesh: {dp} data-parallel replica(s) x {tp}-way "
              f"tensor parallel over {dp * tp} of {jax.device_count()} "
              f"devices")
    # born in replica 0's shardings: no device ever holds a whole-model
    # copy beside its shard (the other replicas copy from these)
    params = model.init(jax.random.key(0), mesh=rmesh)

    if args.continuous:
        import dataclasses as _dc

        from ..train.fault import ReplicaFaultPlan, ServeFaultPlan
        from .engine import (ContinuousEngine, ReplicatedEngine, Request,
                             synthetic_trace)
        from .journal import RequestJournal
        dl_rounds = (None if args.deadline_ms is None
                     else max(1, int(args.deadline_ms / args.round_ms)))
        if args.arrival_trace:
            reqs = []
            for i, tup in enumerate(args.arrival_trace.split(",")):
                parts = [int(x) for x in tup.split(":")]
                arr, plen, budget = parts[:3]
                pri = parts[3] if len(parts) > 3 else args.priority
                dl = (parts[4] if len(parts) > 4
                      else (arr + dl_rounds if dl_rounds else None))
                toks = jax.random.randint(jax.random.key(100 + i), (plen,),
                                          0, model.cfg.vocab)
                reqs.append(Request(rid=i, tokens=[int(t) for t in toks],
                                    max_new=budget, arrival=arr,
                                    priority=pri, deadline=dl))
        else:
            reqs = synthetic_trace(
                args.requests, args.slots, args.prompt_len, args.gen,
                model.cfg.vocab,
                flavor="soak" if args.soak else "chat")
            if args.priority or dl_rounds is not None:
                reqs = [_dc.replace(
                    r, priority=r.priority or args.priority,
                    deadline=(r.arrival + dl_rounds if dl_rounds
                              else r.deadline)) for r in reqs]
        plan = None
        rounds = lambda s: tuple(int(x) for x in s.split(",")) if s else ()
        if (args.fault_exhaust or args.fault_poison or args.fault_slow
                or args.fault_overflow or args.fault_corrupt_swap
                or args.soak):
            plan = ServeFaultPlan(
                exhaust_at=rounds(args.fault_exhaust) or
                ((args.gen,) if args.soak else ()),
                slow_at=rounds(args.fault_slow),
                poison_at=rounds(args.fault_poison),
                mask_poison=True,
                overflow_at=rounds(args.fault_overflow),
                overflow_scale=args.overflow_scale,
                corrupt_swap_at=rounds(args.fault_corrupt_swap))
        if args.degrade_fmt is not None:
            args.preempt = "swap"       # degradation rides the swap store
        esc = None
        if args.escalate is not None:
            from ..core.policy import EscalationPolicy
            esc = EscalationPolicy(
                ladder=tuple(args.escalate.split(",")),
                of_threshold=args.escalate_of_threshold)
        # speculative headroom: the verify chunk writes spec_k slots
        # past each row's budget, so the cache rows grow by K
        max_len = max(r.prompt_len + r.max_new for r in reqs) + args.speculate
        eng_kw = dict(slots=args.slots, max_len=max_len, chunk=args.chunk,
                      spec_k=args.speculate, draft_repeats=args.draft_layers,
                      draft_policy=args.draft_fmt,
                      n_pages=args.pool_pages, stop_token=args.stop_token,
                      temperature=args.temperature,
                      top_k=args.top_k, top_p=args.top_p,
                      seed=args.seed, burst_cap=args.burst_cap,
                      repetition_penalty=args.repetition_penalty,
                      presence_penalty=args.presence_penalty,
                      preempt=args.preempt, degrade_fmt=args.degrade_fmt,
                      shed=args.shed, fault_plan=plan, escalate=esc)
        rplan = None
        if fault_replica is not None:
            rplan = ReplicaFaultPlan(replica=fault_replica[0],
                                     at_burst=fault_replica[1],
                                     mode=fault_replica[2])
        journal = (RequestJournal(args.journal)
                   if args.journal is not None else None)
        replicated = dp > 1 or (args.replicas or 0) > 1
        if replicated:
            eng = ReplicatedEngine(model, params, mesh=mesh,
                                   replicas=args.replicas,
                                   migrate=args.migrate,
                                   replica_fault=rplan, journal=journal,
                                   **eng_kw)
        else:
            eng = ContinuousEngine(model, params, mesh=rmesh,
                                   journal=journal, **eng_kw)
        if rplan is not None or journal is not None:
            # single shot: the fault plan fires once per process and the
            # journal must stay one run's crash-consistent story — no
            # warm-up pass (compile time lands in the reported wall time)
            t0 = time.time()
            fin, stats = eng.run(reqs)
        else:
            fin, stats = eng.run(reqs)      # compile + warm
            t0 = time.time()
            fin, stats = eng.run(reqs)
        dt = time.time() - t0
        print(f"continuous engine: {args.slots} slots, page="
              f"{args.page_size}, chunk={args.chunk}, "
              f"{len(reqs)} requests, pool {stats['n_pages']} pages, "
              f"preempt={args.preempt}"
              + (f", degrade={args.degrade_fmt}" if args.degrade_fmt
                 else "")
              + (f", speculate k={args.speculate}"
                 + (f" draft_layers={args.draft_layers}"
                    if args.draft_layers is not None else "")
                 + (f" draft_fmt={args.draft_fmt}"
                    if args.draft_fmt else "")
                 if args.speculate else "")
              + (f", mesh {mesh_dims[0]}x{mesh_dims[1]}"
                 if mesh_dims else "")
              + (f", replicas={len(eng.engines)} migrate={args.migrate}"
                 if replicated else ""))
        for f in fin:
            trail = ""
            if f.preemptions:
                trail += f" preempted x{f.preemptions}"
            if f.sheds:
                trail += f" shed x{f.sheds}"
            if f.degraded:
                trail += " degraded"
            if f.escalated:
                trail += f" escalated L{f.escalated}"
            if f.deadline is not None:
                trail += (" DEADLINE MISS" if f.deadline_miss
                          else f" met r{f.deadline}")
            print(f"  req {f.rid:3d}: prompt {f.prompt_len:3d} -> "
                  f"{len(f.tokens):3d} tokens  (slot {f.slot}, admitted "
                  f"r{f.admit_round}, finished r{f.finish_round}){trail}")
        n_tok = sum(len(f.tokens) for f in fin)
        print(f"occupancy {stats['occupancy']:.2f} over "
              f"{stats['decode_rounds']} rounds / {stats['bursts']} "
              f"bursts; peak live pages {stats['peak_live_pages']} vs "
              f"{stats['fixed_equiv_pages']} fixed-batch equivalent "
              f"(pool {stats['n_pages']})")
        print(f"robustness: {stats['preemptions']} preemptions "
              f"({stats['preempt_swap']} swap / "
              f"{stats['preempt_reingest']} reingest), "
              f"{stats['shed_events']} sheds, {stats['degraded']} "
              f"degraded, {stats['deadline_misses']}/"
              f"{stats['deadline_total']} deadline misses, "
              f"{stats['poisoned_rounds']} poisoned rounds masked, "
              f"{stats['stragglers']} stragglers, "
              f"{stats['faults_exhaust']} exhaustion episodes")
        if args.speculate:
            print(f"speculative: accept rate "
                  f"{stats['spec_accept_rate']:.2f} over "
                  f"{stats['spec_rounds']} draft/verify row-rounds "
                  f"({stats['spec_emitted']} tokens emitted, chunk "
                  f"k+1={args.speculate + 1})")
        if esc is not None or plan is not None:
            print(f"numerical health: {stats.get('escalations', 0)} "
                  f"escalations ({stats.get('esc_deferred', 0)} deferred, "
                  f"{stats.get('esc_refused', 0)} refused), "
                  f"{stats.get('sdc_injected', 0)} SDC injected / "
                  f"{stats.get('sdc_detected', 0)} detected / "
                  f"{stats.get('sdc_reingest', 0)} recovered by reingest")
        if replicated:
            print(f"replica HA: {stats['ha_kills']} kills, "
                  f"{stats['ha_hangs']} hangs, {stats['ha_migrations']} "
                  f"migrations ({stats['ha_migrated_swap']} swap-blob / "
                  f"{stats['ha_migrated_reingest']} reingest); heartbeats "
                  + ", ".join(f"r{i}:{h['beats']}b/{h['missed']}m "
                              f"{h['status']}"
                              for i, h in enumerate(stats["heartbeats"])))
        if journal is not None:
            journal.close()
            print(f"journal {args.journal}: " + ", ".join(
                f"{v}x {k}" for k, v in sorted(journal.counts().items())))
        if plan is not None and plan.events:
            kinds = {}
            for k, _ in plan.events:
                kinds[k] = kinds.get(k, 0) + 1
            print(f"fault log: " + ", ".join(
                f"{v}x {k}" for k, v in sorted(kinds.items())))
        print(f"{args.arch} [continuous/{args.decode_backend}]: {n_tok} "
              f"tokens in {dt:.2f}s ({n_tok / dt:.1f} tok/s)")
        return
    max_len = args.prompt_len + args.gen
    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0,
                                 model.cfg.vocab)
    prompt_lens = None
    if args.ragged:
        lens = ragged_lengths(args.batch, args.prompt_len)
        prompt_lens = jnp.asarray(lens, jnp.int32)
        # zero the pad tail so the printed pack is honest about what's live
        live = jnp.arange(args.prompt_len)[None, :] < prompt_lens[:, None]
        prompts = jnp.where(live, prompts, 0)
        print(f"ragged pack: lengths {lens} padded to {args.prompt_len}")

    page_table, n_pages = None, None
    if args.paged and not args.ragged:
        # prefix-sharing demo: all rows share the first half of the prompt
        # (causal attention => identical K/V at shared positions), so every
        # page FULLY covered by the common prefix is stored once
        from ..models.paged import (PageAllocator, build_tables,
                                    identity_block_table, num_pages)
        common = args.prompt_len // 2
        prompts = jnp.concatenate(
            [jnp.broadcast_to(prompts[:1, :common],
                              (args.batch, common)), prompts[:, common:]], 1)
        mp = num_pages(max_len, args.page_size)
        n_pages = args.batch * mp
        alloc = PageAllocator(n_pages)
        shared = build_tables(alloc, args.batch, mp,
                              shared_pages=common // args.page_size)
        page_table = jnp.asarray(shared)
        print(f"paged pool: page={args.page_size}, "
              f"{alloc.n_live}/{n_pages} pages live with the shared "
              f"prefix ({common} common prompt tokens) vs {n_pages} "
              f"unshared")
        # parity gate: shared-prefix serving must be BIT-identical to the
        # unshared identity layout (prefill logits + generated tokens)
        par = jax.jit(lambda p, t, tb: model.generate(
            p, t, gen_len=args.gen, max_len=max_len, page_table=tb,
            n_pages=n_pages, return_logits=True))
        g_s, lg_s = par(params, prompts, page_table)
        g_u, lg_u = par(params, prompts,
                        jnp.asarray(identity_block_table(args.batch, mp)))
        d_tok = int(jnp.sum(g_s != g_u))
        d_lg = float(jnp.max(jnp.abs(lg_s - lg_u)))
        print(f"prefix-sharing parity: max |dlogits| = {d_lg:.1e}, "
              f"token mismatches = {d_tok} (both must be 0)")
        assert d_tok == 0 and d_lg == 0.0, "prefix sharing changed outputs"
    elif args.paged:
        print(f"paged pool: page={args.page_size}, identity table "
              f"(ragged rows keep private page runs)")

    key = jax.random.key(args.seed)
    # guard_nonfinite: every sampling site sanitizes its logits and
    # counts guarded rows — finite logits pass through bit-identical,
    # NaN/Inf ones abort serving instead of emitting garbage tokens
    gen_fn = jax.jit(lambda p, t, pl_, tb: model.generate(
        p, t, gen_len=args.gen, max_len=max_len,
        temperature=args.temperature, top_k=args.top_k,
        top_p=args.top_p, key=key, prompt_lens=pl_,
        stop_token=args.stop_token, page_table=tb, n_pages=n_pages,
        repetition_penalty=args.repetition_penalty,
        presence_penalty=args.presence_penalty,
        guard_nonfinite=True, mesh=rmesh)[::2])
    gen, bad = jax.block_until_ready(
        gen_fn(params, prompts, prompt_lens, page_table))
    t0 = time.time()
    gen, bad = jax.block_until_ready(
        gen_fn(params, prompts, prompt_lens, page_table))
    dt = time.time() - t0
    if int(jnp.sum(bad)) > 0:
        from ..train.fault import PoisonedLogitsError
        raise PoisonedLogitsError(
            f"non-finite logits at {int(jnp.sum(bad))} sampling steps "
            f"(rows {np.nonzero(np.asarray(bad))[0].tolist()})")
    n_tok = args.batch * args.gen
    if args.stop_token is not None:
        live_tok = int(jnp.sum(gen != args.stop_token)
                       + jnp.sum(jnp.any(gen == args.stop_token, 1)))
        print(f"stop-token {args.stop_token}: {live_tok}/{n_tok} "
              f"tokens live (rest frozen post-EOS)")
    tag = args.decode_backend + \
        (f"/paged{args.page_size}" if args.paged else "")
    print(f"{args.arch} [{tag}]: "
          f"{n_tok} tokens in {dt:.2f}s ({n_tok / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
