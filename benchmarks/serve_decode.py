"""Serving benchmark: prefill latency + steady-state decode tok/s.

Prefill is A/B'd dense-vs-pallas (``prefill_dense_ms`` / ``prefill_pallas_ms``:
the pure-JAX chunked softmax vs the pruned-grid Pallas flash-attention
kernel behind ``cfg.prefill_backend``), and three decode paths are compared,
on reduced archs (CPU; the same code runs compiled on TPU):

  * ``python``      — the seed per-step loop: one jit'd ``decode_step``
                      dispatch per generated token.
  * ``scan``        — ``Model.generate``: the whole generation is ONE
                      compiled ``lax.scan`` (one dispatch total).
  * ``scan+pallas`` — the scan loop with the fused in-kernel KV-dequant
                      Pallas decode-attention kernel under an fp8 KV cache
                      (policy tp_bf16_kv8): the quantized-cache serving
                      scenario of the FPnew storage-format story.

Steady-state tok/s for the scan paths is measured by differencing two
generation lengths (removes prefill + constant dispatch cost); the python
loop is timed directly over its steps (that IS its steady state).

Ragged A/B (``ragged_prefill_ms`` / ``ragged_decode_tok_s``): the same
padded batch served with four mixed prompt lengths (1/4, 1/2, 3/4, 4/4 of
``prompt_len``) through the per-sequence length plumbing, against the
uniform-padded baseline (``prefill_dense_ms`` / ``scan_tok_s`` — every row
paying the max length).  On CPU the dense path only saves masked-out FLOPs
the hardware still executes; the per-row grid pruning shows up on real
accelerators, where the Pallas kernels skip each row's dead KV blocks.

Paged A/B (``paged_decode_tok_s`` / ``paged_page_size``): the same scan
generation served from the paged KV cache (shared page pool + block-table
indirection, identity tables) against the contiguous baseline
(``scan_tok_s``).  On CPU the dense decode path pays a per-step gather to
rebuild the contiguous view — the column tracks that overhead honestly;
on TPU the Pallas kernel dereferences the table in its index maps and the
gather disappears.  Archs whose mixers cannot page (SSM/MLA/cross-attn)
carry null paged columns, like the ragged ones.

Continuous A/B (``continuous_decode_tok_s`` / ``fixed_batch_tok_s`` /
``continuous_speedup`` / ``continuous_batch_occupancy`` /
``peak_live_pages``): the PR-5 continuous-batching engine
(launch/engine.py — while_loop decode bursts, page-recycling admission,
chunked prefill) against fixed FIFO batches on ONE deterministic
heavy-tail arrival trace (``engine.synthetic_trace``).  Both sides serve
the same requests on the same slot count; useful tokens = the sum of
per-request budgets.  Fixed batching runs every batch to its max budget
(padding short rows — the pre-engine loop's cost model) while the engine
frees a finished row's pages the round it finishes and admits from the
queue mid-generation; ``peak_live_pages`` tracks the pool high-water mark
against the ``slots x max_pages`` a fixed paged batch pins for the whole
run.  Archs that cannot page carry null continuous columns.

Speculative A/B (``spec_decode_tok_s`` / ``spec_accept_rate`` /
``spec_token_parity``): the PR-9 transprecision speculative decoder — a
shallow layer-skip draft proposes k tokens per row, one chunk-scoring
verify call at target precision accepts the longest matching prefix —
against the plain greedy engine on the same trace.  Parity must be TRUE:
speculation is only allowed to change speed, never a token.

Replica-HA soak (``ha_drained`` / ``ha_kills`` / ``ha_migrations`` /
``ha_token_parity`` / ``ha_replay_parity``): the PR-10 fault-tolerance
machinery on a multi-turn ``flavor="session"`` trace — a 2-replica fleet
loses one replica to an injected kill and must drain with tokens identical
to the unfailed fleet, then a single-replica journaled fleet is killed
outright and ``run_with_restarts`` + the request journal must recover the
same streams.  Both parity columns gate TRUE; archs that cannot page carry
nulls.

Writes BENCH_serve.json at the repo root so the serving-perf trajectory is
tracked PR-over-PR.

``PYTHONPATH=src python -m benchmarks.serve_decode [--quick]``
"""
from __future__ import annotations

import argparse
import json
import os
import time

ARCHS = ("gemma2-9b", "qwen3-moe-30b-a3b")
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BENCH_serve.json") if "__file__" in globals() else \
    "BENCH_serve.json"


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _time_call(fn, repeats=3):
    import jax
    jax.block_until_ready(fn())          # compile + warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return _median(ts)


def bench_arch(arch: str, *, batch: int, prompt_len: int, gen: int,
               repeats: int = 3, quick: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.models.registry import build_model

    short = max(2, gen // 4)
    row = {"batch": batch, "prompt_len": prompt_len, "gen": gen}

    def build(policy, backend):
        model = build_model(arch, policy=policy,
                            reduced=True).with_cfg(decode_backend=backend)
        params = model.init(jax.random.key(0))
        prompts = jax.random.randint(
            jax.random.key(1), (batch, prompt_len), 0, model.cfg.vocab)
        return model, params, prompts

    max_len = prompt_len + gen
    model, params, prompts = build("tp_bf16", "dense")

    # -- prefill latency: dense vs pruned-grid Pallas A/B -------------------
    # (pallas runs in interpret mode on CPU — expected to lose here; the A/B
    # tracks both so the TPU rerun lands in the same columns.)
    prefill = jax.jit(lambda p, t: model.prefill(p, t, max_len=max_len))
    row["prefill_ms"] = _time_call(
        lambda: prefill(params, prompts)[0], repeats) * 1e3
    row["prefill_dense_ms"] = row["prefill_ms"]
    model_pp = model.with_cfg(prefill_backend="pallas")
    prefill_pp = jax.jit(lambda p, t: model_pp.prefill(p, t, max_len=max_len))
    row["prefill_pallas_ms"] = _time_call(
        lambda: prefill_pp(params, prompts)[0], repeats) * 1e3

    # -- python per-step loop (the seed path) -------------------------------
    step = jax.jit(lambda p, t, c, i: model.decode_step(p, t, c, i))
    lg, caches0 = prefill(params, prompts)
    tok0 = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
    _ = jax.block_until_ready(step(params, tok0, caches0, prompt_len)[0])

    def run_loop():
        tok, caches = tok0, caches0
        for i in range(gen - 1):
            lg, caches = step(params, tok, caches, prompt_len + i)
            tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        return tok

    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run_loop())
        ts.append(time.perf_counter() - t0)
    row["python_tok_s"] = batch * (gen - 1) / _median(ts)

    # -- scan paths ---------------------------------------------------------
    def scan_tok_s(model, params, prompts, prompt_lens=None, key=""):
        long_fn = jax.jit(lambda p, t, l: model.generate(
            p, t, gen_len=gen, max_len=max_len, prompt_lens=l)[0])
        short_fn = jax.jit(lambda p, t, l: model.generate(
            p, t, gen_len=short, max_len=max_len, prompt_lens=l)[0])
        t_long = _time_call(lambda: long_fn(params, prompts, prompt_lens),
                            repeats)
        t_short = _time_call(lambda: short_fn(params, prompts, prompt_lens),
                             repeats)
        dt = t_long - t_short
        if dt <= 0:
            # timing noise swamped the per-token cost (tiny model / loaded
            # box): report the conservative whole-run rate instead of an
            # astronomical differenced number, and flag it in the row
            print(f"  [warn] unstable {key or 'scan'} differencing "
                  f"(dt={dt * 1e3:.3f} ms); falling back to whole-run rate",
                  flush=True)
            row[f"{key}steady_state_unstable"] = True
            return batch * gen / t_long
        return batch * (gen - short) / dt

    row["scan_tok_s"] = scan_tok_s(model, params, prompts)
    row["scan_speedup"] = row["scan_tok_s"] / row["python_tok_s"]

    # -- ragged A/B: 4 mixed prompt lengths vs the uniform-padded batch -----
    # (attention archs only: Model.prefill refuses prompt_lens for SSM /
    # hybrid mixers — recurrent state can't mask pad tokens — so those rows
    # carry null ragged columns, keeping the ci.sh schema gate honest.)
    from repro.launch.serve import ragged_lengths
    lens = ragged_lengths(batch, prompt_len)
    row["ragged_lens"] = lens
    if any(s.mixer in ("mamba2", "mlstm", "slstm")
           for s in model.cfg.layer_list()):
        row["ragged_prefill_ms"] = None
        row["ragged_decode_tok_s"] = None
        row["ragged_unsupported"] = "ssm mixers"
    else:
        prompt_lens = jnp.asarray(lens, jnp.int32)
        prefill_rg = jax.jit(lambda p, t, l: model.prefill(
            p, t, max_len=max_len, prompt_lens=l))
        row["ragged_prefill_ms"] = _time_call(
            lambda: prefill_rg(params, prompts, prompt_lens)[0],
            repeats) * 1e3
        row["ragged_decode_tok_s"] = scan_tok_s(model, params, prompts,
                                                prompt_lens, key="ragged_")

    # -- paged KV A/B: block-table pool vs the contiguous cache -------------
    page = max(8, prompt_len // 2)
    row["paged_page_size"] = page
    paged_why = model.cfg.paged_unsupported_reason()
    if paged_why is not None:
        row["paged_decode_tok_s"] = None
        row["paged_unsupported"] = paged_why
    else:
        model_pg = model.with_cfg(paged_kv=True, page_size=page)
        row["paged_decode_tok_s"] = scan_tok_s(model_pg, params, prompts,
                                               key="paged_")

    # -- continuous-vs-fixed A/B on a deterministic arrival trace -----------
    # (the PR-5 serving engine: while_loop decode bursts, page-recycling
    # admission, chunked prefill.  BOTH sides serve the SAME heavy-tail
    # trace on the same slot count: fixed batching runs each batch to its
    # max budget via the scan path — the pre-engine serving loop — while
    # the engine pays each row only its own budget and backfills freed
    # slots.  Archs that cannot page carry null columns.)
    cont = continuous_ab(arch, prompt_len=prompt_len, quick=quick)
    row.update(cont)

    # -- speculative-vs-plain A/B on the same engine + trace ----------------
    # (the PR-9 transprecision speculative decoder: a layer-skip draft
    # proposes k tokens per row, one chunk-scoring verify at target
    # precision accepts the longest matching prefix.  The accepted stream
    # must be BIT-IDENTICAL to plain greedy serving — ``spec_token_parity``
    # gates it — so the only thing speculation may change is speed.)
    row.update(speculative_ab(arch, prompt_len=prompt_len, quick=quick))

    # -- robustness soak: overload + injected faults must drain -------------
    # (the PR-6 backpressure machinery: bursty over-committed arrivals on a
    # constrained page pool with injected exhaustion / stragglers / poisoned
    # logits.  The gate is DRAINAGE — every request finishes its full
    # budget — with the preempt/shed/degrade/deadline counters recorded.)
    row.update(robustness_soak(arch, prompt_len=prompt_len, quick=quick))

    # -- replica HA soak: kill one replica, migrate, drain; replay a full
    # -- fleet loss from the request journal (the PR-10 machinery) ----------
    row.update(ha_soak(arch, prompt_len=prompt_len, quick=quick))

    # -- numerical health: flag-telemetry overhead + escalation/SDC soak ----
    # (the PR-7 machinery: IEEE flag counters in the decode kernel, flag-
    # driven KV-precision escalation, checksummed swap payloads.)
    row.update(flag_overhead(repeats=repeats))
    row.update(numerical_health_soak(arch, prompt_len=prompt_len,
                                     quick=quick))

    # -- mesh-sharded serving A/B + simulated-fleet dryrun stats ------------
    # (the PR-8 tensor-parallel machinery: head-sharded attention + paged
    # pools over the `model` axis, psum'd output projections.  Runs in a
    # SUBPROCESS with 8 forced host devices — the parent must keep its
    # single real CPU device for every other timing column.)
    row.update(shard_ab(arch, prompt_len=prompt_len, quick=quick))

    # -- scan + fused Pallas decode kernel over an fp8 KV cache -------------
    row["scan_pallas_kv8_tok_s"] = scan_tok_s(*build("tp_bf16_kv8", "pallas"))
    return row


def shard_probe(arch: str, *, prompt_len: int, gen: int = 64,
                slots: int = 4, n_req: int = 12) -> dict:
    """Tensor-parallel vs single-device continuous serving, INSIDE the
    multi-device subprocess (both legs share the 8-device process so the
    A/B is apples-to-apples).  The tp leg head-shards attention + the
    paged KV pools over a ``("model",)`` mesh; tokens must match the
    unsharded leg exactly (per-head attention is bitwise, the psum'd
    projection snaps once after an fp32 reduction — see
    docs/ARCHITECTURE.md).  On simulated CPU devices the shard_map
    overhead usually LOSES to single-device — ``shard_speedup`` tracks
    the honest ratio; the column exists so the TPU rerun lands in it."""
    import jax
    from repro.launch.engine import ContinuousEngine, synthetic_trace
    from repro.launch.mesh import make_serving_mesh, replica_meshes
    from repro.models.registry import build_model

    model = build_model(arch, policy="tp_bf16", reduced=True)
    why = model.cfg.paged_unsupported_reason()
    nulls = {"shard_devices": None, "shard_decode_tok_s": None,
             "shard_base_tok_s": None, "shard_speedup": None,
             "shard_token_parity": None}
    if why is not None:
        return dict(nulls, shard_unsupported=why)
    h, hkv = model.cfg.n_heads, model.cfg.n_kv_heads
    tp = next((t for t in (8, 4, 2)
               if t <= jax.device_count() and h % t == 0 and hkv % t == 0),
              1)
    if tp < 2:
        return dict(nulls,
                    shard_unsupported=f"no head split: h={h} hkv={hkv} "
                                      f"devices={jax.device_count()}")
    model_pg = model.with_cfg(paged_kv=True, page_size=16)
    params = model_pg.init(jax.random.key(0))
    max_len = prompt_len + gen
    reqs = synthetic_trace(n_req, slots, prompt_len, gen, model.cfg.vocab)
    useful = sum(r.max_new for r in reqs)

    def leg(mesh):
        eng = ContinuousEngine(model_pg, params, slots=slots,
                               max_len=max_len, chunk=16, burst_cap=256,
                               mesh=mesh)
        eng.run(reqs)                              # compile + warm
        t0 = time.perf_counter()
        fin, _ = eng.run(reqs)
        return useful / (time.perf_counter() - t0), fin

    base_rate, fin_a = leg(None)
    mesh = replica_meshes(make_serving_mesh(1, tp))[0]
    shard_rate, fin_b = leg(mesh)
    return {
        "shard_devices": tp,
        "shard_decode_tok_s": shard_rate,
        "shard_base_tok_s": base_rate,
        "shard_speedup": shard_rate / base_rate,
        "shard_token_parity": all(a.tokens == b.tokens
                                  for a, b in zip(fin_a, fin_b)),
    }


def shard_ab(arch: str, *, prompt_len: int, quick: bool = False) -> dict:
    """Drive ``shard_probe`` in a subprocess with 8 forced host devices,
    then collect dryrun cost/memory stats for the production serving
    shape at 256 (single-pod) and 512 (multi-pod) simulated devices.
    Skipped entirely under ``--quick`` (CI smoke keeps one device)."""
    import subprocess
    import sys
    import tempfile

    if quick:
        return {}
    # the children simulate CPU meshes by design: pin them to the CPU so
    # they never reach for an accelerator this process already holds
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.serve_decode",
         "--shard-probe", arch, "--prompt-len", str(prompt_len)],
        capture_output=True, text=True, env=env)
    out = None
    for line in r.stdout.splitlines():
        if line.startswith("SHARD_JSON "):
            out = json.loads(line[len("SHARD_JSON "):])
    if out is None:
        raise RuntimeError(
            f"shard probe subprocess failed for {arch} "
            f"(rc={r.returncode}):\n{(r.stderr or '')[-2000:]}")
    assert out.get("shard_token_parity") in (True, None), \
        f"sharded serving changed tokens for {arch}"

    # dryrun leg: lower + compile the decode cell on the 256- and
    # 512-device production meshes and record the per-device footprint
    devs, peak, flops = [], [], []
    for mp in (False, True):
        with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", "decode_32k",
                   "--json", tmp.name] + (["--multi-pod"] if mp else [])
            rr = subprocess.run(cmd, capture_output=True, text=True,
                                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            try:
                with open(tmp.name) as f:
                    rec = json.load(f)
            except (OSError, ValueError):
                rec = {}
        if not rec.get("ok"):
            raise RuntimeError(
                f"dryrun decode_32k {'pod2' if mp else 'pod1'} failed for "
                f"{arch} (rc={rr.returncode}):\n"
                f"{rec.get('error', (rr.stderr or '')[-2000:])}")
        devs.append(rec["n_devices"])
        peak.append(rec["memory"]["peak_bytes"])
        flops.append(rec["hlo"]["flops"])
    out.update(shard_dryrun_devices=devs, shard_dryrun_peak_bytes=peak,
               shard_dryrun_flops=flops)
    return out


def continuous_ab(arch: str, *, prompt_len: int, quick: bool = False,
                  slots: int = 8, gen_long: int = 192,
                  n_req: int = 48) -> dict:
    """Continuous-batching engine vs fixed batches on one arrival trace.

    Useful tokens = the sum of per-request budgets (identical on both
    sides; the fixed batches' padding tokens past a row's budget are waste,
    which is exactly the point).  Also records mean batch-slot occupancy
    and the page pool's high-water mark against the ``slots x max_pages``
    a fixed paged batch would pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.engine import ContinuousEngine, synthetic_trace
    from repro.models.registry import build_model

    if quick:
        slots, gen_long, n_req = 4, 32, 10
    model = build_model(arch, policy="tp_bf16", reduced=True)
    why = model.cfg.paged_unsupported_reason()
    if why is not None:
        return {"continuous_decode_tok_s": None, "fixed_batch_tok_s": None,
                "continuous_speedup": None, "continuous_batch_occupancy":
                None, "peak_live_pages": None, "continuous_unsupported": why}
    page = 16
    model_pg = model.with_cfg(paged_kv=True, page_size=page)
    params = model_pg.init(jax.random.key(0))
    max_len = prompt_len + gen_long
    reqs = synthetic_trace(n_req, slots, prompt_len, gen_long,
                           model.cfg.vocab)
    useful = sum(r.max_new for r in reqs)

    eng = ContinuousEngine(model_pg, params, slots=slots, max_len=max_len,
                           chunk=16, burst_cap=256)
    eng.run(reqs)                                  # compile + warm
    ts = []
    for _ in range(1 if quick else 3):
        t0 = time.perf_counter()
        fin, st = eng.run(reqs)
        ts.append(time.perf_counter() - t0)
    dt_c = _median(ts)
    assert all(len(f.tokens) == r.max_new for f, r in zip(fin, reqs))

    # fixed baseline: FIFO batches of up to `slots` arrived requests, each
    # run to its max budget through the scan path (the pre-engine loop)
    def fixed_plan():
        q = sorted(reqs, key=lambda r: (r.arrival, r.rid))
        plan, clock, i = [], 0, 0
        while i < len(q):
            clock = max(clock, q[i].arrival)
            batch = [r for r in q[i:i + slots] if r.arrival <= clock]
            i += len(batch)
            g = max(r.max_new for r in batch)
            plan.append((batch, g))
            clock += g
        return plan

    plan = fixed_plan()
    fns = {}

    def fx(bsz, g):
        if (bsz, g) not in fns:
            fns[(bsz, g)] = jax.jit(lambda p, t, l: model_pg.generate(
                p, t, gen_len=g, max_len=max_len, prompt_lens=l)[0])
        return fns[(bsz, g)]

    def batch_args(batch):
        toks = np.zeros((len(batch), prompt_len), np.int32)
        lens = np.asarray([r.prompt_len for r in batch], np.int32)
        for j, r in enumerate(batch):
            toks[j, :r.prompt_len] = r.tokens
        return jnp.asarray(toks), jnp.asarray(lens)

    for batch, g in plan:                          # compile + warm
        t, l = batch_args(batch)
        jax.block_until_ready(fx(len(batch), g)(params, t, l))
    ts = []
    for _ in range(1 if quick else 3):
        t0 = time.perf_counter()
        for batch, g in plan:
            t, l = batch_args(batch)
            jax.block_until_ready(fx(len(batch), g)(params, t, l))
        ts.append(time.perf_counter() - t0)
    dt_f = _median(ts)

    return {
        "continuous_decode_tok_s": useful / dt_c,
        "fixed_batch_tok_s": useful / dt_f,
        "continuous_speedup": dt_f / dt_c,
        "continuous_batch_occupancy": st["occupancy"],
        "peak_live_pages": st["peak_live_pages"],
        "continuous_fixed_equiv_pages": st["fixed_equiv_pages"],
        "continuous_slots": slots,
        "continuous_n_requests": n_req,
        "continuous_useful_tokens": useful,
        "continuous_rounds": st["rounds"],
        "continuous_bursts": st["bursts"],
    }


def speculative_ab(arch: str, *, prompt_len: int, quick: bool = False,
                   slots: int = 4, gen: int = 64, n_req: int = 12,
                   spec_k: int = 3, draft_repeats: int = 1) -> dict:
    """Speculative-vs-plain continuous serving on one arrival trace.

    Both engines serve the SAME deterministic trace on the same slots;
    the speculative leg drafts ``spec_k`` tokens per row with a
    ``draft_repeats``-deep layer-skip pass and verifies the chunk in one
    target-precision call.  ``spec_token_parity`` asserts the headline
    guarantee — every request's accepted stream equals the plain greedy
    engine's bit for bit — and ``spec_accept_rate`` (emitted tokens over
    ``live-row-rounds x (k+1)``) tracks how much of each draft survives.
    On CPU the draft pass is real compute on the critical path, so the
    speedup is honest-but-pessimistic; on accelerators the narrow-format
    draft is where the transprecision energy story cashes out.  Archs
    that cannot page carry nulls."""
    import jax
    from repro.launch.engine import ContinuousEngine, synthetic_trace
    from repro.models.registry import build_model

    if quick:
        slots, gen, n_req = 2, 16, 6
    keys = ("spec_decode_tok_s", "spec_plain_tok_s", "spec_speedup",
            "spec_accept_rate", "spec_token_parity")
    model = build_model(arch, policy="tp_bf16", reduced=True)
    why = model.cfg.paged_unsupported_reason()
    if why is not None:
        out = {k: None for k in keys}
        out["spec_unsupported"] = why
        return out
    model_pg = model.with_cfg(paged_kv=True, page_size=16)
    params = model_pg.init(jax.random.key(0))
    max_len = prompt_len + gen + spec_k        # draft lookahead headroom
    reqs = synthetic_trace(n_req, slots, prompt_len, gen, model.cfg.vocab)
    useful = sum(r.max_new for r in reqs)

    def leg(**kw):
        eng = ContinuousEngine(model_pg, params, slots=slots,
                               max_len=max_len, chunk=16, burst_cap=64,
                               **kw)
        eng.run(reqs)                              # compile + warm
        ts = []
        for _ in range(1 if quick else 3):
            t0 = time.perf_counter()
            fin, st = eng.run(reqs)
            ts.append(time.perf_counter() - t0)
        return useful / _median(ts), fin, st

    plain_rate, fin_p, _ = leg()
    spec_rate, fin_s, st = leg(spec_k=spec_k, draft_repeats=draft_repeats)
    return {
        "spec_decode_tok_s": spec_rate,
        "spec_plain_tok_s": plain_rate,
        "spec_speedup": spec_rate / plain_rate,
        "spec_accept_rate": st["spec_accept_rate"],
        "spec_token_parity": (
            len(fin_s) == len(fin_p) == n_req
            and all(a.tokens == b.tokens for a, b in zip(fin_s, fin_p))),
        "spec_k": spec_k,
        "spec_draft_repeats": draft_repeats,
        "spec_rounds": st["spec_rounds"],
        "spec_emitted": st["spec_emitted"],
    }


def robustness_soak(arch: str, *, prompt_len: int, quick: bool = False,
                    slots: int = 4, gen: int = 64, n_req: int = 24) -> dict:
    """Overload soak through the robustness machinery.

    The soak trace (``synthetic_trace(flavor="soak")``: arrival bursts far
    wider than ``slots``, long documents, mixed priorities, deadlines on
    the top tier, quality-sensitive ``no_degrade`` requests) is served on
    a page pool sized to about HALF the worst-case reservation, with a
    ``ServeFaultPlan`` injecting pool exhaustion, a straggler stall and
    masked NaN logits.  The engine must drain it completely — zero stuck,
    zero lost, every budget honored — by preempting (swap-to-host, fp8
    degraded where permitted), shedding with backoff and deadline-aware
    scheduling.  The counters land in BENCH_serve.json as the robustness
    trajectory; archs that cannot page carry nulls."""
    import jax
    from repro.launch.engine import ContinuousEngine, synthetic_trace
    from repro.models.paged import num_pages
    from repro.models.registry import build_model
    from repro.train.fault import ServeFaultPlan

    if quick:
        slots, gen, n_req = 2, 16, 8
    model = build_model(arch, policy="tp_bf16", reduced=True)
    why = model.cfg.paged_unsupported_reason()
    keys = ("soak_drained", "soak_requests", "soak_tok_s",
            "soak_preemptions", "soak_shed_events", "soak_degraded",
            "soak_deadline_miss_rate", "soak_poisoned_rounds",
            "soak_faults_exhaust")
    if why is not None:
        out = {k: None for k in keys}
        out["soak_unsupported"] = why
        return out
    page = 16
    model_pg = model.with_cfg(paged_kv=True, page_size=page)
    params = model_pg.init(jax.random.key(0))
    max_len = prompt_len + gen
    reqs = synthetic_trace(n_req, slots, prompt_len, gen, model.cfg.vocab,
                           flavor="soak")
    worst = max(num_pages(r.prompt_len + r.max_new, page) for r in reqs)
    # ~half the worst-case steady-state reservation: admission cannot hold
    # every slot's worst case, so preemption/shedding must engage
    n_pages = max(worst + 2, (slots * worst) // 2 + 1)
    plan = ServeFaultPlan(exhaust_at=(gen // 2, 3 * gen), exhaust_for=4,
                          slow_at=(gen // 4,), slow_s=0.01,
                          poison_at=tuple(range(gen // 2, gen // 2 + 4)),
                          mask_poison=True)
    eng = ContinuousEngine(model_pg, params, slots=slots, max_len=max_len,
                           chunk=16, n_pages=n_pages, preempt="swap",
                           degrade_fmt="fp8", fault_plan=plan)
    eng.run(reqs)                                  # compile + warm
    t0 = time.perf_counter()
    fin, st = eng.run(reqs)
    dt = time.perf_counter() - t0
    drained = (len(fin) == n_req
               and all(len(f.tokens) == r.max_new
                       for f, r in zip(fin, reqs)))
    return {
        "soak_drained": drained,
        "soak_requests": n_req,
        "soak_tok_s": sum(len(f.tokens) for f in fin) / dt,
        "soak_preemptions": st["preemptions"],
        "soak_shed_events": st["shed_events"],
        "soak_degraded": st["degraded"],
        "soak_deadline_miss_rate": st["deadline_miss_rate"],
        "soak_poisoned_rounds": st["poisoned_rounds"],
        "soak_faults_exhaust": st["faults_exhaust"],
        "soak_pool_pages": n_pages,
        "soak_deadline_total": st["deadline_total"],
    }


def ha_soak(arch: str, *, prompt_len: int, quick: bool = False,
            slots: int = 3, gen: int = 16, n_req: int = 12) -> dict:
    """Replica-HA soak: fleet survives a kill; a full loss replays.

    Two legs over ONE multi-turn ``flavor="session"`` trace (later turns
    re-send the whole conversation as a growing shared prefix, so a
    migrated session resumes mid-conversation):

    * **kill leg** — a 2-replica meshless fleet loses one replica to an
      injected kill mid-run; the survivor adopts the victim's in-flight
      requests by free-and-reingest and the fleet must DRAIN (every
      budget honored) with tokens identical to the unfailed fleet
      (``ha_token_parity``).
    * **replay leg** — a single-replica journaled fleet is killed with
      NO survivor; ``run_with_restarts`` restarts it and the journal
      replays every unfinished request from its last journaled token —
      ``ha_replay_parity`` gates the recovered streams against the same
      oracle.

    Archs that cannot page carry nulls, like the other serving legs."""
    import jax
    from repro.launch.engine import ReplicatedEngine, synthetic_trace
    from repro.launch.journal import RequestJournal
    from repro.models.registry import build_model
    from repro.train.fault import ReplicaFaultPlan, run_with_restarts

    if quick:
        slots, n_req = 2, 8
    keys = ("ha_drained", "ha_requests", "ha_kills", "ha_migrations",
            "ha_token_parity", "ha_replay_parity", "ha_tok_s",
            "ha_journal_records")
    model = build_model(arch, policy="tp_bf16", reduced=True)
    why = model.cfg.paged_unsupported_reason()
    if why is not None:
        out = {k: None for k in keys}
        out["ha_unsupported"] = why
        return out
    page = 16
    model_pg = model.with_cfg(paged_kv=True, page_size=page)
    params = model_pg.init(jax.random.key(0))
    reqs = synthetic_trace(n_req, slots, prompt_len, gen, model.cfg.vocab,
                           flavor="session")
    ml = max(r.prompt_len + r.max_new for r in reqs)

    def mk(n, **kw):
        return ReplicatedEngine(model_pg, params, replicas=n, slots=slots,
                                max_len=ml, chunk=16, burst_cap=2,
                                migrate="reingest", **kw)

    base, _ = mk(2).run(reqs)                      # oracle + compile warm
    t0 = time.perf_counter()
    fin, st = mk(2, replica_fault=ReplicaFaultPlan(
        replica=1, at_burst=2, mode="kill")).run(reqs)
    dt = time.perf_counter() - t0
    drained = (len(fin) == n_req
               and all(len(f.tokens) == r.max_new
                       for f, r in zip(fin, reqs)))
    parity = all(a.tokens == b.tokens for a, b in zip(fin, base))

    jr = RequestJournal()
    solo = mk(1, replica_fault=ReplicaFaultPlan(
        replica=0, at_burst=2, mode="kill"), journal=jr).bind(reqs)
    _, restarts = run_with_restarts(lambda: solo, max_restarts=2)
    fin2, _ = solo.run()        # answered from the journal's finish records
    replay_parity = (restarts >= 1
                     and all(a.tokens == b.tokens
                             for a, b in zip(fin2, base)))
    return {
        "ha_drained": drained,
        "ha_requests": n_req,
        "ha_kills": st["ha_kills"],
        "ha_migrations": st["ha_migrations"],
        "ha_token_parity": parity,
        "ha_replay_parity": replay_parity,
        "ha_tok_s": sum(len(f.tokens) for f in fin) / dt,
        "ha_journal_records": sum(jr.counts().values()),
    }


def flag_overhead(repeats: int = 3) -> dict:
    """Flag-telemetry overhead A/B on the fused decode kernel.

    Times ``kernels.ops.decode_attention`` over an fp8-container ragged KV
    strip with ``return_flags`` off vs on — the cost of accumulating the
    per-block IEEE OF/UF/NX/NV counters alongside the attention math
    (docs/KERNELS.md).  On CPU both sides run the Pallas interpreter, so
    the ratio is a loose upper bound; on TPU the counters are a handful of
    vector compares + integer adds per visited tile."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.policy import get_policy
    from repro.kernels import ops as kops

    pol = get_policy("em_fp8").replace(kv_fmt="fp8")
    rs = np.random.RandomState(0)
    b, h, hkv, s, d = 4, 8, 2, 256, 64
    q = jnp.asarray(rs.randn(b, h, 1, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, hkv, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, hkv, s, d), jnp.float32)
    kv_len = jnp.asarray([s, s // 2, 3, s], jnp.int32)

    plain = jax.jit(lambda q, k, v, l: kops.decode_attention(
        q, k, v, kv_len=l, policy=pol, interpret=True))
    flagged = jax.jit(lambda q, k, v, l: kops.decode_attention(
        q, k, v, kv_len=l, policy=pol, interpret=True, return_flags=True))
    t_off = _time_call(lambda: plain(q, k, v, kv_len), repeats)
    t_on = _time_call(lambda: flagged(q, k, v, kv_len)[0], repeats)
    return {
        "flag_decode_ms": t_off * 1e3,
        "flag_decode_flags_ms": t_on * 1e3,
        "flag_telemetry_overhead": t_on / t_off,
    }


def numerical_health_soak(arch: str, *, prompt_len: int,
                          quick: bool = False, slots: int = 4,
                          gen: int = 48, n_req: int = 12) -> dict:
    """Deterministic numerical-health soak: escalation + SDC-checked swap.

    Two engine runs prove the numerical-health gates end-to-end:

    * **escalation leg** — the fp32 wide-container pool with the
      ``fp8 -> fp16 -> fp16alt`` ladder and an injected write-side K/V
      overflow (``overflow_at`` scales the rows' K/V writes by 2^16): the
      saturating casts keep logits finite while OF pressure crosses the
      threshold, the engine re-ingests the pressured rows one rung wider
      between bursts, and every request still drains its full budget with
      zero poisoned rounds and no ``PoisonedLogitsError``.
    * **SDC leg** — swap-mode preemption on a half-sized page pool with a
      bit flip injected into the first swap payloads: every corruption
      must be caught by the CRC32 check at swap-in (injected == detected,
      zero undetected) and recovered by re-ingest with tokens IDENTICAL
      to an uncorrupted twin of the same run.

    Both legs replay one deterministic fault plan; archs that cannot page
    carry nulls, like the ragged/paged columns."""
    import jax
    from repro.core.policy import EscalationPolicy
    from repro.launch.engine import ContinuousEngine, synthetic_trace
    from repro.models.paged import num_pages
    from repro.models.registry import build_model
    from repro.train.fault import ServeFaultPlan

    if quick:
        slots, gen, n_req = 2, 16, 6
    keys = ("esc_soak_drained", "esc_soak_escalations",
            "esc_soak_escalated_requests", "esc_soak_deferred",
            "esc_soak_refused", "esc_soak_poisoned_rounds",
            "esc_soak_tok_s", "sdc_soak_injected", "sdc_soak_detected",
            "sdc_soak_reingest", "sdc_soak_token_parity")
    model = build_model(arch, policy="fp32", reduced=True)
    why = model.cfg.paged_unsupported_reason()
    if why is not None:
        out = {k: None for k in keys}
        out["health_soak_unsupported"] = why
        return out
    page = 16
    max_len = prompt_len + gen

    # -- escalation leg: overflow fault -> saturate -> escalate -> drain ----
    model_pg = model.with_cfg(paged_kv=True, page_size=page)
    params = model_pg.init(jax.random.key(0))
    reqs = synthetic_trace(n_req, slots, prompt_len, gen, model.cfg.vocab)
    worst = max(num_pages(r.prompt_len + r.max_new, page) for r in reqs)
    plan = ServeFaultPlan(overflow_at=(3, 4), overflow_scale=65536.0)
    eng = ContinuousEngine(
        model_pg, params, slots=slots, max_len=max_len, chunk=16,
        n_pages=slots * worst + 2, burst_cap=8, fault_plan=plan,
        escalate=EscalationPolicy(of_threshold=4))
    eng.run(reqs)                                  # compile + warm
    t0 = time.perf_counter()
    fin, st = eng.run(reqs)
    dt = time.perf_counter() - t0
    esc_drained = (len(fin) == n_req
                   and all(len(f.tokens) == r.max_new
                           for f, r in zip(fin, reqs)))
    out = {
        "esc_soak_drained": esc_drained,
        "esc_soak_escalations": st["escalations"],
        "esc_soak_escalated_requests": sum(1 for f in fin if f.escalated),
        "esc_soak_deferred": st["esc_deferred"],
        "esc_soak_refused": st["esc_refused"],
        "esc_soak_poisoned_rounds": st["poisoned_rounds"],
        "esc_soak_tok_s": sum(len(f.tokens) for f in fin) / dt,
    }

    # -- SDC leg: corrupted swap payloads must be detected + recovered ------
    # (bf16 pool under page pressure so swap preemption actually engages;
    # the clean twin pins the recovered tokens bit-for-bit.)
    model_sw = build_model(arch, policy="tp_bf16", reduced=True).with_cfg(
        paged_kv=True, page_size=page)
    params_sw = model_sw.init(jax.random.key(0))
    reqs_sw = synthetic_trace(n_req, slots, prompt_len, gen,
                              model_sw.cfg.vocab, flavor="soak")
    worst = max(num_pages(r.prompt_len + r.max_new, page) for r in reqs_sw)
    n_pages = max(worst + 2, (slots * worst) // 2 + 1)

    # exhaustion episode in BOTH twins (identical trajectories; corruption
    # alone differs) so swap preemption reliably engages at full size
    pressure = dict(exhaust_at=(gen // 2,), exhaust_for=4)

    def sdc_run(fault_plan):
        e = ContinuousEngine(model_sw, params_sw, slots=slots,
                             max_len=max_len, chunk=16, n_pages=n_pages,
                             preempt="swap", fault_plan=fault_plan)
        return e.run(reqs_sw)

    fin_clean, _ = sdc_run(ServeFaultPlan(**pressure))
    fin_sdc, st = sdc_run(ServeFaultPlan(corrupt_swap_at=tuple(range(4)),
                                         **pressure))
    out.update({
        "sdc_soak_injected": st["sdc_injected"],
        "sdc_soak_detected": st["sdc_detected"],
        "sdc_soak_reingest": st["sdc_reingest"],
        "sdc_soak_token_parity": (
            len(fin_sdc) == len(fin_clean) == n_req
            and all(a.tokens == b.tokens
                    for a, b in zip(fin_sdc, fin_clean))),
    })
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="*", default=list(ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=48)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="one arch, short generation (CI smoke)")
    ap.add_argument("--shard-probe", default=None, metavar="ARCH",
                    help="internal re-entry: run the tensor-parallel A/B "
                         "in THIS process (expects forced host devices) "
                         "and print SHARD_JSON instead of benchmarking")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.shard_probe:
        out = shard_probe(args.shard_probe, prompt_len=args.prompt_len)
        print("SHARD_JSON " + json.dumps(out))
        return out
    if args.quick:
        args.archs, args.gen, args.repeats = args.archs[:1], 16, 1

    import jax
    report = {"meta": {"backend": jax.default_backend(),
                       "device": str(jax.devices()[0]),
                       "quick": bool(args.quick)},
              "archs": {}}
    for arch in args.archs:
        print(f"[serve_decode] {arch} ...", flush=True)
        row = bench_arch(arch, batch=args.batch, prompt_len=args.prompt_len,
                         gen=args.gen, repeats=args.repeats,
                         quick=args.quick)
        report["archs"][arch] = row
        fmt = lambda x, unit: "n/a" if x is None else f"{x:.1f} {unit}"
        print(f"  prefill dense {row['prefill_dense_ms']:.1f} ms "
              f"/ pallas {row['prefill_pallas_ms']:.1f} ms "
              f"/ ragged {fmt(row['ragged_prefill_ms'], 'ms')} | "
              f"python {row['python_tok_s']:.1f} tok/s | "
              f"scan {row['scan_tok_s']:.1f} tok/s "
              f"({row['scan_speedup']:.2f}x) | "
              f"ragged {fmt(row['ragged_decode_tok_s'], 'tok/s')} | "
              f"paged {fmt(row['paged_decode_tok_s'], 'tok/s')} "
              f"(page={row['paged_page_size']}) | "
              f"scan+pallas(kv8) {row['scan_pallas_kv8_tok_s']:.1f} tok/s",
              flush=True)
        if row.get("continuous_decode_tok_s") is not None:
            print(f"  continuous {row['continuous_decode_tok_s']:.1f} tok/s "
                  f"vs fixed {row['fixed_batch_tok_s']:.1f} tok/s "
                  f"({row['continuous_speedup']:.2f}x) | occupancy "
                  f"{row['continuous_batch_occupancy']:.2f} | peak pages "
                  f"{row['peak_live_pages']}/"
                  f"{row['continuous_fixed_equiv_pages']}", flush=True)
        else:
            print(f"  continuous n/a "
                  f"({row.get('continuous_unsupported')})", flush=True)
        if row.get("spec_decode_tok_s") is not None:
            print(f"  speculative {row['spec_decode_tok_s']:.1f} tok/s "
                  f"vs plain {row['spec_plain_tok_s']:.1f} tok/s "
                  f"({row['spec_speedup']:.2f}x) | accept "
                  f"{row['spec_accept_rate']:.2f} (k={row['spec_k']}, "
                  f"draft_repeats={row['spec_draft_repeats']}) | "
                  f"parity={row['spec_token_parity']}", flush=True)
        else:
            print(f"  speculative n/a "
                  f"({row.get('spec_unsupported')})", flush=True)
        if row.get("soak_drained") is not None:
            print(f"  soak drained={row['soak_drained']} "
                  f"({row['soak_requests']} reqs, "
                  f"{row['soak_tok_s']:.1f} tok/s) | "
                  f"{row['soak_preemptions']} preempts, "
                  f"{row['soak_shed_events']} sheds, "
                  f"{row['soak_degraded']} degraded, miss-rate "
                  f"{row['soak_deadline_miss_rate']:.2f}, "
                  f"{row['soak_poisoned_rounds']} poisoned, "
                  f"{row['soak_faults_exhaust']} exhaustions", flush=True)
        else:
            print(f"  soak n/a ({row.get('soak_unsupported')})", flush=True)
        if row.get("ha_drained") is not None:
            print(f"  ha drained={row['ha_drained']} "
                  f"({row['ha_requests']} session reqs, "
                  f"{row['ha_tok_s']:.1f} tok/s) | "
                  f"{row['ha_kills']} kills, "
                  f"{row['ha_migrations']} migrations, "
                  f"parity={row['ha_token_parity']}, "
                  f"replay_parity={row['ha_replay_parity']} "
                  f"({row['ha_journal_records']} journal records)",
                  flush=True)
        else:
            print(f"  ha n/a ({row.get('ha_unsupported')})", flush=True)
        print(f"  flag telemetry {row['flag_telemetry_overhead']:.2f}x "
              f"({row['flag_decode_ms']:.1f} -> "
              f"{row['flag_decode_flags_ms']:.1f} ms)", flush=True)
        if row.get("shard_devices") is not None:
            print(f"  shard tp={row['shard_devices']}: "
                  f"{row['shard_decode_tok_s']:.1f} tok/s vs base "
                  f"{row['shard_base_tok_s']:.1f} tok/s "
                  f"({row['shard_speedup']:.2f}x), "
                  f"parity={row['shard_token_parity']} | dryrun "
                  f"{row.get('shard_dryrun_devices')} devices, peak "
                  f"{[f'{b/2**30:.1f}G' for b in row.get('shard_dryrun_peak_bytes', [])]}",
                  flush=True)
        elif not args.quick:
            print(f"  shard n/a ({row.get('shard_unsupported')})",
                  flush=True)
        if row.get("esc_soak_drained") is not None:
            print(f"  health esc drained={row['esc_soak_drained']} "
                  f"({row['esc_soak_escalations']} escalations, "
                  f"{row['esc_soak_escalated_requests']} reqs wider, "
                  f"{row['esc_soak_poisoned_rounds']} poisoned) | "
                  f"sdc {row['sdc_soak_injected']} injected / "
                  f"{row['sdc_soak_detected']} detected / "
                  f"{row['sdc_soak_reingest']} reingested, "
                  f"parity={row['sdc_soak_token_parity']}", flush=True)
        else:
            print(f"  health soak n/a "
                  f"({row.get('health_soak_unsupported')})", flush=True)

    if not args.quick:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"[serve_decode] wrote {args.out}")
    return report


if __name__ == "__main__":
    main()
