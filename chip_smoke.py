#!/usr/bin/env python3
"""Chip smoke: serve qwen3-moe-30b-a3b at its published widths on a TPU.

    python chip_smoke.py [--seed S]     # one chip
    python chip_smoke.py --chips 4      # the --mesh paths, on four chips

One chip (the default): the objects ``python -m repro.launch.serve
--continuous`` builds — ``build_model`` and ``ContinuousEngine`` — serve
``N_REQUESTS`` requests on ``SLOTS`` slots: paged KV with ``PAGE``-token
pages, ``CHUNK``-token chunked prefill, and the Pallas decode and prefill
kernels resolved from the platform.  Every width is the published one
(``configs/qwen3_moe_30b_a3b.py``); only the depth is cut, to ``DEPTH`` of
48 layers, so the weights (random, from ``--seed``) fit one v5e chip.
Checks, each fatal:

  * every request finishes its whole budget, no round is poisoned and
    every first-token logits row is finite;
  * the compiled burst and prefill-chunk programs contain the Pallas
    kernels (``tpu_custom_call``);
  * each request's last-prompt-token logits agree with plain
    ``Model.generate`` on the same chip (dense attention, contiguous
    cache) within a bf16 tolerance measured in the same run: ``TOL_NOISE``
    times the distance bf16 rounding alone puts between that reference and
    the same model in float32, which itself may not exceed ``NOISE_MAX``;
  * decode: each request's prompt and emitted tokens, fed through that
    reference, show every emitted token within the same tolerance of the
    reference's best logit at its step (a teacher-forced check of every
    burst step the Pallas decode kernel ran).
    How many free-running greedy tokens agree before the first divergence
    is reported, not checked: random weights have near-ties.

``--chips 4`` runs only the mesh paths and what they are compared with:
the same model and requests at ``--mesh 1,4`` (TP=4 attention heads, EP=4
experts) and at ``--mesh 4,1`` (four one-chip replicas, each device's
memory printed so the replicas visibly sit on four chips), then a
one-device run of the same engine in this process.  Both mesh paths'
logits are compared with the one-device run's, and all three pass the
decode check.

Earlier lines report the depth, parameter bytes, compile seconds, peak
device memory and a smoke tok/s (a smoke number, not a benchmark).  The
last line of standard output is one JSON object naming the device.  On any
other platform than TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-moe-30b-a3b"
#: layers kept of 48: the largest depth whose serving programs and
#: weights fit one v5e chip (16 GiB) with room to spare — compiled for a
#: described v5e, depth 10 peaks at 15.3 GB (parameter init: 13.7 GB of
#: weights + 1.6 GB scratch), depth 11 would need 16.6 GB
DEPTH = 10
PAGE = 128
CHUNK = 128
SLOTS = 4
N_REQUESTS = 8
PROMPT_LENS = (128, 1024)       # inclusive range, drawn from --seed
NEW_TOKENS = (32, 64)
#: logits tolerance, in units of NOISE: the largest |logit| difference,
#: over the real vocabulary of every request, between plain bf16
#: ``Model.generate`` and the same model in float32 at HIGHEST matmul
#: precision.  Through DEPTH layers of a bf16 residual stream and drop-free
#: top-8 routing that one rounding can flip, NOISE is the scale bf16 itself
#: sets.  A serving path as accurate as that reference lies within NOISE of
#: float32 too, hence within 2 NOISE of the reference; the third NOISE is
#: slack for paths that round in different places.  Whether a path computing
#: in a narrower format than bf16 (an fp8 cache) fails it has not been tried.
#: The decode check bounds each step's margin (reference best minus the
#: emitted token's reference logit) by the same tolerance: a correct path's
#: margin is its error at two entries, a random token's is several logits.
TOL_NOISE = 3.0
#: ceiling on NOISE itself, so a fault shared by the engine and the
#: reference cannot widen the tolerance without bound: about twice the
#: NOISE of the default seed on a TPU v5e (0.4633)
NOISE_MAX = 1.0


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def build_model(depth: int = DEPTH, reduced: bool = False):
    from repro.models.registry import build_model as build
    model = build(ARCH, policy="tp_bf16", reduced=reduced)
    return model.with_cfg(n_layers=depth, paged_kv=True, page_size=PAGE,
                          decode_backend="auto", prefill_backend="auto")


def make_requests(vocab: int, seed: int, n: int = N_REQUESTS,
                  prompt_lens=PROMPT_LENS, new_tokens=NEW_TOKENS):
    from repro.launch.engine import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        new = int(rng.integers(new_tokens[0], new_tokens[1] + 1))
        reqs.append(Request(rid=i, max_new=new, tokens=rng.integers(
            0, vocab, size=plen).tolist()))
    return reqs


def param_bytes(params) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def bytes_in_use(device) -> int:
    return (device.memory_stats() or {}).get("bytes_in_use", 0)


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        ms = d.memory_stats() or {}
        parts.append(f"{d.id}: in use {ms.get('bytes_in_use', 0):,} B, "
                     f"peak {ms.get('peak_bytes_in_use', 0):,} B")
    return "; ".join(parts)


def programs_have_kernels(eng) -> dict:
    """Compile the engine's own burst and first prefill-chunk programs
    (the serving variants, without the ``prompt_logits`` capture) and
    report whether each contains a Pallas kernel."""
    import jax.numpy as jnp
    table = eng._table_device()
    state = np.zeros((10, eng.slots), np.int32)
    burst = eng._burst.lower(eng.params, eng.caches, table, state, None,
                             eng._key).compile().as_text()
    off, m = min(k[:2] for k in eng._chunk_fns)
    chunk = eng._chunk_fn(off, m).lower(
        eng.params, eng.caches, table, jnp.zeros((m, eng.chunk), jnp.int32),
        jnp.zeros((3, m), jnp.int32), None, eng._key).compile().as_text()
    return {"burst": "tpu_custom_call" in burst,
            "prefill_chunk": "tpu_custom_call" in chunk}


def serve(model, params, reqs, *, slots: int, chunk: int, mesh=None,
          runs: int = 2, check_kernels: bool = False) -> dict:
    """Serve ``reqs`` ``runs`` times through one engine (the first run
    compiles) and return host-side results only, so the engine and its
    device buffers die with this call."""
    from repro.launch.engine import ContinuousEngine
    max_len = max(r.prompt_len + r.max_new for r in reqs)
    eng = ContinuousEngine(model, params, mesh=mesh, slots=slots,
                           max_len=max_len, chunk=chunk)
    seconds = []
    for _ in range(runs):
        eng.prompt_logits = {}
        t0 = time.perf_counter()
        fin, stats = eng.run(reqs)
        seconds.append(time.perf_counter() - t0)
    out = {"tokens": {f.rid: list(f.tokens) for f in fin},
           "logits": eng.prompt_logits, "stats": stats, "seconds": seconds}
    if check_kernels:
        out["kernels"] = programs_have_kernels(eng)
    return out


def check_served(reqs, served, what: str) -> None:
    toks, stats = served["tokens"], served["stats"]
    short = [r.rid for r in reqs if len(toks[r.rid]) != r.max_new]
    check(not short, f"{what}: requests {short} did not finish their budget")
    check(stats["poisoned_rounds"] == 0
          and stats.get("nonfinite_prefill", 0) == 0,
          f"{what}: poisoned rounds")
    check(all(np.isfinite(served["logits"][r.rid]).all() for r in reqs),
          f"{what}: non-finite first-token logits")
    print(f"{what}: all {len(reqs)} requests finished their budgets "
          f"({sum(r.max_new for r in reqs)} tokens), 0 poisoned rounds, "
          f"finite logits", flush=True)


def pad_width(reqs, chunk: int) -> int:
    """Width every reference prompt is right-padded to (one program)."""
    return -(-max(r.prompt_len for r in reqs) // chunk) * chunk


def plain(model):
    """The reference model: dense attention over a contiguous cache."""
    return model.with_cfg(paged_kv=False, decode_backend="dense",
                          prefill_backend="dense")


def reference(model, params, reqs, *, pad_to: int) -> dict:
    """Plain ``Model.generate`` per request — dense attention, contiguous
    cache — with every prompt right-padded to ``pad_to`` (one compiled
    program): greedy tokens and the last-prompt-token logits."""
    import jax
    ref_model = plain(model)
    gen_len = max(r.max_new for r in reqs)
    fn = jax.jit(lambda p, t, pl: ref_model.generate(
        p, t, gen_len=gen_len, max_len=pad_to + gen_len, prompt_lens=pl,
        return_logits=True, guard_nonfinite=True))
    out = {"tokens": {}, "logits": {}, "seconds": []}
    for r in reqs:
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :r.prompt_len] = r.tokens
        t0 = time.perf_counter()
        gen, lgs, bad = jax.block_until_ready(
            fn(params, toks, np.asarray([r.prompt_len], np.int32)))
        out["seconds"].append(time.perf_counter() - t0)
        check(int(bad[0]) == 0, f"reference: non-finite logits, request "
                                f"{r.rid}")
        out["tokens"][r.rid] = np.asarray(gen[0, :r.max_new]).tolist()
        out["logits"][r.rid] = np.asarray(lgs[0, 0])
    return out


def f32_reference(model, params, reqs, *, chunk: int) -> dict:
    """Last-prompt-token logits of the same model in float32: every matmul
    at HIGHEST precision on exactly widened bf16 weights, dense attention.
    Prompts go through in ``chunk``-token pieces over a paged float32
    cache, so the drop-free expert buffers stay chunk-sized on one chip."""
    import dataclasses
    import functools

    import jax
    from repro.core.policy import get_policy
    from repro.models.transformer import init_caches
    m32 = dataclasses.replace(model, policy=get_policy("fp32")).with_cfg(
        decode_backend="dense", prefill_backend="dense")
    width = -(-max(r.prompt_len for r in reqs) // chunk) * chunk
    fns, logits = {}, {}
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            caches = init_caches(m32.cfg, 1, width, m32.policy)
            for off in range(0, r.prompt_len, chunk):
                if off not in fns:
                    fns[off] = jax.jit(functools.partial(
                        lambda o, p, t, c, n: m32.prefill_chunk(
                            p, t, c, q_offset=o, chunk_lens=n), off))
                piece = r.tokens[off:off + chunk]
                t = np.zeros((1, chunk), np.int32)
                t[0, :len(piece)] = piece
                lg, caches = fns[off](params, t, caches,
                                      np.asarray([len(piece)], np.int32))
            logits[r.rid] = np.asarray(lg[0, -1])
    return logits


def logit_tolerance(reqs, ref, f32, vocab: int) -> float:
    """``TOL_NOISE`` x NOISE, NOISE measured between ``ref`` (bf16
    ``Model.generate``) and ``f32`` (``f32_reference``)."""
    noise = max(float(np.max(np.abs(ref["logits"][r.rid][:vocab]
                                    - f32[r.rid][:vocab]))) for r in reqs)
    tol = TOL_NOISE * noise
    print(f"bf16 noise: max |generate(bf16) - float32| = {noise:.4g} over "
          f"{len(reqs)} requests (ceiling {NOISE_MAX:g}); logits tolerance "
          f"{TOL_NOISE:g} x noise = {tol:.4g}", flush=True)
    check(np.isfinite(tol) and tol > 0, "no measurable bf16 noise")
    check(noise <= NOISE_MAX, f"bf16 noise {noise:.4g} over its ceiling "
                              f"{NOISE_MAX:g}")
    return tol


def forced_margins(model, params, reqs, tokens, *, pad_to: int) -> dict:
    """Each request's prompt, then ``tokens[rid]`` (what a serving path
    emitted), fed through the plain reference as ``Model.generate`` runs
    it (``prefill``, then one ``decode_step`` per token).  Per emitted
    token: how far its reference logit lies below the reference's best at
    that step (``margin``, 0 where it is the reference's argmax) and how
    far the mean logit does (``chance``: what a random token scores)."""
    import jax
    import jax.numpy as jnp
    ref_model, vocab = plain(model), model.cfg.vocab
    gen_len = max(r.max_new for r in reqs)

    def run(p, toks, plens, forced):
        lg0, caches = ref_model.prefill(p, toks, max_len=pad_to + gen_len,
                                        prompt_lens=plens)

        def step(carry, t):
            c, pos = carry
            lg, c = ref_model.decode_step(p, t[:, None], c, pos)
            return (c, pos + 1), lg[:, 0]

        _, lgs = jax.lax.scan(step, (caches, plens), forced[:, :-1].T)
        lgs = jnp.concatenate([lg0[:, -1][None], lgs])[..., :vocab]
        best = lgs.max(-1)
        got = jnp.take_along_axis(lgs, forced.T[..., None], -1)[..., 0]
        return (best - got)[:, 0], (best - lgs.mean(-1))[:, 0]

    fn, out = jax.jit(run), {}
    for r in reqs:
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :r.prompt_len] = r.tokens
        forced = np.zeros((1, gen_len), np.int32)
        forced[0, :r.max_new] = tokens[r.rid]
        margin, chance = fn(params, toks, np.asarray([r.prompt_len],
                                                     np.int32), forced)
        out[r.rid] = (np.asarray(margin)[:r.max_new],
                      np.asarray(chance)[:r.max_new])
    return out


def check_decode(reqs, margins, tol: float, what: str) -> float:
    """Fatal: every emitted token within ``tol`` of the reference's best
    logit at its step (``forced_margins``).  Returns the worst margin."""
    worst = 0.0
    for r in reqs:
        m = margins[r.rid][0]
        check(np.isfinite(m).all(), f"{what}: non-finite reference logits, "
                                    f"request {r.rid}")
        step = int(np.argmax(m))
        check(m[step] <= tol, f"{what}: request {r.rid} token {step} scores "
                              f"{m[step]:.4g} below the reference's best > "
                              f"tol {tol:.4g}")
        worst = max(worst, float(m[step]))
    n = sum(r.max_new for r in reqs)
    top = sum(int((margins[r.rid][0] == 0).sum()) for r in reqs)
    chance = min(float(margins[r.rid][1].min()) for r in reqs)
    print(f"{what}: decode check, prompt + emitted tokens through the "
          f"reference: every token within {worst:.4g} of the reference's "
          f"best (tol {tol:.4g}); {top}/{n} are its argmax; a random token "
          f"would sit >= {chance:.4g} below", flush=True)
    return worst


def compare(reqs, got, want, vocab: int, what: str, tol: float,
            f32=None) -> list:
    """Fatal logits check and reported greedy agreement of ``got`` against
    ``want``; returns per-request counts of agreeing leading tokens.
    ``f32`` (last-prompt-token float32 logits) adds each request's own
    distance from float32 to the report."""
    agree, worst = [], 0.0
    for r in reqs:
        a = got["logits"][r.rid][:vocab]
        diff = float(np.max(np.abs(a - want["logits"][r.rid][:vocab])))
        worst = max(worst, diff)
        check(diff <= tol, f"{what}: request {r.rid} logits differ by "
                           f"{diff:.4g} > tol {tol:.4g}")
        same = 0
        for x, y in zip(got["tokens"][r.rid], want["tokens"][r.rid]):
            if x != y:
                break
            same += 1
        agree.append(same)
        vs32 = ("" if f32 is None else f", vs float32 "
                f"{float(np.max(np.abs(a - f32[r.rid][:vocab]))):.4g}")
        print(f"  request {r.rid}: prompt {r.prompt_len}, max |dlogit| "
              f"{diff:.4g}{vs32}; greedy tokens agree {same}/{r.max_new} "
              f"before the first divergence", flush=True)
    print(f"{what}: logits within tolerance (worst {worst:.4g}, tol "
          f"{tol:.4g}); greedy agreement {sum(agree)}/"
          f"{sum(r.max_new for r in reqs)} tokens, "
          f"{sum(a == r.max_new for a, r in zip(agree, reqs))}/{len(reqs)} "
          f"requests identical", flush=True)
    return agree


def describe(model, params) -> None:
    c, moe = model.cfg, model.cfg.moe
    print(f"model {ARCH}: published widths d_model={c.d_model} "
          f"heads={c.n_heads}/{c.n_kv_heads} kv head_dim={c.head_dim} "
          f"experts={moe.n_experts} top-{moe.top_k} d_expert={moe.d_expert} "
          f"vocab={c.vocab} untied head; depth {c.n_layers} of 48 layers "
          f"(depth cut only); {param_bytes(params):,} parameter bytes",
          flush=True)


def references(model, params, reqs, *, chunk: int):
    """Plain bf16 ``Model.generate``, the float32 yardstick, and the logits
    tolerance they set."""
    ref = reference(model, params, reqs, pad_to=pad_width(reqs, chunk))
    rs = ref["seconds"]
    print(f"reference Model.generate (dense, contiguous): first call "
          f"{rs[0]:.3f} s (compile + run), later calls "
          f"{np.mean(rs[1:]) if len(rs) > 1 else rs[0]:.3f} s", flush=True)
    t0 = time.perf_counter()
    f32 = f32_reference(model, params, reqs, chunk=chunk)
    print(f"float32 reference: {time.perf_counter() - t0:.3f} s (compile + "
          f"run)", flush=True)
    return ref, f32, logit_tolerance(reqs, ref, f32, model.cfg.vocab)


def run_one_chip(model, params, reqs, *, slots: int = SLOTS,
                 chunk: int = CHUNK, check_kernels: bool = True) -> dict:
    """The default phase: serve through the engine, then check it against
    ``Model.generate``.  Returns the numbers it printed."""
    served = serve(model, params, reqs, slots=slots, chunk=chunk,
                   check_kernels=check_kernels)
    cold, warm = served["seconds"][0], served["seconds"][-1]
    n_tok = sum(r.max_new for r in reqs)
    print(f"engine: first run {cold:.3f} s (compile + serve), warm run "
          f"{warm:.3f} s; smoke {n_tok / warm:.1f} tok/s (a smoke number, "
          f"not a benchmark); {served['stats']['bursts']} bursts, "
          f"occupancy {served['stats']['occupancy']:.2f}", flush=True)
    check_served(reqs, served, "engine")
    if check_kernels:
        k = served["kernels"]
        print(f"kernels: burst tpu_custom_call={k['burst']}, prefill chunk "
              f"tpu_custom_call={k['prefill_chunk']}", flush=True)
        check(k["burst"], "burst program has no Pallas kernel")
        check(k["prefill_chunk"], "prefill-chunk program has no Pallas kernel")
    ref, f32, tol = references(model, params, reqs, chunk=chunk)
    rs = ref["seconds"]
    agree = compare(reqs, served, ref, model.cfg.vocab,
                    "engine vs Model.generate", tol, f32=f32)
    margin = check_decode(reqs, forced_margins(
        model, params, reqs, served["tokens"], pad_to=pad_width(reqs, chunk)),
        tol, "engine")
    compile_s = (cold - warm) + (rs[0] - (np.mean(rs[1:]) if len(rs) > 1
                                          else 0.0))
    print(f"compile seconds: {compile_s:.1f} (engine first run minus warm "
          f"run, plus reference first call minus later calls)", flush=True)
    return {"served": served, "reference": ref, "agree": agree,
            "margin": margin, "compile_s": compile_s}


def run_four_chips(model, reqs, seed: int, *, slots: int = SLOTS,
                   chunk: int = CHUNK, check_kernels: bool = True) -> None:
    """The mesh paths, TP=4 and then four replicas, each with its params
    born on its own mesh and freed before the next phase; then the
    one-device run and the references all three are checked against."""
    import jax
    from repro.launch.engine import ReplicatedEngine
    from repro.launch.mesh import make_serving_mesh, replica_meshes
    key = jax.random.key(seed)
    devs = jax.devices()[:4]

    mesh = replica_meshes(make_serving_mesh(1, 4))[0]
    params = model.init(key, mesh=mesh)
    describe(model, params)
    tp = serve(model, params, reqs, slots=slots, chunk=chunk, mesh=mesh,
               runs=1, check_kernels=check_kernels)
    del params
    gc.collect()
    print(f"--mesh 1,4: {tp['seconds'][0]:.3f} s first run (compile + "
          f"serve); kernels {tp.get('kernels')}", flush=True)
    check_served(reqs, tp, "--mesh 1,4")
    if check_kernels:
        check(all(tp["kernels"].values()), "--mesh 1,4 programs lack "
                                           "kernels")
    print(f"memory after --mesh 1,4: {memory_line(devs)}", flush=True)

    mesh = make_serving_mesh(4, 1)
    params = model.init(key, mesh=replica_meshes(mesh)[0])
    max_len = max(r.prompt_len + r.max_new for r in reqs)
    fleet = ReplicatedEngine(model, params, mesh=mesh, slots=slots,
                             max_len=max_len, chunk=chunk)
    for e in fleet.engines:
        e.prompt_logits = {}
    t0 = time.perf_counter()
    fin, stats = fleet.run(reqs)
    dt = time.perf_counter() - t0
    on = sorted({d.id for e in fleet.engines
                 for d in jax.tree.leaves(e.params)[0].devices()})
    print(f"--mesh 4,1: {stats['replicas_n']} replicas, params on devices "
          f"{on}, {dt:.3f} s first run", flush=True)
    print(f"memory with 4 replicas live: {memory_line(devs)}", flush=True)
    in_use = [bytes_in_use(d) for d in devs]
    check(len(on) == 4 and min(in_use) > param_bytes(params),
          "--mesh 4,1 replicas are not on four devices")
    logits = {}
    for e in fleet.engines:
        logits.update(e.prompt_logits)
    rep = {"tokens": {f.rid: list(f.tokens) for f in fin},
           "logits": logits, "stats": stats}
    del fleet, params
    gc.collect()
    check_served(reqs, rep, "--mesh 4,1")

    params = model.init(key)
    one = serve(model, params, reqs, slots=slots, chunk=chunk, runs=1)
    check_served(reqs, one, "one device")
    _, f32, tol = references(model, params, reqs, chunk=chunk)
    for what, got in (("--mesh 1,4", tp), ("--mesh 4,1", rep)):
        compare(reqs, got, one, model.cfg.vocab, f"{what} vs one device",
                tol, f32=f32)
    for what, got in (("one device", one), ("--mesh 1,4", tp),
                      ("--mesh 4,1", rep)):
        check_decode(reqs, forced_margins(
            model, params, reqs, got["tokens"],
            pad_to=pad_width(reqs, chunk)), tol, what)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the --mesh 1,4 and 4,1 paths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} devices")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, found "
          f"{len(devices)}")
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache_dir}", flush=True)

    from repro.kernels.ops import resolve_backend
    model = build_model()
    backends = (resolve_backend(model.cfg.decode_backend),
                resolve_backend(model.cfg.prefill_backend))
    print(f"backends from platform {dev.platform}: decode={backends[0]} "
          f"prefill={backends[1]}", flush=True)
    check(backends == ("pallas", "pallas"), "backends did not resolve to "
                                            "pallas")
    reqs = make_requests(model.cfg.vocab, args.seed)
    print(f"requests: {len(reqs)} on {SLOTS} slots, prompts "
          f"{[r.prompt_len for r in reqs]}, new tokens "
          f"{[r.max_new for r in reqs]}, chunk {CHUNK}, page {PAGE}",
          flush=True)

    if args.chips == 4:
        run_four_chips(model, reqs, args.seed)
    else:
        t0 = time.perf_counter()
        params = jax.block_until_ready(model.init(jax.random.key(args.seed)))
        print(f"init: {time.perf_counter() - t0:.3f} s (compile + run)",
              flush=True)
        describe(model, params)
        run_one_chip(model, params, reqs)
        ms = dev.memory_stats() or {}
        print(f"peak_bytes_in_use: {ms.get('peak_bytes_in_use', 0):,} "
              f"(bytes_limit {ms.get('bytes_limit', 0):,})", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
