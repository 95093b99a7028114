"""One benchmark run of one cell, from set-up to the result line.

A run:

1. reads the cell (``workloads/<cell>.json``), its configuration
   (``configs/<config>.json``) and its traffic mix (``traffic/<mix>.json``);
2. builds the program's model from the configuration and hands it weights
   the benchmark draws on the device from ``--seed`` (``weights.py``);
3. warms up: compiles, or loads from the persistent cache, the burst
   program and every (chunk offset, wave width) prefill program the window
   can reach, in parallel; serves one wave of equal, longest-possible
   prompts for every wave width ``1..slots``, which runs each of them; and
   reads every burst-output width the host will read.  Everything so far
   is ``setup_s``;
4. opens the window: an open loop adopts each request into the engine at
   its due time; the engine's ``step()`` does the serving, and an
   in-memory recorder passed as its ``journal`` timestamps every
   admission, token delivery and finish;
5. closes the window after ``--seconds``, stops arrivals and drains the
   requests in flight up to the cell's cap (a request unfinished there has
   failed);
6. reads the device's peak memory, frees the engine, and compares a sample
   of what the window served with the plain reference (``check/``).

Compilations inside the window are counted; the comparison requires none.
With ``--trace 1`` the profiler records ``TRACE_S`` seconds or more from
the first step boundary past ``TRACE_FROM`` of the window, up to a step
boundary with no prompt half prefilled (``Tracer``); the benchmark's own
host spans (``bench.*``) mark what the host was doing, and the per-layer
metrics are read from that interval of the trace and the event log
(``layers.py``).  With ``control`` (``run.py --control 1``, never in a
benchmark run) the numbers compared are the control's (``check/``): the
float8 reference scored in the program's place, which has to come out
not correct.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
#: requests compared with the reference cover at least this many served
#: tokens unless the cell says otherwise
SAMPLE_TOKENS = 400
#: trace files, deleted once read
TRACE_DIR = ROOT / ".bench_trace"
#: a traced run profiles at least this many seconds of its window, from
#: this share of the window on (by then the queue has built up)
TRACE_S = 12.0
TRACE_FROM = 0.5
#: compilations seen while the window is open (JAX monitoring events)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def use_compile_cache() -> None:
    """Name JAX's persistent compile cache before JAX is imported: a fixed
    directory inside the checkout, whatever the machine names (the
    program's own helper then keeps it), with no size limit, so that no
    entry is evicted.  Every program is kept, the quick ones too, so a
    second run in a checkout compiles nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def metric_entries(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


_METRICS: Dict[str, object] = {}


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    if name not in _METRICS:
        path = HERE / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _METRICS[name] = mod.read
    return _METRICS[name]


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict                  # workloads/<name>.json
    config: dict                # configs/<config>.json
    mix: dict                   # traffic/<mix>.json


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    spec = load_json(HERE / "workloads" / f"{name}.json")
    if (spec["config"], spec["traffic"]) != (entry["config"],
                                             entry["traffic"]):
        raise SystemExit(f"bench: workloads/{name}.json disagrees with "
                         f"BENCHMARK.json on its config or traffic")
    return Cell(name, spec, load_json(HERE / "configs"
                                      / f"{spec['config']}.json"),
                load_json(HERE / "traffic" / f"{spec['traffic']}.json"))


# -- the program ------------------------------------------------------------
def build_model(config: dict):
    """The program's model for a configuration file, checked against the
    file's published widths."""
    from repro.models.registry import build_model as build
    prog = config["program"]
    model = build(prog["registry"], policy=prog["policy"],
                  reduced=bool(prog.get("reduced", False)))
    model = model.with_cfg(**prog["overrides"])
    c = model.cfg
    want = {
        "n_layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or config["hidden_size"]
        // config["num_attention_heads"],
        "vocab": config["vocab_size"],
        "rope_theta": config["rope_theta"],
        "norm_eps": config["rms_norm_eps"],
        "tie_embeddings": config["tie_word_embeddings"],
    }
    got = {k: getattr(c, k) for k in want}
    if config.get("num_experts"):
        want.update(n_experts=config["num_experts"],
                    top_k=config["num_experts_per_tok"],
                    d_expert=config["moe_intermediate_size"],
                    norm_topk=config["norm_topk_prob"])
        got.update(n_experts=c.moe.n_experts, top_k=c.moe.top_k,
                   d_expert=c.moe.d_expert, norm_topk=c.moe.router_norm_topk)
    else:
        want["d_ff"] = config["intermediate_size"]
        got["d_ff"] = c.d_ff
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise SystemExit(f"bench: program config departs from the file "
                         f"(program, file): {bad}")
    return model


class Recorder:
    """Stands in for the engine's request journal: timestamps every record
    the engine appends (``admit``, ``tokens``, ``finish``, ...) in memory,
    tagged with the number of the ``step()`` call it came from.
    ``records`` stays empty, so the engine never treats a run as a
    restart."""
    records = ()

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.log: List[tuple] = []
        self.step = 0
        self._seen = 0
        #: admitted requests whose first token has not come yet
        self.prefilling = set()

    def append(self, kind: str, **kw) -> None:
        rid = kw.get("rid")
        if kind == "admit":
            self.prefilling.add(rid)
        elif kind == "tokens":
            self.prefilling.discard(rid)
        self.log.append((time.perf_counter(), kind, rid,
                         len(kw.get("toks", ())), self.step))

    def new_finishes(self) -> List[tuple]:
        """(rid, time) of finish records since the last call."""
        out = [(r, t) for t, k, r, _, _ in self.log[self._seen:]
               if k == "finish"]
        self._seen = len(self.log)
        return out


class Tracer:
    """Profiles the window from the first step boundary at or after
    ``t_from`` for at least ``TRACE_S`` seconds, up to the first step
    boundary after that at which no prompt is half prefilled.  The
    benchmark's host spans mark what the host was doing; the interval is
    the host span ``bench.window``."""

    def __init__(self):
        self.active = False
        self.t_from = self.t0 = self.t1 = self.stop_s = None

    def start(self) -> None:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only, no Python calls
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.active, self.t0 = True, time.perf_counter()

    def poll(self, rec: Recorder, force: bool = False) -> None:
        now = time.perf_counter()
        if self.t0 is None and self.t_from is not None and now >= self.t_from:
            self.start()
        elif self.active and (force or (now >= self.t0 + TRACE_S
                                        and not rec.prefilling)):
            import jax
            self.t1 = now
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False
            self.stop_s = time.perf_counter() - now


def step(eng, rec: Recorder, tracer: Optional[Tracer]) -> None:
    from jax.profiler import TraceAnnotation
    rec.step += 1
    with TraceAnnotation("bench.step"):
        eng.step()
    if tracer is not None:
        tracer.poll(rec)


@dataclasses.dataclass
class Req:
    rid: int
    due_s: float                # host clock (perf_counter) seconds
    prompt_len: int
    max_new: int
    adopt_s: Optional[float] = None     # handed to the engine (open loop)
    admit_s: Optional[float] = None     # admitted into a slot
    first_s: Optional[float] = None
    last_s: Optional[float] = None
    n_out: int = 0
    finished: bool = False
    #: (time, tokens, step, first) per delivery; ``first`` marks the
    #: first token, which the prefill-chunk program samples
    deliveries: List[tuple] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    cell: Cell
    seed: int
    seconds: float
    requests: List[Req]
    t_open: float
    t_close: float
    stats: dict
    setup_s: float
    shape: object               # work.Shape
    peak: dict                  # peaks row
    engine: dict                # the cell's engine settings
    trace: Optional[object] = None      # trace.TraceView
    span: Optional[tuple] = None        # host-clock (open, close) traced


class CompileCounter:
    """Counts JAX lowering and compilation events while open (a ``with``
    block around the window)."""

    def __enter__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.n += 1


# -- the run ----------------------------------------------------------------
def make_engine(model, params, cell: Cell, rec: Recorder):
    from repro.launch.engine import ContinuousEngine
    from bench.generator import bounds
    e = cell.spec["engine"]
    p_max, n_max = bounds(cell.mix)
    return ContinuousEngine(model, params, slots=e["slots"],
                            max_len=p_max + n_max, chunk=e["chunk"],
                            burst_cap=e.get("burst_cap", 64), journal=rec)


def precompile(eng, p_max: int, workers: int = 8) -> None:
    """Lower and compile (or load from the persistent cache) the engine's
    burst program and every (chunk offset, wave width) prefill program in
    a thread pool.  This fills the same in-memory caches the engine's own
    calls then hit, so the serving warm-up that follows compiles nothing,
    and a cold checkout compiles in parallel."""
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor
    s, c = eng.slots, eng.chunk
    table = jnp.zeros((s, eng.max_pages), jnp.int32)
    jobs = [lambda: eng._burst.lower(
        eng.params, eng.caches, table, jnp.zeros((10, s), jnp.int32), None,
        eng._key).compile()]
    for m in range(1, s + 1):
        for off in range(0, p_max, c):
            jobs.append(lambda off=off, m=m: eng._chunk_fn(off, m).lower(
                eng.params, eng.caches, table, jnp.zeros((m, c), jnp.int32),
                jnp.zeros((3, m), jnp.int32), None, eng._key).compile())
    # the first burst and chunk programs trace in this thread, so every
    # module the program imports lazily is imported once, before threads
    # could race on a half-initialised import
    for j in jobs[:2]:
        j()
    with ThreadPoolExecutor(workers) as ex:
        for f in [ex.submit(j) for j in jobs[2:]]:
            f.result()


def warm_up(eng, cell: Cell, vocab: int, seed: int, stamp=None) -> None:
    """Every program the window can call, compiled in parallel
    (``precompile``); then one wave of ``m`` equal prompts of the longest
    length the mix can send, for every ``m`` in ``1..slots``, so every
    (chunk offset, wave width) prefill program and the burst program run
    once; then every width of burst output the host reads back
    (``out[:, :n]``, ``n <= burst_cap``)."""
    import jax.numpy as jnp
    from repro.launch.engine import Request
    from bench.generator import bounds
    p_max, _ = bounds(cell.mix)
    stamp = stamp or (lambda what: None)
    eng.start([])
    precompile(eng, p_max)
    stamp("precompiled")
    rng = np.random.default_rng([int(seed) % (1 << 64), 9])
    for m in range(1, eng.slots + 1):
        eng.run([Request(rid=-(100 * m + j), max_new=2,
                         tokens=rng.integers(0, vocab, p_max).tolist())
                 for j in range(m)])
    stamp("warm-up served")
    # an uncommitted array, as the burst's output is (a committed one would
    # miss the slice programs' cache)
    out = jnp.asarray(np.zeros((eng.slots, eng.burst_cap), np.int32))
    for n in range(eng.burst_cap + 1):
        np.asarray(out[:, :n])


def _entry(req_obj):
    from repro.launch.engine import _QEntry
    return _QEntry(req=req_obj, not_before=0)


def drive_open(eng, rec: Recorder, plan, tokens, seconds: float,
               tracer: Optional[Tracer] = None) -> tuple:
    """Adopt each planned request at its due time; step the engine while it
    has work, sleep to the next due time while it has none."""
    from jax.profiler import TraceAnnotation
    from repro.launch.engine import Request
    t_open = time.perf_counter()
    t_close = t_open + seconds
    if tracer is not None:
        tracer.t_from = t_open + TRACE_FROM * seconds
    reqs = [Req(p.rid, t_open + p.due_s, p.prompt_len, p.max_new)
            for p in plan]
    i, n = 0, len(plan)
    while True:
        now = time.perf_counter()
        batch = []
        while i < n and reqs[i].due_s <= now:
            p = plan[i]
            reqs[i].adopt_s = now
            batch.append(_entry(Request(rid=p.rid, tokens=tokens[p.rid],
                                        max_new=p.max_new)))
            i += 1
        if batch:
            with TraceAnnotation("bench.adopt"):
                eng.adopt(batch)
        if now >= t_close:
            break
        if eng.has_work():
            step(eng, rec, tracer)
        else:
            nxt = reqs[i].due_s if i < n else t_close
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, t_close) - now))
            if tracer is not None:
                tracer.poll(rec)
    return reqs, t_open, t_close


def drain(eng, rec: Recorder, cap_s: float,
          tracer: Optional[Tracer] = None) -> None:
    from jax.profiler import TraceAnnotation
    t_cap = time.perf_counter() + cap_s
    with TraceAnnotation("bench.drain"):
        while eng.has_work() and time.perf_counter() < t_cap:
            step(eng, rec, tracer)


def attach_log(reqs: List[Req], rec: Recorder) -> None:
    by = {r.rid: r for r in reqs}
    for t, kind, rid, n, stp in rec.log:
        r = by.get(rid)
        if r is None:
            continue
        if kind == "admit" and r.admit_s is None:
            r.admit_s = t
        elif kind == "tokens" and n:
            r.deliveries.append((t, n, stp, r.first_s is None))
            r.n_out += n
            r.first_s = r.first_s if r.first_s is not None else t
            r.last_s = t
        elif kind == "finish":
            r.finished = True


def judge(cell: Cell, cmp: dict, reqs: List[Req], compiles: int,
          control: bool = False) -> tuple:
    """Each number compared beside its limit, and whether all hold: the
    gap statistics the cell names (the control's with ``control``; a
    request the reference cannot score reads as a huge gap), no request
    unfinished, none short of its budget, nothing compiled in the
    window."""
    from bench.stats import finite
    pre = "control_" if control else ""
    check = {k: {"value": finite(cmp[pre + k], 1e9), "limit": lim}
             for k, lim in cell.spec["check"].items()}
    check.update({
        "unfinished": {"value": sum(1 for q in reqs if not q.finished),
                       "limit": 0},
        "short_answers": {"value": sum(1 for q in reqs if q.finished
                                       and q.n_out != q.max_new),
                          "limit": 0},
        "window_compiles": {"value": compiles, "limit": 0},
    })
    return check, all(c["value"] <= c["limit"] for c in check.values())


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, control: bool = False) -> dict:
    """Everything after the platform check; returns the result dict."""
    import jax
    from bench import generator, weights, work
    from bench.check import compare
    from bench.peaks import peak as peak_row
    from bench.stats import pct, queue_waits_ms, tpots_ms, ttfts_ms

    devices = jax.devices()
    dev = devices[0]
    pk = peak_row(dev.device_kind) if dev.platform == "tpu" else None
    stamps = [("start", time.perf_counter() - t_process)]

    def stamp(what):
        stamps.append((what, time.perf_counter() - t_process))

    model = build_model(cell.config)
    params = jax.block_until_ready(weights.make(model, seed))
    stamp("weights")
    vocab = cell.config["vocab_size"]
    plan = generator.plan(cell.mix, cell.spec, seconds)
    tokens = generator.prompt_tokens(seed, plan, vocab)
    rec = Recorder()
    eng = make_engine(model, params, cell, rec)
    stamp("engine")
    warm_up(eng, cell, vocab, seed, stamp)
    eng.start([])
    rec.reset()
    tracer = Tracer() if trace else None
    setup_s = time.perf_counter() - t_process
    with CompileCounter() as counter:
        reqs, t_open, t_close = drive_open(eng, rec, plan, tokens, seconds,
                                           tracer)
        drain(eng, rec, cell.spec["drain_cap_s"], tracer)
        if tracer is not None:
            tracer.poll(rec, force=True)
    stamp("drained")
    tv = span = None
    if trace:
        from bench import trace as tr
        tv = tr.view(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        span = (tracer.t0, tracer.t1)
        stamp("trace read")
    results, stats = eng.finalize()
    attach_log(reqs, rec)
    ms = dev.memory_stats() or {}
    mem_peak = int(ms.get("peak_bytes_in_use", 0))
    served = {r.rid: (tokens[r.rid], list(results[r.rid].tokens))
              for r in reqs if r.finished}
    del eng, results
    gc.collect()

    r = Run(cell, seed, seconds, reqs, t_open, t_close, stats, setup_s,
            work.Shape.from_config(cell.config), pk, cell.spec["engine"],
            tv, span)
    bench = benchmark()
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metric_entries(bench, cell.name, kind):
        v = metric_reader(m["name"])(r)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- the comparison ----------------------------------------------------
    rids = compare.sample(served, seed, cell.spec.get("sample_tokens",
                                                     SAMPLE_TOKENS))
    t0 = time.perf_counter()
    cmp = compare.compare(params, cell.config, served, rids, control=control)
    ref_s = time.perf_counter() - t0
    check, correct = judge(cell, cmp, reqs, counter.n, control)
    failed = check["unfinished"]["value"]
    late = [(q.adopt_s - q.due_s) * 1e3 for q in reqs
            if q.adopt_s is not None]
    # a backlog that grows through the window (load above the knee) shows
    # as a longer queue wait in its second half
    half = t_open + seconds / 2
    waits = list(zip(queue_waits_ms(r), reqs))
    halves = [pct([w for w, q in waits if (q.due_s >= half) == h], 50)
              for h in (False, True)]
    info = {
        "requests": len(reqs), "tokens_served": sum(q.n_out for q in reqs),
        "compared_requests": len(rids), "reference_s": ref_s,
        "gaps": {k: v for k, v in cmp.items() if k != "per_request"},
        "control": control,
        "seconds_since_start": stamps,
        "late_adopt_p90_ms": pct(late, 90) if late else None,
        "queue_wait_p50_ms_by_half": halves,
        "ttft_ms_p50_p75_p90": [pct(ttfts_ms(r), p) for p in (50, 75, 90)],
        "tpot_ms_p50_p75_p90": [pct(tpots_ms(r), p) for p in (50, 75, 90)],
        "traced_s": (None if tracer is None else
                     [tracer.t0 - t_open, tracer.t1 - t_open,
                      tracer.stop_s]),
        "stats": {
            k: stats[k] for k in ("decode_rounds", "bursts", "occupancy",
                                  "peak_live_pages", "preemptions",
                                  "shed_events")},
    }
    print(f"bench: {json.dumps(info)}", file=sys.stderr, flush=True)
    out = {"correct": bool(correct), "attempted": len(reqs),
           "failed": failed, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": mem_peak}}
    if tv is not None:
        out["device"]["busy_s"] = tv.busy_s
        out["device"]["window_s"] = tv.window_s
        out["breakdown"] = {"device_ops": tv.top_ops(10),
                            "idle_gaps": tv.idle_gaps(10)}
    for k, v in check.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    out["check"] = check
    return out
