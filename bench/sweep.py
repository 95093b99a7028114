#!/usr/bin/env python3
"""Knee sweep of an open-loop cell (run once on the chip, by hand).

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,1.5,2 \
        [--seconds 51] [--seed 7]

Builds and warms the cell's engine once, then offers the cell's traffic mix
at each rate in turn through the same timed path a benchmark run drives,
and prints per rate: requests, the completed rate, output tokens delivered
per second of the window, TTFT median and p75, TPOT p75, queue wait in the
window's first and second halves, the requests due but not yet admitted
when the window closed (the backlog), and the seconds the drain took.  A
rate is sustained while the backlog at the close stays within a couple of
requests and the second half's queue wait does not outgrow the first's.
The cell file then fixes ``rate_rps`` at about 0.8 of the highest
sustained rate; the benchmark itself never searches for a rate.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from bench import harness
    harness.use_compile_cache()
    import jax
    from bench import generator, stats, weights
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: needs a TPU")
    cell = harness.load_cell(args.workload)
    if cell.mix["arrivals"]["loop"] != "open":
        raise SystemExit("sweep: only open-loop cells have a knee")
    model = harness.build_model(cell.config)
    params = weights.make(model, args.seed)
    rec = harness.Recorder()
    eng = harness.make_engine(model, params, cell, rec)
    harness.warm_up(eng, cell, cell.config["vocab_size"], args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - T_PROCESS}),
          flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        spec = dict(cell.spec, rate_rps=rate)
        plan = generator.plan(cell.mix, spec, args.seconds)
        tokens = generator.prompt_tokens(args.seed, plan,
                                         cell.config["vocab_size"])
        eng.start([])
        rec.reset()
        reqs, t_open, t_close = harness.drive_open(eng, rec, plan, tokens,
                                                   args.seconds)
        t_d = time.perf_counter()
        harness.drain(eng, rec, cell.spec["drain_cap_s"])
        drain_s = time.perf_counter() - t_d
        eng.finalize()
        harness.attach_log(reqs, rec)
        run = type("R", (), {"requests": reqs})
        half = t_open + args.seconds / 2
        waits = stats.queue_waits_ms(run)
        first = [w for w, r in zip(waits, reqs) if r.due_s < half]
        second = [w for w, r in zip(waits, reqs) if r.due_s >= half]
        done = [r for r in reqs if r.finished]
        backlog = sum(1 for r in reqs if r.due_s <= t_close
                      and (r.admit_s is None or r.admit_s > t_close))
        delivered = sum(n for r in reqs for t, n, *_ in r.deliveries
                        if t_open <= t <= t_close)
        print(json.dumps({
            "rate": rate, "requests": len(reqs), "finished": len(done),
            "completed_rps": len(done) / (args.seconds + drain_s),
            "delivered_tok_s": delivered / args.seconds,
            "backlog_at_close": backlog,
            "ttft_p50_ms": stats.pct(stats.ttfts_ms(run), 50),
            "ttft_p75_ms": stats.pct(stats.ttfts_ms(run), 75),
            "tpot_p75_ms": stats.pct(stats.tpots_ms(run), 75),
            "queue_p50_first_half_ms": stats.pct(first, 50),
            "queue_p50_second_half_ms": stats.pct(second, 50),
            "drain_s": drain_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
