#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from (run on the chip).

    python3 bench/check/calibrate.py --workload <cell> --seeds 101,102,... \
        [--control-seeds 101,102,103] [--seconds 51] [--dump DIR]

In one process: builds and warms the cell's engine once, then for every
seed draws that seed's weights and traffic, serves a window at the cell's
own load through the same timed path a benchmark run drives, drains it,
and compares the same sample a run compares with the plain reference,
judged as a run judges it (``harness.judge``).  On the control seeds it
also judges the control: the float8 reference in the program's place,
scored at the same positions.  Prints one JSON line per seed, then a
summary: for each number the cell compares, the program's largest reading
(the lower reading of its limit) and the control's smallest (the upper
reading).  ``--dump`` keeps every compared token's gap and control gap,
one ``.npz`` per seed.  The benchmark's own runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def readings(cell, seeds, control_seeds, seconds, *, require_tpu=True,
             dump=None):
    """Per seed: the program's numbers and whether they pass, and on
    ``control_seeds`` the control's.  Returns a list of dicts."""
    import jax
    import numpy as np
    from bench import generator, harness, weights
    from bench.check import compare

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: needs a TPU")
    model = harness.build_model(cell.config)
    params = weights.make(model, seeds[0])
    rec = harness.Recorder()
    eng = harness.make_engine(model, params, cell, rec)
    harness.warm_up(eng, cell, cell.config["vocab_size"], seeds[0])
    out = []
    for seed in seeds:
        eng.params = params = None
        gc.collect()
        params = jax.block_until_ready(weights.make(model, seed))
        eng.params = params
        plan = generator.plan(cell.mix, cell.spec, seconds)
        tokens = generator.prompt_tokens(seed, plan, cell.config["vocab_size"])
        eng.start([])
        rec.reset()
        with harness.CompileCounter() as counter:
            reqs, _, _ = harness.drive_open(eng, rec, plan, tokens, seconds)
            harness.drain(eng, rec, cell.spec["drain_cap_s"])
        results, _ = eng.finalize()
        harness.attach_log(reqs, rec)
        served = {r.rid: (tokens[r.rid], list(results[r.rid].tokens))
                  for r in reqs if r.finished}
        rids = compare.sample(served, seed, cell.spec.get(
            "sample_tokens", harness.SAMPLE_TOKENS))
        # the page pool waits on the host while the reference runs, so the
        # reference has the memory it has in a benchmark run (engine freed)
        pool = jax.device_get(eng.caches)
        eng.caches = None
        gc.collect()
        ctrl = seed in control_seeds
        t0 = time.perf_counter()
        cmp = compare.compare(params, cell.config, served, rids,
                              control=ctrl, keep=dump is not None)
        compare_s = time.perf_counter() - t0
        eng.caches = jax.device_put(pool)
        del pool
        check, ok = harness.judge(cell, cmp, reqs, counter.n)
        row = {"seed": seed, "correct": ok,
               "check": {k: v["value"] for k, v in check.items()},
               "control_correct": None, "control_check": None}
        if ctrl:
            c_check, c_ok = harness.judge(cell, cmp, reqs, counter.n,
                                          control=True)
            row.update(control_correct=c_ok, control_check={
                k: v["value"] for k, v in c_check.items()})
        row.update({k: v for k, v in cmp.items()
                    if k not in ("per_request", "tokens_by_rid")})
        row.update(requests=len(reqs), compared_requests=len(rids),
                   compare_s=compare_s)
        if dump is not None:
            Path(dump).mkdir(parents=True, exist_ok=True)
            arrays = {f"{k}_{rid}": v
                      for rid, d in cmp["tokens_by_rid"].items()
                      for k, v in d.items()}
            np.savez_compressed(Path(dump) / f"{cell.name}_{seed}.npz",
                                **arrays)
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def limits_readings(cell, rows) -> dict:
    """For each gap statistic the cell compares: the program's largest
    reading and the control's smallest (None without control seeds)."""
    out = {}
    for k in cell.spec["check"]:
        ctrl = [r["control_" + k] for r in rows if "control_" + k in r]
        out[k] = {"lower": max(r[k] for r in rows),
                  "upper": min(ctrl) if ctrl else None,
                  "seeds": len(rows), "control_seeds": len(ctrl)}
    return out


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    from bench import harness
    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = readings(cell, seeds, ctrl, args.seconds, dump=args.dump)
    print(json.dumps({"workload": args.workload,
                      "readings": limits_readings(cell, rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
