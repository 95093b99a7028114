#!/usr/bin/env python3
"""Run one cell of the chip benchmark on the TPU this process starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device`` and, last,
``check``: each number the correctness comparison read, beside its limit.
The same numbers close standard error.  Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
``--control 1`` puts the float8 control in the program's place in the
comparison (``bench/check/``): a run that must come out not correct.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    harness.use_compile_cache()
    bench = harness.benchmark()
    cell = harness.load_cell(args.workload, bench)
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found "
                         f"{devices[0].platform!r} devices")
    if len(devices) < chips:
        raise SystemExit(f"bench: {args.workload} needs {chips} chips, JAX "
                         f"found {len(devices)}")
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS, control=bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
