#!/usr/bin/env python3
"""Readings of the correctness check for its limits: the program's, and the
bfloat16 control's, on several seeds of one cell.

    python3 bench/control.py --workload paper100k.stream --seconds 10 \\
        --seeds 11 12 13

For each seed, in one process, a run of the cell at its own size and load
(``--seconds`` of window), checked as every run is, then at six of the
window's ticks drawn from the seed the reference settled in bfloat16 put in
the program's place: its reserve against the float64 one, its prices
against the float32 reference's, the float32 reference's excess demand at
its prices, and its allocation against the float32 reference's at those
prices.  The control's readings decide ``correct``, which must come out
false.  One JSON line per seed goes to stdout: ``correct`` (the control's
verdict), ``control`` and ``program`` (each reading beside its limit).  A
limit sits above the largest program reading over a dozen seeds or more
and below the smallest control reading; ``PERF.md`` records both.  The
benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench.run_cell import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("[control] needs a TPU; nothing was run", file=sys.stderr)
        return 2
    from bench import harness

    for seed in args.seeds:
        res = harness.run(spec["config"], spec["mix"], spec["limits"], spec["e2e"], {}, seed,
                          args.seconds, False, None, time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"], "control": res["checks"],
                          "program": res["program_checks"], "metrics": res["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
