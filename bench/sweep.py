#!/usr/bin/env python3
"""Find a cell's knee: the highest mean rate whose backlog does not grow.

    python3 bench/sweep.py --workload paper100k.stream --seed 11 \\
        --seconds 10 --rates 2000 4000 6000 8000

One process stands the cell's market up once and drives one window per
rate, in the order given, each with the cell's traffic mix at that mean
rate.  The backlog is the number of bids due but not yet submitted, read at
every tick's start; it grows when its mean over the window's last third
exceeds that over its first third by more than one tick's worth of bids
(``rate × tick period``).  One JSON line per rate goes to stdout.  Only
``poisson`` mixes have a rate to sweep.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from bench.run_cell import load_cell  # noqa: E402


def backlog_growth(cell, t0: float, rate: float) -> tuple[float, float, bool]:
    """(first-third mean, last-third mean, grows) of the backlog at tick starts."""
    win = [t for t in cell.ticks if t.in_window and t0 <= t.start < t0 + cell.seconds]
    due = cell.sched.due
    b = np.array([np.searchsorted(due, t.start - t0, side="right") - (t.boundary - cell.base)
                  for t in win], np.float64)
    k = max(len(b) // 3, 1)
    first, last = float(b[:k].mean()), float(b[-k:].mean())
    period = float(cell.mix["tick"]["every_s"])
    return first, last, last - first > rate * period


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("[sweep] needs a TPU; nothing was run", file=sys.stderr)
        return 2
    from bench import harness

    harness.configure_cache()
    cell = harness.Cell(spec["config"], spec["mix"], args.seed, args.seconds)
    warmed: set = set()
    for rate in args.rates:
        cell.mix = dict(spec["mix"], rate_per_s=rate)
        cell.schedule()
        warmed |= cell.warm_up(skip=warmed)
        t0, t1 = cell.drive(lambda _: contextlib.nullcontext())
        e2e = cell.end_to_end(t0, t1)
        first, last, grows = backlog_growth(cell, t0, rate)
        print(json.dumps({"rate_per_s": rate, **e2e, "attempted": cell.attempted,
                          "failed": cell.failed, "backlog_first_third": first,
                          "backlog_last_third": last, "backlog_grows": grows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
