#!/usr/bin/env python3
"""One run of one cell of the market benchmark, on the accelerator.

    python3 bench/run_cell.py --workload paper100k.stream --seed 7 \\
        --seconds 30 --trace 0

The cell is named in ``BENCHMARK.json`` at the root of the checkout; its
deployment, traffic mix, correctness limits and per-layer metric readers are
files under ``bench/`` found by name.  With ``--trace 0`` the result line
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics read from a profiler trace of the window.  The last line of stdout
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``checks``, each number
the correctness check compared beside its limit; the same comparisons are
the last lines of stderr.

The run exits nonzero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for: nothing falls back to the CPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the compilation cache stays inside the checkout, at a path that never moves
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# the program and this package, and not bench/ itself: its trace.py would
# shadow the standard library's module of that name
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import traffic  # noqa: E402


def _read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by the names in ``BENCHMARK.json``."""
    spec = _read_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "config": _read_json(root, config["file"]),
        "mix": traffic.load_mix(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")),
        "limits": _read_json(BENCH, "limits", f"{name}.json"),
        "e2e": {m["name"]: m["unit"] for m in spec["end_to_end"] if _applies(m, name)},
        "readers": {m["name"]: (m["unit"], _reader(m["name"]))
                    for m in spec["per_layer"] if _applies(m, name)},
    }


def peaks_for(kind: str) -> dict:
    table = _read_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) {ok}", file=sys.stderr)
    sys.stderr.flush()
    keys = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"]
    print(json.dumps({k: result[k] for k in keys if k in result}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)

    import jax

    devices = jax.devices()
    chips = int(spec["cell"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"[bench] needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform!r} device(s); nothing was run", file=sys.stderr)
        return 2
    peaks = peaks_for(devices[0].device_kind)

    from bench import harness

    result = harness.run(
        spec["config"], spec["mix"], spec["limits"], spec["e2e"],
        spec["readers"] if args.trace else {}, args.seed, args.seconds,
        bool(args.trace), peaks, T_START,
    )
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
