"""CI benchmark regression guard over the BENCH_settlement.json trajectory.

    PYTHONPATH=src python -m benchmarks.check_regression economy_epoch bid_eval_sparse

For each named benchmark, compares the *latest* record's ``us_per_call``
against the most recent earlier record of the same name and fails (exit 1)
on a > ``--threshold`` (default 1.5×) slowdown.  Benchmarks with fewer than
two records are skipped — a brand-new benchmark has no baseline to regress
against.  Run it right after a ``--json`` benchmark pass, so the comparison
is fresh-run vs last-recorded.

When ``$GITHUB_STEP_SUMMARY`` is set (i.e. inside a GitHub Actions job),
a per-benchmark markdown trend table — latest vs previous us_per_call,
ratio, verdict, and the recent record history with git SHAs — is appended
to the job summary, so the settlement perf trajectory is readable from the
Actions UI without downloading the artifact.

Records are stamped with ``workload`` (the ECONOMY_EPOCH_* env overrides
in effect) and ``host`` (BENCH_HOST_TAG / "github-ci" /
hostname) by ``run.py --json``; the guard only compares records whose
(name, workload, host) identity matches the latest record's, and loudly
skips a benchmark whose latest record has no like-for-like baseline —
a dev-container number can never fail CI against a runner number, and an
override run can never fail against a default run.  The 1.5× default still
leaves headroom for same-host jitter; the guard is a tripwire, not a
verdict.
"""
from __future__ import annotations

import argparse
import os
import sys

from .run import JSON_PATH, _load_records

HISTORY = 5  # records per benchmark shown in the trend table


def _identity(rec: dict) -> tuple:
    """What must match for two records to be comparable: same workload env
    overrides and same host.  _load_records normalizes both keys, so legacy
    unstamped records form their own ({}, "unknown") cohort."""
    return (tuple(sorted((rec.get("workload") or {}).items())),
            rec.get("host", "unknown"))


def _trend_rows(names: list[str], records: list) -> list[dict]:
    """One summary row per guarded benchmark (newest record last).

    History and the prev/last comparison are restricted to records whose
    (workload, host) identity matches the *latest* record of that name;
    ``row["foreign"]`` counts the records excluded by that filter."""
    rows = []
    for name in names:
        named = [r for r in records if r.get("name") == name]
        if not named:
            rows.append({"name": name, "history": [], "foreign": 0})
            continue
        ident = _identity(named[-1])
        same = [r for r in named if _identity(r) == ident]
        row = {
            "name": name,
            "history": same[-HISTORY:],
            "foreign": len(named) - len(same),
            "host": named[-1].get("host", "unknown"),
        }
        if len(same) >= 2:
            prev, last = same[-2], same[-1]
            row["prev"], row["last"] = prev, last
            row["ratio"] = last["us_per_call"] / max(prev["us_per_call"], 1e-9)
        rows.append(row)
    return rows


def _markdown_table(rows: list[dict], threshold: float) -> str:
    lines = [
        "### Settlement benchmark trend",
        "",
        f"Guard threshold: >{threshold:g}x us_per_call vs the prior record "
        "fails the job.",
        "",
        "| benchmark | latest us/call | prev us/call | ratio | verdict | "
        f"last {HISTORY} records (us/call @ sha) |",
        "|---|---|---|---|---|---|",
    ]
    for row in rows:
        hist = "; ".join(
            f"{r['us_per_call']:.0f} @{r['git_sha']}" for r in row["history"]
        ) or "—"
        if "ratio" in row:
            verdict = "REGRESSION" if row["ratio"] > threshold else "ok"
            lines.append(
                f"| {row['name']} | {row['last']['us_per_call']:.1f} | "
                f"{row['prev']['us_per_call']:.1f} | {row['ratio']:.2f}x | "
                f"{verdict} | {hist} |"
            )
        else:
            note = "no baseline"
            if row.get("foreign"):
                note += f" ({row['foreign']} foreign skipped)"
            lines.append(
                f"| {row['name']} | — | — | — | {note} | {hist} |"
            )
    return "\n".join(lines) + "\n"


def _write_step_summary(table: str) -> None:
    """Append the trend table to the GitHub Actions job summary, if any."""
    path = os.environ.get("GITHUB_STEP_SUMMARY")
    if not path:
        return
    with open(path, "a") as f:
        f.write(table)


def check(names: list[str], threshold: float, path: str = JSON_PATH) -> int:
    records = _load_records(path)
    rows = _trend_rows(names, records)
    failed = False
    for row in rows:
        name = row["name"]
        if "ratio" not in row:
            why = (
                f"no like-for-like baseline on host "
                f"'{row.get('host', 'unknown')}' "
                f"({row['foreign']} record(s) from other hosts/workloads "
                "excluded)"
                if row.get("foreign")
                else "no prior baseline"
            )
            print(
                f"# SKIPPED {name}: {len(row['history'])} comparable "
                f"record(s) — {why}"
            )
            continue
        prev, last, ratio = row["prev"], row["last"], row["ratio"]
        line = (
            f"{name}: {last['us_per_call']:.1f} us (@{last['git_sha']}) vs "
            f"{prev['us_per_call']:.1f} us (@{prev['git_sha']}) = {ratio:.2f}x"
        )
        if ratio > threshold:
            print(f"REGRESSION {line} > {threshold}x", file=sys.stderr)
            failed = True
        else:
            print(f"ok {line}")
    _write_step_summary(_markdown_table(rows, threshold))
    return 1 if failed else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="+", help="benchmark names to guard")
    ap.add_argument("--threshold", type=float, default=1.5,
                    help="max allowed us_per_call ratio vs the prior record")
    ap.add_argument("--path", default=JSON_PATH)
    args = ap.parse_args()
    sys.exit(check(args.names, args.threshold, args.path))


if __name__ == "__main__":
    main()
