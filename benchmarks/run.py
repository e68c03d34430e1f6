"""Benchmark orchestrator — one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  REPRO_FULL=1 switches to
paper-scale configs (4000 nodes / 288 slots / ~700k tasks).

``--json`` additionally records each bench run into ``BENCH_<name>.json``
(e.g. ``BENCH_scheduler_throughput.json``).  The file is MERGE-APPENDED,
not overwritten: it holds ``{"bench": ..., "runs": [...]}`` where every
run carries the rows plus the git commit and a UTC timestamp, so the
perf trajectory across PRs survives in-repo and
``scripts/check_bench.py`` can diff the latest run against its
predecessor.  Legacy bare-list files (pre-trajectory format) are wrapped
into the first run on first touch.

``--only <name>`` restricts the run to one bench (repeatable; the
``bench_`` prefix is optional): ``python benchmarks/run.py --json --only
fault_recovery``.  Bare positional names keep working as a legacy filter:
``python benchmarks/run.py --json bench_scheduler_throughput``.
"""
from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

BENCHES = [
    "bench_trace_analysis",
    "bench_fig6_utilization",
    "bench_fig7_qos",
    "bench_fig8_penalty",
    "bench_fig9_load_balance",
    "bench_fig10_cluster_size",
    "bench_fig11_demand_scale",
    "bench_estimator_gap",
    "bench_scheduler_throughput",
    "bench_serving",
    "bench_fault_recovery",
    "bench_roofline",
]


def _git_commit() -> str:
    """Short HEAD hash, suffixed ``+dirty`` when the worktree has
    uncommitted changes — so a trajectory row can never silently pass off
    a dirty-tree measurement as the clean commit it names
    (``check_bench.py`` diffs against the nearest same-dirtiness run).
    """
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, check=True,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"
    try:
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"],
            capture_output=True, text=True, check=True,
        ).stdout.strip())
    except Exception:
        return commit
    return commit + "+dirty" if dirty else commit


def record_run(path: str, bench: str, rows, *, commit: str,
               timestamp: str) -> dict:
    """Merge-append one bench run into the trajectory file at ``path``.

    Returns the full document written.  Pre-existing content is kept:
    the current schema appends to ``runs``; a legacy bare row list is
    wrapped into a first run with ``commit="pre-history"`` so old
    baselines stay diffable.  Unreadable files are replaced (with a
    warning) rather than crashing the bench run.
    """
    doc = {"bench": bench, "runs": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev, list):  # legacy format: bare row list
                doc["runs"] = [{"commit": "pre-history", "timestamp": None,
                                "rows": prev}]
            elif isinstance(prev, dict) and isinstance(prev.get("runs"),
                                                       list):
                doc["runs"] = prev["runs"]
            else:
                print(f"# warning: {path} has an unrecognized shape "
                      f"(no 'runs' list); starting a fresh trajectory",
                      file=sys.stderr)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# warning: could not merge {path} ({e}); rewriting",
                  file=sys.stderr)
    doc["runs"].append({"commit": commit, "timestamp": timestamp,
                        "rows": rows})
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return doc


def main() -> None:
    enable_compile_cache()
    full = os.environ.get("REPRO_FULL", "0") == "1"
    args = sys.argv[1:]
    write_json = "--json" in args
    args = [a for a in args if a != "--json"]
    only = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--only":
            if i + 1 >= len(args):
                raise SystemExit("run.py: --only requires a bench name")
            only.append(args[i + 1])
            i += 2
        elif a.startswith("--only="):
            only.append(a.split("=", 1)[1])
            i += 1
        else:
            only.append(a)          # legacy positional filter
            i += 1
    only = [o if o.startswith("bench_") else f"bench_{o}" for o in only]
    unknown = [o for o in only if o not in BENCHES]
    if unknown:
        raise SystemExit(
            f"run.py: unknown bench(es) {unknown}; known: {BENCHES}")
    only = only or None
    commit = _git_commit()
    timestamp = datetime.datetime.now(datetime.timezone.utc).isoformat()
    print("name,us_per_call,derived")
    t_start = time.time()
    failures = 0
    for mod_name in BENCHES:
        if only and mod_name not in only:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}",
                             fromlist=["run"])
            rows = mod.run(full)
            for row in rows:
                print(row.csv(), flush=True)
            if write_json:
                bench = mod_name.removeprefix("bench_")
                out = f"BENCH_{bench}.json"
                record_run(out, bench,
                           [{"name": r.name, "us_per_call": r.us_per_call,
                             **r.derived} for r in rows],
                           commit=commit, timestamp=timestamp)
                print(f"# appended run {commit} to {out}", flush=True)
        except Exception as e:
            failures += 1
            print(f"{mod_name},0,ERROR={type(e).__name__}:{e}", flush=True)
            traceback.print_exc(limit=4, file=sys.stderr)
    print(f"# total_wall_s={time.time() - t_start:.1f} failures={failures}",
          flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
