#!/usr/bin/env python3
"""Device time per slot of the program's named scopes, for `sim.*` cells.

For each cell it makes the set-up of a benchmark run (the front end's
``Session``), a traced window recorded as a ``--trace 1`` run records
it, and an untraced window timed as a ``--trace 0`` run times it.  The
recorded device time is put down to the named scopes with the text of
the compiled program that ran (``scopes.per_slot``); the device's slot
period is set against the untraced window's ``1 / sim_slots_per_s``.
Run it on the chip, from the root of a checkout:

    python3 chipbench/tools/scope_profile.py --workload sim.gct4000.overload \
        --workload sim.gct4000.trace --seed 3013000001 --seconds 10

Each cell prints one JSON line and writes it to
``chipbench/_out/scope_profile/<cell>.<seed>.json``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
OUT = HERE / "_out" / "scope_profile"


def program_text(session) -> str:
    """The text of the program that the session's studies run."""
    import jax

    from repro.core import simulator

    exp = session.exp
    key = jax.random.PRNGKey(0)
    return simulator.simulate_core.lower(
        exp.trace, exp.arrival_table, exp.cluster, exp.policy, exp.params,
        key, exp.estimator, exp.controller).compile().as_text()


def top_ops(trace, names, slots, n=15):
    """[instruction, scope, device ms per slot] of the longest ops."""
    import scopes

    tot = defaultdict(float)
    for d in trace.devices:
        for name, s, e in d["ops"]:
            tot[scopes.instruction(name)] += (min(e, trace.hi)
                                              - max(s, trace.lo))
    per = len(trace.devices) * slots * 1e6
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[i, scopes.scope(names[i]) if i in names else scopes.UNMAPPED,
             v / per] for i, v in top]


def profile(run, seed: int, seconds: float, trace_dir: Path) -> dict:
    """One cell's row: untraced rate, traced window by scope, costs."""
    import scopes
    import tracing
    from harness import load_module

    frontend = load_module(run.frontend, "scope_profile_frontend")
    session = frontend.Session(run.config, run.mix, seed)
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = tracing.Tracer(trace_dir)
    traced = session.window(seconds, tracer)
    tracer.close()
    session.results = []          # the window counts the session's studies
    untraced = session.window(seconds)

    t0 = time.perf_counter()
    trace = tracing.reduce(trace_dir)
    t1 = time.perf_counter()
    names = scopes.op_names(program_text(session))
    table = scopes.per_slot(trace, scopes.read_containers(trace_dir), names)
    t2 = time.perf_counter()
    shutil.rmtree(trace_dir, ignore_errors=True)

    rate = untraced["e2e"]["sim_slots_per_s"]
    row = {"workload": run.cell["name"], "seed": seed,
           "untraced": {"sim_slots_per_s": rate,
                        "slot_ms": 1000.0 / rate,
                        "studies": untraced["info"]["studies"]},
           "traced": {"studies": traced["info"]["studies"],
                      "window_s": trace.window_s(),
                      "busy_s": trace.busy_s(),
                      "idle_share": trace.idle_share(),
                      "breakdown": trace.breakdown()},
           "reduce_s": t1 - t0, "scope_reduce_s": t2 - t1,
           "instructions": len(names),
           "scoped_instructions": sum(scopes.scope(o) != scopes.UNSCOPED
                                      for o in names.values()),
           "scopes": table}
    if table is not None:
        row["slot_period_vs_untraced"] = (table["slot_period_ms"]
                                          / row["untraced"]["slot_ms"] - 1)
        row["admission_ms_per_slot"] = scopes.layer_ms(table, "admission")
        row["slot_pipeline_ms_per_slot"] = scopes.layer_ms(table,
                                                           "slot_pipeline")
        row["top_ops"] = top_ops(trace, names, table["slots"])
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    import jax

    from harness import enable_cache, resolve

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("scope_profile: needs a TPU; JAX finds none")
    enable_cache()
    # JAX leaves metadata out of the cache key, so a cached program that
    # differs from this one only in its scopes (an older commit's) would
    # be loaded, and its text would name no scope
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    OUT.mkdir(parents=True, exist_ok=True)
    for cell in args.workload:
        row = profile(resolve(bench, cell), args.seed, args.seconds,
                      HERE / "_out" / "scope_trace" / cell)
        row["device"] = dev.device_kind
        line = json.dumps(row)
        (OUT / f"{cell}.{args.seed}.json").write_text(line + "\n")
        print(line, flush=True)


if __name__ == "__main__":
    main()
