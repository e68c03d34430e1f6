#!/usr/bin/env python3
"""Readings of a cell's numbers compared, for setting their limits.

For each seed it prints the control's reading: the plain reference in
bfloat16 (the precision below the configuration's float32) put in the
program's place and compared with the float32 reference, at the cell's
own size.  With ``--program`` it also prints the program's own reading
(its timed path against the float32 reference), which is what a run's
check reads.  Run it on the chip, from the root of a checkout:

    python3 chipbench/tools/control.py --workload sim.gct4000.overload \
        --seeds 11,12,13
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from harness import enable_cache, load_module, resolve
    from reference.sim_ref import run_reference

    enable_cache()
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    run = resolve(bench, args.workload)
    fe = load_module(run.frontend, "frontend")
    conf, mix = run.config, run.mix
    print(f"device {jax.devices()[0].device_kind}", flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        row = {"workload": args.workload, "seed": seed}
        slots = int(conf["check_slots"])
        cut = dict(conf, n_slots=slots)
        tasks = fe.make_tasks(conf, mix, seed)
        key = fe.demand_seed(seed, 0)
        ref = run_reference(cut, tasks, key)
        low = run_reference(cut, tasks, key, dtype=jnp.bfloat16)
        row["control"] = fe.compare(low, ref, slots, tasks["arrival"])
        if args.program:
            s = fe.Session(conf, mix, seed)
            s.window(0.0)
            kept = s.release()
            kept["study"] = 0
            row["program"] = fe.compare(kept, ref, slots, tasks["arrival"])
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
