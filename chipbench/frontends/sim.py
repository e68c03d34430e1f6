"""Simulator front end: ``repro.api.Experiment`` -> ``simulate_core``.

Set-up generates the cell's task trace from ``--seed`` over the whole
trace horizon (a fixed number of tasks per mix, so every seed runs one
program), builds the ``Experiment`` at the configuration's cluster
size and compiles its program ahead of time.  The window runs studies
back to back, each ended by ``block_until_ready``, until its time is up;
study k draws its demand noise from a key made from (seed, k), a traced
argument, so nothing compiles again.  A traced run records a few
seconds of its second study, from ``TRACE_FROM`` of the way through it
(timed by the first study), when the cluster is full and the retry
queue is under pressure; the window ends with the study in which the
recording started (a later one where a study is shorter than the
profiler's start).

The check draws one finished study from the seed and runs the plain
reference (``reference/sim_ref.py``) on the same trace and key over the
configuration's first ``check_slots`` slots (two thirds of the study, so
that the reference takes less time than the window): every decision
taken in those slots (each task's placement and admit slot) and the
per-slot QoS (and penalty, which is not held to a limit: see PERF.md).
"""
from __future__ import annotations

import sys
import time

import numpy as np

from gen.cluster import generate_taskset


def make_tasks(config: dict, mix: dict, seed: int) -> dict:
    """The cell's trace: the mix's fixed task count (its calibrated
    offered load) over the whole trace horizon, drawn from the seed."""
    return generate_taskset(seed, int(mix["n_tasks"]),
                            int(config["trace_slots"]))


def demand_seed(seed: int, k: int) -> int:
    """Study k's demand-noise seed: fits ``jax.random.PRNGKey``."""
    return (seed * 1_000_003 + k) % (2 ** 31)


def sim_config(config: dict):
    from repro.core import SimConfig

    return SimConfig(n_nodes=int(config["n_nodes"]),
                     n_slots=int(config["n_slots"]),
                     arrivals_per_slot=int(config["arrivals_per_slot"]),
                     retry_capacity=int(config["retry_capacity"]))


# A study is one device call of about 10^7 operations; a trace of a
# whole one is too large to write and read within a run, so a traced run
# records at most TRACE_SECONDS of its second study, between these
# shares of the first study's time: past the slot (about 48 of 96) by
# which the cluster has filled, and before the study ends.
TRACE_FROM, TRACE_UNTIL = 0.6, 0.9
TRACE_SECONDS = 2.0


class Session:

    def __init__(self, config: dict, mix: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from repro.api import Experiment
        from repro.core import FlexParams, TaskSet, simulator

        self.config, self.seed = config, seed
        t0 = time.perf_counter()
        self.tasks = make_tasks(config, mix, seed)
        t1 = time.perf_counter()
        ts = TaskSet(**{k: jnp.asarray(v) for k, v in self.tasks.items()})
        exp = Experiment(ts, sim_config(config), policy=config["policy"],
                         params=FlexParams.default(
                             qos_target=float(config["qos_target"])),
                         estimator=config["estimator"])
        key = jnp.stack([jax.random.PRNGKey(demand_seed(seed, 0))])[0]
        simulator.simulate_core.lower(
            exp.trace, exp.arrival_table, exp.cluster, exp.policy,
            exp.params, key, exp.estimator, exp.controller).compile()
        print(f"chipbench: sim set-up: trace of {len(self.tasks['arrival'])} "
              f"tasks {t1 - t0:.3f} s, program {time.perf_counter() - t1:.3f}"
              " s", file=sys.stderr, flush=True)
        self.exp = exp
        self.results = []

    def window(self, seconds: float, tracer=None) -> dict:
        import jax

        n_slots = int(self.config["n_slots"])
        t0 = time.perf_counter()
        first_s = None
        while True:
            k = len(self.results)
            if tracer is not None and k == 1:
                tracer.start(TRACE_FROM * first_s,
                             min(TRACE_SECONDS,
                                 (TRACE_UNTIL - TRACE_FROM) * first_s),
                             "chipbench.study")
            t_study = time.perf_counter()
            res = jax.block_until_ready(
                self.exp.run(seeds=demand_seed(self.seed, k)))
            first_s = first_s or time.perf_counter() - t_study
            self.results.append((res.placement, res.admit_slot,
                                 res.metrics.qos, res.metrics.penalty))
            del res
            if tracer is not None and k >= 1 and tracer.started():
                tracer.close()
            if (time.perf_counter() - t0 >= seconds
                    and (tracer is None or tracer.done())):
                break
        wall = time.perf_counter() - t0
        studies = len(self.results)
        return {"e2e": {"sim_slots_per_s": studies * n_slots / wall},
                "attempted": studies * len(self.tasks["arrival"]),
                "failed": 0,
                "info": {"studies": studies, "window_s": wall,
                         "tasks": len(self.tasks["arrival"])}}

    def release(self) -> dict:
        """One study drawn from the seed, on the host; the rest dropped."""
        k = int(np.random.default_rng(self.seed).integers(len(self.results)))
        place, admit, qos, pen = (np.asarray(x) for x in self.results[k])
        self.results, self.exp = None, None
        return {"study": k, "placement": place, "admit_slot": admit,
                "qos": qos, "penalty": pen}

    def check(self, kept: dict, limits: dict) -> list:
        """(name, value, limit) of each number the configuration holds
        to a limit."""
        from reference.sim_ref import run_reference

        slots = int(self.config["check_slots"])
        ref = run_reference(dict(self.config, n_slots=slots), self.tasks,
                            demand_seed(self.seed, kept["study"]))
        got = compare(kept, ref, slots, self.tasks["arrival"])
        return [(k, got[k], limits[k]) for k in sorted(limits)]


def compare(kept: dict, ref: dict, slots: int, arrival) -> dict:
    """Numbers compared over the first ``slots`` slots: the share of the
    tasks that arrived in them whose (placement, admit slot) as of the
    end of the last differs from the reference's, and the largest gaps of
    the per-slot QoS and penalty series."""
    late = kept["admit_slot"] >= slots
    place = np.where(late, -1, kept["placement"])
    admit = np.where(late, -1, kept["admit_slot"])
    due = arrival < slots
    differ = ((place != ref["placement"])
              | (admit != ref["admit_slot"]))[due]
    return {
        "tasks_differing": float(differ.mean()) if differ.size else 0.0,
        "qos_gap": float(np.max(np.abs(kept["qos"][:slots] - ref["qos"]))),
        "penalty_gap": float(np.max(np.abs(kept["penalty"][:slots]
                                           - ref["penalty"]))),
    }
