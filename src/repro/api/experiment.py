"""``Experiment``: one compiled XLA program per (policy, cluster) study.

The seed repo re-ran the Python simulator once per seed / parameter point
(``benchmarks/common.py``'s loop).  ``Experiment`` instead traces the
simulator once and ``vmap``s over PRNG seeds and ``FlexParams`` sweeps, so
a 10-seed x 8-theta study is a single device program:

    exp = Experiment(trace, cluster, policy="flex-f")
    res = exp.run(seeds=range(10))                       # leaves: (10, S, ...)
    res = exp.run(seeds=[0, 1], sweep=[p1, p2, p3])      # leaves: (3, 2, S, ...)

Policies are registry names or policy objects — anything
``repro.api.registry.resolve_policy`` accepts.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import simulator
from repro.core.types import FlexParams, SimConfig, SimResult, TaskSet


def _stack_params(sweep) -> FlexParams:
    """list[FlexParams] | stacked FlexParams -> stacked pytree.

    Sweep points are taken VERBATIM — ``prepare_params`` pinning (e.g.
    LeastFit's theta=1) is deliberately not applied, otherwise a theta
    sweep over a pinning policy would collapse to identical rows.
    """
    if isinstance(sweep, FlexParams):
        return sweep
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *sweep)


class Experiment:
    """A workload x cluster x policy study with a vmapped runner."""

    def __init__(self, trace: TaskSet, cluster: Optional[SimConfig] = None,
                 policy="flex-f", params: Optional[FlexParams] = None,
                 estimator=None, est_noise_std: float = 0.0,
                 controller=None):
        self.trace = trace
        self.cluster = cluster if cluster is not None else SimConfig()
        # Same normalization as the simulate() entry point (one
        # implementation — the two front-ends cannot drift).  ``estimator``
        # may be a repro.estimators registry name or an estimator object;
        # None defers to SimConfig.estimator, then "current".
        (self.policy, self.params, self.estimator,
         self.controller) = simulator._resolve(
            policy, params, estimator, est_noise_std, controller,
            self.cluster)
        self._table = None

    # -- internals ---------------------------------------------------------

    @property
    def arrival_table(self) -> jnp.ndarray:
        if self._table is None:
            table = simulator.build_arrival_table(
                np.asarray(self.trace.arrival), self.cluster.n_slots,
                self.cluster.arrivals_per_slot)
            self._table = jnp.asarray(table)
        return self._table

    def _one(self, params: FlexParams, key: jax.Array) -> SimResult:
        return simulator.simulate_core(
            self.trace, self.arrival_table, self.cluster, self.policy,
            params, key, self.estimator, self.controller)

    # -- public API ---------------------------------------------------------

    def run(self, seeds=0, sweep=None) -> SimResult:
        """Simulate; vmap over seeds and an optional FlexParams sweep.

        seeds: int (single run, no leading axis) or a sequence of ints
          (leading seed axis on every result leaf).
        sweep: optional list of FlexParams (or a pre-stacked FlexParams
          pytree); adds an outer sweep axis.

        Returns a SimResult whose leaves carry [sweep, [seed,]] leading axes.
        """
        single_seed = not isinstance(seeds,
                                     (Sequence, range, np.ndarray, jax.Array))
        seed_list = [seeds] if single_seed else list(seeds)
        keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seed_list])

        fn = self._one
        if not single_seed:
            fn = jax.vmap(fn, in_axes=(None, 0))

        if sweep is None:
            key_arg = keys[0] if single_seed else keys
            return fn(self.params, key_arg)

        stacked = _stack_params(sweep)
        key_arg = keys[0] if single_seed else keys
        return jax.vmap(fn, in_axes=(0, None))(stacked, key_arg)

    def summarize(self, qos_target: float = 0.99, **run_kw):
        """Single-run convenience: ``analysis.summarize`` of ``run()``."""
        from repro.traces import analysis
        return analysis.summarize(self.trace, self.run(**run_kw), qos_target)
