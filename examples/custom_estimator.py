"""Define, register and evaluate a CUSTOM load estimator end-to-end.

The whole estimator is ~20 lines: a frozen dataclass with ``init_state``
(build the :class:`repro.estimators.EstimatorState` pytree the simulator
carries through its scan) and ``refresh`` (new state from fresh (N, R)
usage measurements).  Register a name and ``SimConfig(estimator=...)``,
``Experiment(estimator=...)`` and the serving engine can all use it.

This one is a peak-hold estimator: L-hat tracks the running peak of
measured usage, decayed each slot — more conservative than ``current``
(it remembers bursts), cheaper than the windowed ``quantile``.

  PYTHONPATH=src python examples/custom_estimator.py
"""
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.api import Experiment, register_estimator
from repro.compile_cache import enable_compile_cache
from repro.core import SimConfig
from repro.estimators import EstimatorState, zeros_state
from repro.traces import generate_calibrated


@register_estimator("peak-hold")
@dataclasses.dataclass(frozen=True)
class PeakHoldEstimator:
    """L-hat = max(measured, decay * previous L-hat): remembers bursts."""

    decay: float = 0.95

    def init_state(self, n_nodes: int, n_resources: int = 2):
        return zeros_state(n_nodes, n_resources)

    def refresh(self, state, node_usage, key):
        est = jnp.maximum(node_usage, self.decay * state.est)
        return EstimatorState(est=est, aux=state.aux)


def main():
    enable_compile_cache()
    cfg = SimConfig(n_nodes=100, n_slots=32, arrivals_per_slot=256,
                    retry_capacity=64, reclamation=True, reclaim_pool=256)
    ts = generate_calibrated(0, cfg.n_nodes, cfg.n_slots, offered_load=1.6)
    for name in ("current", "peak-hold"):
        res = Experiment(ts, cfg._replace(estimator=name),
                         policy="least-fit").run()
        adm = np.asarray(res.placement >= 0).mean()
        qos = np.asarray(res.metrics.qos)
        recl = int(res.metrics.n_reclaimed[-1])
        print(f"{name:10s} admitted {adm:.3f}  QoS {qos.mean():.4f}  "
              f"reclaimed {recl}")


if __name__ == "__main__":
    main()
