"""Define, register and evaluate a CUSTOM placement policy end-to-end.

The whole policy is ~15 lines: subclass nothing, implement ``feasible`` +
``score`` with the shared admission helpers, register a name, and the
simulator, Experiment runner and benchmarks can all use it.  ``run`` then
vmaps 8 seeds into one XLA program and prints the seed spread.

  PYTHONPATH=src python examples/custom_policy.py
"""
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.api import Experiment, admission, register_policy
from repro.compile_cache import enable_compile_cache
from repro.core import SimConfig
from repro.traces import generate_calibrated


@register_policy("random-fit")
@dataclasses.dataclass(frozen=True)
class RandomFitPolicy:
    """Admit anywhere the penalized usage fits; break ties pseudo-randomly
    (a hash of the node's task count and the task's source bucket)."""

    name = "random-fit"

    def feasible(self, ctx, task):
        load = admission.usage_load(ctx.node.est_usage, ctx.node.reserved,
                                    ctx.penalty)
        return admission.fits(load, task.request, 1.0)

    def score(self, ctx, task):
        mix = ctx.node.n_tasks * 2654435 + task.src * 40503
        return (mix % 9973).astype(jnp.float32)


def main():
    enable_compile_cache()
    cfg = SimConfig(n_nodes=200, n_slots=64, arrivals_per_slot=1024,
                    retry_capacity=256)
    ts = generate_calibrated(0, cfg.n_nodes, cfg.n_slots, offered_load=1.6)
    for name in ("flex-f", "random-fit"):
        res = Experiment(ts, cfg, policy=name).run(seeds=range(8))
        qos = np.asarray(res.metrics.qos)            # (8, S)
        util = np.asarray(res.metrics.usage[..., 0])  # (8, S)
        print(f"{name:10s} over 8 vmapped seeds: "
              f"util {util.mean():.3f} +/- {util.mean(axis=1).std():.4f}  "
              f"QoS {qos.mean():.4f} +/- {qos.mean(axis=1).std():.4f}")


if __name__ == "__main__":
    main()
