"""Built-in load estimators (paper §4.2, §5.1 + the predictive variants).

``current`` and ``ewma`` are the stateful re-expressions of the seed
repo's :mod:`repro.core.estimator` stub — same jnp expressions, same
operation order, so the historical ``est_noise_std`` knob and the legacy
stateless classes stay bit-identical (tests/test_estimators.py proves it).

``quantile`` is the sliding peak-window predictor the related work uses
for right-sizing (Lu & Chen's demand prediction, Beloglazov & Buyya's
consolidation margins): L-hat = the q-quantile of the last ``window``
usage measurements per node/resource, held in a static ring buffer
carried through the simulator scan.  High q tracks recent *peaks*, which
is what makes headroom reclamation safe: reclaimed capacity is judged
against near-peak predicted usage, not the instantaneous sample.

The ``learned`` estimator lives in :mod:`repro.estimators.learned`.

All estimators are frozen dataclasses — hashable static-jit arguments;
every array lives in the :class:`EstimatorState` pytree.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import estimator as _est
from repro.core.types import NUM_RESOURCES
from repro.estimators.base import EstimatorState, zeros_state
from repro.estimators.registry import register_estimator


def ring_push(buffer: jnp.ndarray, t: jnp.ndarray,
              usage: jnp.ndarray) -> jnp.ndarray:
    """Write ``usage`` into slot ``t % window`` of a (W, ...) ring buffer.

    The FIRST measurement (t == 0) is broadcast into every window slot, so
    the window is always full and downstream reductions (quantile, MLP
    input) never need a fill-count special case; until the window wraps
    once, unwritten slots simply repeat the first sample.
    """
    written = buffer.at[t % buffer.shape[0]].set(usage)
    return jnp.where(t == 0, jnp.broadcast_to(usage, buffer.shape), written)


def ring_chronological(buffer: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Reorder a ring buffer oldest-first (newest sample last).

    After ``ring_push`` at slot ``t`` the newest sample sits at
    ``t % W``; rolling by ``-(t % W + 1)`` puts it at index W-1.
    """
    return jnp.roll(buffer, -(t % buffer.shape[0] + 1), axis=0)


@register_estimator("current")
@dataclasses.dataclass(frozen=True)
class CurrentEstimator:
    """The paper's estimator: L-hat = measured current usage.

    ``noise_std`` adds multiplicative measurement noise (clamped at zero —
    an estimate is never negative) so tests and benches can stress the
    penalty controller with a *bad* estimator.
    """

    noise_std: float = 0.0

    def init_state(self, n_nodes: int,
                   n_resources: int = NUM_RESOURCES) -> EstimatorState:
        return zeros_state(n_nodes, n_resources)

    def refresh(self, state: EstimatorState, node_usage: jnp.ndarray,
                key: jax.Array) -> EstimatorState:
        return EstimatorState(
            est=_est.current_usage(node_usage, key, self.noise_std), aux=())


@register_estimator("ewma")
@dataclasses.dataclass(frozen=True)
class EwmaEstimator:
    """EWMA smoothing (the related work's standard choice).

    ``decay=0`` degenerates to the ``current`` estimator exactly
    (0 * prev + 1 * measurement).
    """

    decay: float = 0.7

    def init_state(self, n_nodes: int,
                   n_resources: int = NUM_RESOURCES) -> EstimatorState:
        return zeros_state(n_nodes, n_resources)

    def refresh(self, state: EstimatorState, node_usage: jnp.ndarray,
                key: jax.Array) -> EstimatorState:
        return EstimatorState(
            est=_est.ewma(state.est, node_usage, self.decay), aux=())


@register_estimator("quantile")
@dataclasses.dataclass(frozen=True)
class QuantileWindowEstimator:
    """Sliding peak-window quantile predictor.

    L-hat = the ``q``-quantile (linear interpolation, numpy semantics)
    over the last ``window`` usage samples per node/resource.  State is a
    static ``(window, N, R)`` ring buffer plus a slot counter; the first
    sample fills the whole window (see ``ring_push``), so the quantile is
    always over exactly ``window`` values.
    """

    window: int = 12   # 1 h of history at the trace's 5-minute slots
    q: float = 0.9

    def init_state(self, n_nodes: int,
                   n_resources: int = NUM_RESOURCES) -> EstimatorState:
        buffer = jnp.zeros((self.window, n_nodes, n_resources), jnp.float32)
        return zeros_state(n_nodes, n_resources,
                           aux=(buffer, jnp.zeros((), jnp.int32)))

    def refresh(self, state: EstimatorState, node_usage: jnp.ndarray,
                key: jax.Array) -> EstimatorState:
        buffer, t = state.aux
        buffer = ring_push(buffer, t, node_usage)
        est = jnp.quantile(buffer, self.q, axis=0).astype(jnp.float32)
        return EstimatorState(est=est, aux=(buffer, t + 1))
