"""Placement policy unit tests (Algorithms 1-3)."""
import jax.numpy as jnp
import numpy as np

from repro.api import PolicyContext, TaskView, admission, get_policy
from repro.core import (FlexParams, NodeState, fifo_scheduler,
                        lrf_scheduler)

P = FlexParams.default()


def _place(ns, request, penalty, name):
    """ScheduleOne (Alg. 3) of one valid task from source 0."""
    ctx = PolicyContext(node=ns, penalty=jnp.asarray(penalty), params=P)
    task = TaskView(jnp.asarray(request), jnp.asarray(0), jnp.asarray(0))
    return admission.admit_one(get_policy(name), ctx, task,
                               jnp.asarray(True))


def _admit(ns, reqs, valid, name):
    """Admit a queue of source-0 batch tasks in queue order at P = 1."""
    zeros = jnp.zeros((reqs.shape[0],), jnp.int32)
    return admission.admit_queue(get_policy(name), ns, reqs, zeros, zeros,
                                 valid, jnp.asarray(1.0), P)


def _node(est, requested=None, n=None):
    est = jnp.asarray(est, jnp.float32)
    N = est.shape[0]
    ns = NodeState.zeros(N)
    ns = ns._replace(est_usage=est)
    if requested is not None:
        ns = ns._replace(requested=jnp.asarray(requested, jnp.float32))
    return ns


def test_flex_places_on_least_loaded():
    ns = _node([[0.8, 0.8], [0.1, 0.1], [0.5, 0.5]])
    _, idx = _place(ns, [0.1, 0.1], 1.0, "flex-f")
    assert int(idx) == 1


def test_flex_respects_capacity_with_penalty():
    ns = _node([[0.6, 0.6]])
    # P=1: 0.6 + 0.3 <= 1 fits;  P=1.5: 0.9 + 0.3 > 1 rejected
    _, i1 = _place(ns, [0.3, 0.3], 1.0, "flex-f")
    _, i2 = _place(ns, [0.3, 0.3], 1.5, "flex-f")
    assert int(i1) == 0 and int(i2) == -1


def test_leastfit_uses_requests_not_usage():
    ns = _node(est=[[0.9, 0.9], [0.0, 0.0]],
               requested=[[0.1, 0.1], [0.8, 0.8]])
    _, idx = _place(ns, [0.1, 0.1], 1.0, "least-fit")
    assert int(idx) == 0  # lowest REQUESTED, despite high usage


def test_reservation_accumulates_within_round():
    ns = _node([[0.0, 0.0], [0.0, 0.0]])
    reqs = jnp.full((4, 2), 0.4, jnp.float32)
    valid = jnp.ones((4,), bool)
    ns2, placed = _admit(ns, reqs, valid, "flex-f")
    placed = np.asarray(placed)
    # 0.4 each, capacity 1.0 -> two per node, alternating via reservations
    assert (placed >= 0).all()
    assert sorted(placed.tolist()) == [0, 0, 1, 1]


def test_invalid_entries_skipped():
    ns = _node([[0.0, 0.0]])
    reqs = jnp.full((2, 2), 0.3, jnp.float32)
    valid = jnp.asarray([True, False])
    ns2, placed = _admit(ns, reqs, valid, "flex-f")
    assert int(placed[0]) == 0 and int(placed[1]) == -1
    assert int(ns2.n_tasks[0]) == 1


def test_fifo_vs_lrf_order():
    loads = jnp.zeros((2,))
    reqs = jnp.asarray([0.1, 0.9, 0.5, 0.2])
    lf, af = fifo_scheduler(loads, reqs)
    ll, al = lrf_scheduler(loads, reqs)
    # LRF balances better on this instance
    assert float(jnp.max(ll)) <= float(jnp.max(lf)) + 1e-6
    # assignments returned in original order
    assert al.shape == af.shape == (4,)
