"""repro.api: registry round-trip, custom policies, front-end equivalence,
Experiment vmapping, and simulator/serving admission parity."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import (Experiment, PolicyContext, TaskView, admission,
                       get_policy, list_policies, register_policy,
                       resolve_policy)
from repro.api.policies import FlexFifoPolicy, PriorityFlexPolicy
from repro.core import (CLASS_BATCH, CLASS_PRODUCTION, ControllerState,
                        FlexParams, NodeState, SimConfig, run)
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.traces import generate_calibrated

CFG = SimConfig(n_nodes=40, n_slots=12, arrivals_per_slot=128,
                retry_capacity=32)


@pytest.fixture(scope="module")
def ts():
    return generate_calibrated(0, CFG.n_nodes, CFG.n_slots, 1.5)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_round_trip():
    names = list_policies()
    for name in ("least-fit", "oversub", "flex-f", "flex-l",
                 "best-fit-usage", "flex-priority"):
        assert name in names
        p = get_policy(name)
        assert p.name == name
        assert hash(p) == hash(get_policy(name))  # usable as static jit arg


def test_registry_unknown_policy():
    with pytest.raises(KeyError, match="registered"):
        get_policy("no-such-policy")


def test_register_custom_factory():
    register_policy("api-test-tight-priority",
                    lambda: PriorityFlexPolicy(headroom=0.3))
    p = get_policy("api-test-tight-priority")
    assert p.headroom == 0.3


def test_resolve_policy_accepts_kind_name_and_object():
    # a policy has one name, its registry name; objects pass through
    p = get_policy("flex-f")
    assert resolve_policy("flex-f") == p
    assert resolve_policy(p) is p
    with pytest.raises(KeyError, match="registered"):
        resolve_policy("flex")


# ---------------------------------------------------------------------------
# Front-end equivalence: the run() entry point == the Experiment path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["least-fit", "oversub", "flex-f",
                                  "flex-l"])
def test_shim_bit_identical(ts, name):
    r_kind = run(ts, CFG, name)
    r_reg = Experiment(ts, CFG, policy=name).run(seeds=0)
    np.testing.assert_array_equal(np.asarray(r_kind.placement),
                                  np.asarray(r_reg.placement))
    np.testing.assert_array_equal(np.asarray(r_kind.metrics.qos),
                                  np.asarray(r_reg.metrics.qos))
    np.testing.assert_array_equal(np.asarray(r_kind.metrics.usage),
                                  np.asarray(r_reg.metrics.usage))


# ---------------------------------------------------------------------------
# Custom user-defined policy end-to-end
# ---------------------------------------------------------------------------

@register_policy("api-test-most-free-mem")
@dataclasses.dataclass(frozen=True)
class MostFreeMemPolicy:
    """Place on the node with the most free estimated memory."""

    name = "api-test-most-free-mem"

    def feasible(self, ctx, task):
        load = admission.usage_load(ctx.node.est_usage, ctx.node.reserved,
                                    ctx.penalty)
        return admission.fits(load, task.request, 1.0)

    def score(self, ctx, task):
        load = admission.usage_load(ctx.node.est_usage, ctx.node.reserved,
                                    ctx.penalty)
        return -load[:, 1]


def test_custom_policy_through_experiment(ts):
    res = Experiment(ts, CFG, policy="api-test-most-free-mem").run(seeds=0)
    pl = np.asarray(res.placement)
    assert ((pl >= -1) & (pl < CFG.n_nodes)).all()
    assert (pl >= 0).sum() > 0
    assert float(jnp.max(res.metrics.usage)) <= 1.0 + 1e-3


def test_new_registry_policies_run(ts):
    for name in ("best-fit-usage", "flex-priority"):
        res = Experiment(ts, CFG, policy=name).run(seeds=0)
        pl = np.asarray(res.placement)
        assert ((pl >= -1) & (pl < CFG.n_nodes)).all()
        assert (pl >= 0).sum() > 0


# ---------------------------------------------------------------------------
# Experiment vmapping: seeds and FlexParams sweeps in one program
# ---------------------------------------------------------------------------

def test_experiment_multi_seed_vmap(ts):
    res = Experiment(ts, CFG, policy="flex-f").run(seeds=[0, 1, 2])
    assert res.metrics.qos.shape == (3, CFG.n_slots)
    assert res.placement.shape == (3, ts.num_tasks)
    # seed 0 row must equal the single-seed run (vmap is just batching)
    single = Experiment(ts, CFG, policy="flex-f").run(seeds=0)
    np.testing.assert_array_equal(np.asarray(res.placement[0]),
                                  np.asarray(single.placement))
    # different seeds must differ somewhere (demand noise differs)
    assert not np.array_equal(np.asarray(res.metrics.usage[0]),
                              np.asarray(res.metrics.usage[1]))


def test_experiment_params_sweep(ts):
    sweep = [FlexParams.default(theta=1.0), FlexParams.default(theta=2.5)]
    res = Experiment(ts, CFG, policy="oversub").run(seeds=[0, 1], sweep=sweep)
    assert res.metrics.qos.shape == (2, 2, CFG.n_slots)
    admitted = (np.asarray(res.placement) >= 0).sum(axis=-1)  # (sweep, seed)
    # more oversubscription admits at least as many tasks
    assert (admitted[1] >= admitted[0]).all()


def test_experiment_estimator_knob(ts):
    res = Experiment(ts, CFG, policy="flex-f", estimator="ewma").run(seeds=0)
    assert res.metrics.qos.shape == (CFG.n_slots,)


def test_estimator_noise_rejected_for_non_current(ts):
    # silently dropping the noise knob would fake a clean-estimator run
    with pytest.raises(ValueError, match="est_noise_std"):
        Experiment(ts, CFG, policy="flex-f", estimator="ewma",
                   est_noise_std=0.5)


def test_sweep_not_nullified_by_pinning_policy(ts):
    # least-fit pins theta for its DEFAULT params, but explicit sweep
    # points must be honoured verbatim or theta studies collapse
    sweep = [FlexParams.default(theta=1.0), FlexParams.default(theta=1.5)]
    res = Experiment(ts, CFG, policy="least-fit").run(seeds=0, sweep=sweep)
    admitted = (np.asarray(res.placement) >= 0).sum(axis=-1)
    assert admitted[1] > admitted[0]


# ---------------------------------------------------------------------------
# Policy behaviour units
# ---------------------------------------------------------------------------

def _ctx(est, penalty=1.0, params=None):
    n = len(est)
    node = NodeState.zeros(n)._replace(
        est_usage=jnp.asarray(est, jnp.float32))
    return PolicyContext(node=node, penalty=jnp.asarray(penalty),
                         params=params or FlexParams.default())


def test_priority_policy_protects_headroom():
    pol = PriorityFlexPolicy(headroom=0.2)
    ctx = _ctx([[0.7, 0.7]])
    req = jnp.asarray([0.2, 0.2])
    batch = TaskView(req, jnp.asarray(0), jnp.asarray(CLASS_BATCH))
    prod = TaskView(req, jnp.asarray(0), jnp.asarray(CLASS_PRODUCTION))
    # 0.7 + 0.2 > 0.8 (batch cap) but <= 1.0 (production cap)
    assert not bool(pol.feasible(ctx, batch)[0])
    assert bool(pol.feasible(ctx, prod)[0])


def test_priority_queue_order_production_first():
    pol = PriorityFlexPolicy()
    reqs = jnp.asarray([[0.1, 0.9], [0.1, 0.2], [0.1, 0.5]], jnp.float32)
    prio = jnp.asarray([CLASS_BATCH, CLASS_PRODUCTION, CLASS_PRODUCTION])
    order = pol.queue_order(reqs, prio, jnp.ones((3,), bool))
    # production tasks first (LRF within class), batch last
    assert order.tolist() == [2, 1, 0]


def test_best_fit_packs_fullest_feasible_node():
    pol = get_policy("best-fit-usage")
    ctx = _ctx([[0.1, 0.1], [0.6, 0.6], [0.95, 0.95]])
    task = TaskView(jnp.asarray([0.2, 0.2]), jnp.asarray(0), jnp.asarray(0))
    _, idx = admission.admit_one(pol, ctx, task, jnp.asarray(True))
    assert int(idx) == 1  # node 2 infeasible, node 1 fullest feasible


# ---------------------------------------------------------------------------
# Simulator / serving engine admission parity (shared core)
# ---------------------------------------------------------------------------

def _parity_case(usage, cap, penalty, declared):
    """Run the SAME admission decision through both substrates."""
    # serving engine side: replicas as single-resource KV nodes
    eng = ServeEngine(EngineConfig(
        n_replicas=len(usage), kv_budget_tokens=cap,
        policy="flex-f", straggler_weight=0.5,
        admission_mode="sequential", admit_batch=8))
    eng._usage_snap = np.asarray(usage, float)
    eng.ctrl = ControllerState(penalty=jnp.asarray(penalty),
                               prev_qos=jnp.asarray(1.0))
    req = Request(rid=0, prompt_len=0, max_tokens=declared,
                  true_tokens=declared)
    eng.submit(req)
    admitted = eng.admit_pending() == 1

    # simulator side: same numbers normalized to unit capacity, both
    # resources equal, no same-source signal (w_src term is zero)
    pol = FlexFifoPolicy()
    est = np.repeat(np.asarray(usage, float)[:, None] / cap, 2, axis=1)
    ctx = _ctx(est, penalty=penalty)
    task = TaskView(jnp.full((2,), declared / cap, jnp.float32),
                    jnp.asarray(0), jnp.asarray(0))
    feas_sim = pol.feasible(ctx, task)
    _, idx = admission.admit_one(pol, ctx, task, jnp.asarray(True))
    return admitted, req.replica, np.asarray(feas_sim), int(idx)


def test_admission_parity_simulator_vs_engine():
    # plenty of room: both admit, same replica, same feasibility mask
    admitted, replica, feas, idx = _parity_case(
        usage=[300.0, 100.0, 500.0], cap=1000, penalty=1.2, declared=200)
    assert admitted and feas.all()
    assert replica == idx == 1

    # tight: some replicas infeasible, still the same choice
    admitted, replica, feas, idx = _parity_case(
        usage=[900.0, 100.0, 750.0], cap=1000, penalty=1.2, declared=200)
    assert admitted
    assert feas.tolist() == [False, True, False]
    assert replica == idx == 1

    # nothing fits under the penalty: both substrates reject
    admitted, replica, feas, idx = _parity_case(
        usage=[900.0, 950.0, 920.0], cap=1000, penalty=1.2, declared=300)
    assert not admitted and not feas.any() and idx == -1
