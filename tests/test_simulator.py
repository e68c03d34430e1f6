"""Simulator invariants + scheduler-differentiation system behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Experiment
from repro.core import SimConfig, allocation, run
from repro.traces import analysis, generate_calibrated

CFG = SimConfig(n_nodes=60, n_slots=32, arrivals_per_slot=256,
                retry_capacity=64, record_node_usage=True)


@pytest.fixture(scope="module")
def ts():
    return generate_calibrated(0, CFG.n_nodes, CFG.n_slots, 1.5)


@pytest.fixture(scope="module")
def results(ts):
    return {k: run(ts, CFG, k) for k in
            ("least-fit", "oversub", "flex-f", "flex-l")}


def test_node_capacity_never_exceeded(results):
    for res in results.values():
        assert float(jnp.max(res.metrics.node_usage)) <= 1.0 + 1e-3


def test_placements_valid(results, ts):
    for res in results.values():
        pl = np.asarray(res.placement)
        assert ((pl >= -1) & (pl < CFG.n_nodes)).all()
        adm = np.asarray(res.admit_slot)
        arr = np.asarray(ts.arrival)
        placed = pl >= 0
        assert (adm[placed] >= arr[placed]).all()


def test_flex_beats_leastfit_utilization(results, ts):
    s_lf = analysis.summarize(ts, results["least-fit"], 0.99)
    s_ff = analysis.summarize(ts, results["flex-f"], 0.99)
    assert s_ff["avg_usage_cpu"] > 1.2 * s_lf["avg_usage_cpu"]
    assert s_ff["n_admitted"] > s_lf["n_admitted"]


def test_flex_qos_beats_oversub(results):
    q_flex = float(jnp.mean(results["flex-f"].metrics.qos))
    q_over = float(jnp.mean(results["oversub"].metrics.qos))
    assert q_flex >= q_over
    assert q_flex >= 0.985


def test_penalty_reacts_to_noise(ts):
    res = run(ts, CFG, "flex-f", est_noise_std=0.6)
    p = np.asarray(res.metrics.penalty)
    assert p.max() > 1.5  # controller backed off at least once


def test_deterministic(ts):
    r1 = run(ts, CFG, "flex-f", seed=7)
    r2 = run(ts, CFG, "flex-f", seed=7)
    np.testing.assert_array_equal(np.asarray(r1.placement),
                                  np.asarray(r2.placement))


# A cluster whose task count gives the allocator a ladder of several rungs
# (1024, 2048, 3072 under T = 5573) and a running set that climbs from the
# first rung to the second within the study.
LADDER_CFG = SimConfig(n_nodes=160, n_slots=32, arrivals_per_slot=512,
                       retry_capacity=64, record_node_usage=True)


@pytest.fixture(scope="module")
def ladder_exp():
    ts = generate_calibrated(0, LADDER_CFG.n_nodes, LADDER_CFG.n_slots, 1.5)
    return Experiment(ts, LADDER_CFG, "flex-f")


def test_vmapped_seeds_equal_single_runs(ladder_exp):
    seeds = (3, 4)
    both = ladder_exp.run(seeds=list(seeds))
    singles = [ladder_exp.run(seeds=s) for s in seeds]
    rows = np.asarray(both.metrics.alloc_rows)
    # batched, every member runs on the widest rung any of them needs, and
    # the admission loop runs the largest ready count of the batch
    for name in ("alloc_rows", "admit_steps"):
        got = np.asarray(getattr(both.metrics, name))
        np.testing.assert_array_equal(
            got, np.broadcast_to(np.max([np.asarray(getattr(s.metrics, name))
                                         for s in singles], axis=0),
                                 got.shape))
    batch_wide = dict(alloc_rows=None, admit_steps=None)
    for i, one in enumerate(singles):
        pairs = zip(jax.tree.leaves(both._replace(
                        metrics=both.metrics._replace(**batch_wide))),
                    jax.tree.leaves(one._replace(
                        metrics=one.metrics._replace(**batch_wide))))
        for b, o in pairs:
            b, o = np.asarray(b)[i], np.asarray(o)
            if np.issubdtype(o.dtype, np.floating):
                # XLA orders a batched float reduction differently: the
                # per-node and cluster usage series differ in the last bit
                # between batched and single runs, the full-width
                # allocator's too
                np.testing.assert_allclose(b, o, rtol=1e-6, atol=1e-7)
            else:
                np.testing.assert_array_equal(b, o)
        for name in ("qos", "penalty"):   # the controller's series: exact
            np.testing.assert_array_equal(
                np.asarray(getattr(both.metrics, name))[i],
                np.asarray(getattr(one.metrics, name)))
        run_n = np.asarray(one.metrics.n_running)
        ran = np.asarray(one.metrics.alloc_rows)
        assert (ran >= run_n).all()
        assert set(ran.tolist()) == {1024, 2048}
    assert (rows >= np.asarray(both.metrics.n_running)).all()


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(sub, "consts") and hasattr(sub, "jaxpr"):
                    yield from _eqns(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_vmapped_ladder_is_one_cond(ladder_exp):
    n_rungs = len(allocation._ladder(ladder_exp.trace.arrival.shape[0])) + 1
    assert n_rungs == 4
    keys = jnp.stack([jax.random.PRNGKey(s) for s in (0, 1)])
    jx = jax.make_jaxpr(jax.vmap(ladder_exp._one, in_axes=(None, 0)))(
        ladder_exp.params, keys)
    eqns = list(_eqns(jx.jaxpr))
    ladders = [e for e in eqns if e.primitive.name == "cond"
               and len(e.params["branches"]) == n_rungs]
    assert len(ladders) == 1
    assert ladders[0].invars[0].aval.shape == ()   # one rung for the batch
    assert not [e for e in eqns if e.primitive.name == "select_n"
                and len(e.invars) - 1 == n_rungs]
