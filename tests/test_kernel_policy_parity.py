"""Kernel path vs reference path parity across the policy layer.

The contract (docs/kernels.md): for every policy exposing the
``kernel_inputs`` hook, routing admission through the fused Pallas
filter+score kernel must reproduce the reference ``feasible``/``score``
path decision-for-decision.  Verified here at three altitudes — one
``pick_node`` decision, an ``admit_queue`` loop, and whole simulator
runs — with the kernel in interpreter mode so CPU CI runs the real
tiling/masking logic.

The wavefront tests extend the same contract to batched admission
(``admit_queue_wavefront`` / ``SimConfig(admission_mode="wavefront")``):
conflict-round commits must be placement-for-placement identical to the
sequential scan, including on an adversarial queue where every task wants
the same node (one commit per round — the worst case the prefix rule
must survive, docs/kernels.md).  Parity must hold across the whole
tuning grid of ``admit_queue_wavefront``: top-K candidate caching at
every K >= 1 (incl. the K=1 argmax-reduction) and score-bucket dedup
on/off over duplicate-heavy and all-unique queues.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import admission, get_policy, policy_supports_kernel
from repro.core import SimConfig, run
from repro.core.types import FlexParams, NodeState
from repro.kernels import flex_score
from repro.traces import generate_calibrated

pytestmark = pytest.mark.pallas_interpret

KERNEL_POLICIES = ["flex-f", "flex-l", "flex-priority", "best-fit-usage"]
REFERENCE_ONLY = ["least-fit", "oversub"]

CFG = SimConfig(n_nodes=70, n_slots=16, arrivals_per_slot=64,
                retry_capacity=32)
# Small enough that 4 policies x 3 cluster sizes stay CPU-cheap, sized to
# cross the 512-node tile boundary (N=513) per the acceptance criteria.
WAVE_CFG = SimConfig(n_slots=10, arrivals_per_slot=48, retry_capacity=24)


def _node_state(n, key):
    ks = jax.random.split(key, 3)
    return NodeState.zeros(n)._replace(
        est_usage=jax.random.uniform(ks[0], (n, 2)) * 0.7,
        reserved=jax.random.uniform(ks[1], (n, 2)) * 0.1,
        n_tasks=jnp.full((n,), 3, jnp.int32),
        src_count=jax.random.randint(ks[2], (n, 64), 0, 3))


def test_neg_inf_convention_shared():
    # One masking convention across the admission core, the kernel and
    # its reference oracle — docs/kernels.md calls this out as load-bearing.
    from repro.kernels.flex_score import ref
    assert admission.NEG_INF == flex_score.NEG_INF == ref.NEG_INF


def test_capability_flags():
    for name in KERNEL_POLICIES:
        assert policy_supports_kernel(get_policy(name)), name
    for name in REFERENCE_ONLY:
        assert not policy_supports_kernel(get_policy(name)), name


@pytest.mark.parametrize("name", KERNEL_POLICIES + REFERENCE_ONLY)
def test_pick_node_kernel_matches_reference(name):
    # Reference-only policies must silently keep the reference path when
    # use_kernel is requested; kernel policies must agree exactly.
    pol = get_policy(name)
    node = _node_state(100, jax.random.PRNGKey(0))
    ctx = admission.PolicyContext(node=node, penalty=jnp.asarray(1.3),
                                  params=FlexParams.default())
    for prio in (0, 1):
        task = admission.TaskView(jnp.asarray([0.1, 0.12]),
                                  jnp.asarray(5), jnp.asarray(prio))
        i_ref, f_ref = admission.pick_node(pol, ctx, task, use_kernel=False)
        i_ker, f_ker = admission.pick_node(pol, ctx, task, use_kernel=True,
                                           interpret=True)
        assert int(i_ref) == int(i_ker)
        assert bool(f_ref) == bool(f_ker)


@pytest.mark.parametrize("name", KERNEL_POLICIES)
def test_admit_queue_kernel_matches_reference(name):
    pol = get_policy(name)
    params = FlexParams.default()
    node = _node_state(70, jax.random.PRNGKey(2))
    Q = 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    reqs = jax.random.uniform(ks[0], (Q, 2)) * 0.15
    srcs = jax.random.randint(ks[1], (Q,), 0, 64)
    prios = jax.random.randint(ks[2], (Q,), 0, 2)
    valid = jnp.ones((Q,), bool)
    pen = jnp.asarray(1.2)
    _, pl_ref = admission.admit_queue(pol, node, reqs, srcs, prios, valid,
                                      pen, params)
    _, pl_ker = admission.admit_queue(pol, node, reqs, srcs, prios, valid,
                                      pen, params, use_kernel=True,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(pl_ref), np.asarray(pl_ker))


@pytest.mark.parametrize("name", KERNEL_POLICIES)
def test_simulator_kernel_matches_reference(name):
    # Acceptance criterion: whole simulator runs with the kernel-backed
    # path produce the same admissions/utilization as the reference path.
    ts = generate_calibrated(0, CFG.n_nodes, CFG.n_slots, 1.5)
    ref = run(ts, CFG, name)
    ker = run(ts, CFG._replace(use_kernel=True, kernel_interpret=True), name)
    np.testing.assert_array_equal(np.asarray(ref.placement),
                                  np.asarray(ker.placement))
    np.testing.assert_array_equal(np.asarray(ref.admit_slot),
                                  np.asarray(ker.admit_slot))
    np.testing.assert_allclose(np.asarray(ref.metrics.usage),
                               np.asarray(ker.metrics.usage))
    np.testing.assert_allclose(np.asarray(ref.metrics.qos),
                               np.asarray(ker.metrics.qos))


def test_reference_only_policy_runs_with_use_kernel():
    # use_kernel on an RLB policy is a no-op, not an error: the run must
    # equal the plain reference run.
    ts = generate_calibrated(0, CFG.n_nodes, CFG.n_slots, 1.5)
    ref = run(ts, CFG, "least-fit")
    ker = run(ts, CFG._replace(use_kernel=True, kernel_interpret=True),
              "least-fit")
    np.testing.assert_array_equal(np.asarray(ref.placement),
                                  np.asarray(ker.placement))


# ---------------------------------------------------------------------------
# Wavefront batched admission parity
# ---------------------------------------------------------------------------

def _queue(Q, key, n_src=64):
    ks = jax.random.split(key, 3)
    reqs = jax.random.uniform(ks[0], (Q, 2)) * 0.15
    srcs = jax.random.randint(ks[1], (Q,), 0, n_src)
    prios = jax.random.randint(ks[2], (Q,), 0, 2)
    return reqs, srcs, prios


# (topk, dedup_buckets) grid of admit_queue_wavefront: a short K=2 list
# without dedup, K=1 (argmax-reduction), the K=8 default with and without
# dedup.
WAVEFRONT_KNOBS = [(2, 0), (1, 64), (8, 64), (8, 0)]


@pytest.mark.parametrize("name", KERNEL_POLICIES)
@pytest.mark.parametrize("n", [5, 100, 513])
def test_wavefront_queue_matches_sequential(name, n):
    # admit_queue(batch_mode=True) vs the sequential scan: identical
    # placements AND identical final NodeState, including padding entries
    # (valid=False tail) and tasks that find no feasible node — under the
    # default knobs (topk=8 + dedup).
    pol = get_policy(name)
    params = FlexParams.default()
    for seed in range(3):
        node = _node_state(n, jax.random.PRNGKey(seed))
        Q = 48
        reqs, srcs, prios = _queue(Q, jax.random.PRNGKey(seed + 50))
        valid = jnp.arange(Q) < Q - 4
        pen = jnp.asarray(1.2)
        ns_s, pl_s = admission.admit_queue(pol, node, reqs, srcs, prios,
                                           valid, pen, params)
        ns_w, pl_w = admission.admit_queue(pol, node, reqs, srcs, prios,
                                           valid, pen, params,
                                           batch_mode=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(pl_s), np.asarray(pl_w))
        for a, b in zip(ns_s, ns_w):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("topk,dedup", WAVEFRONT_KNOBS)
def test_wavefront_knob_grid_matches_sequential(topk, dedup):
    # Property: every knob combination produces the SAME decisions — the
    # knobs trade sweeps for rounds, never correctness.  One policy, a
    # tile-boundary N, three seeds (the per-policy sweep runs above).
    pol = get_policy("flex-f")
    params = FlexParams.default()
    for seed in range(3):
        node = _node_state(513, jax.random.PRNGKey(seed))
        Q = 48
        reqs, srcs, prios = _queue(Q, jax.random.PRNGKey(seed + 50))
        valid = jnp.arange(Q) < Q - 4
        pen = jnp.asarray(1.2)
        ns_s, pl_s = admission.admit_queue(pol, node, reqs, srcs, prios,
                                           valid, pen, params)
        ns_w, pl_w = admission.admit_queue_wavefront(
            pol, node, reqs, srcs, prios, valid, pen, params,
            interpret=True, topk=topk, dedup_buckets=dedup)
        np.testing.assert_array_equal(np.asarray(pl_s), np.asarray(pl_w))
        for a, b in zip(ns_s, ns_w):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_wavefront_fat_tie_margin_matches_sequential():
    # A margin 1000x the default over-flags ties: more rounds and
    # re-sweeps, never a different decision.
    pol = get_policy("flex-f")
    params = FlexParams.default()
    for seed in range(3):
        node = _node_state(513, jax.random.PRNGKey(seed))
        Q = 48
        reqs, srcs, prios = _queue(Q, jax.random.PRNGKey(seed + 50))
        valid = jnp.arange(Q) < Q - 4
        pen = jnp.asarray(1.2)
        ns_s, pl_s = admission.admit_queue(pol, node, reqs, srcs, prios,
                                           valid, pen, params)
        ns_w, pl_w = admission.admit_queue_wavefront(
            pol, node, reqs, srcs, prios, valid, pen, params,
            interpret=True, tie_margin=1e-2)
        np.testing.assert_array_equal(np.asarray(pl_s), np.asarray(pl_w))
        for a, b in zip(ns_s, ns_w):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dup_heavy", [True, False])
def test_wavefront_dedup_queue_regimes(dup_heavy):
    # Score-bucket dedup on a duplicate-heavy queue (4 shapes x 3
    # sources = 12 distinct rows << dedup_buckets: the compacted kernel
    # branch) and an all-unique queue wider than the bucket budget (the
    # full-width fallback branch) — decisions identical to the sequential
    # scan and to the dedup-off wavefront in both regimes.
    pol = get_policy("flex-f")
    params = FlexParams.default()
    Q = 48
    node = _node_state(100, jax.random.PRNGKey(7))
    if dup_heavy:
        shapes = jax.random.uniform(jax.random.PRNGKey(1), (4, 2)) * 0.15
        reqs = shapes[jnp.arange(Q) % 4]
        srcs = (jnp.arange(Q, dtype=jnp.int32) // 4) % 3
        dedup = 16   # 12 distinct rows fit: dedup branch taken
    else:
        reqs, srcs, _ = _queue(Q, jax.random.PRNGKey(2))
        dedup = 16   # 48 distinct rows overflow: full-width fallback
    prios = jnp.zeros((Q,), jnp.int32)
    valid = jnp.ones((Q,), bool)
    pen = jnp.asarray(1.2)
    ns_s, pl_s = admission.admit_queue(pol, node, reqs, srcs, prios, valid,
                                       pen, params)
    ns_w, pl_w = admission.admit_queue_wavefront(
        pol, node, reqs, srcs, prios, valid, pen, params, interpret=True,
        dedup_buckets=dedup)
    ns_o, pl_o = admission.admit_queue_wavefront(
        pol, node, reqs, srcs, prios, valid, pen, params, interpret=True,
        dedup_buckets=0)
    np.testing.assert_array_equal(np.asarray(pl_s), np.asarray(pl_w))
    np.testing.assert_array_equal(np.asarray(pl_s), np.asarray(pl_o))
    for a, b in zip(ns_s, ns_w):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", KERNEL_POLICIES)
@pytest.mark.parametrize("topk", [1, 8])
def test_wavefront_adversarial_single_hot_node(name, topk):
    # Every task from the same source, one node far emptier than the rest:
    # all pending tasks pick that node, so the dup rule admits one task at
    # a time until the node fills.  This is the degenerate case where the
    # naive "commit unless an earlier task picked the same node" shortcut
    # would still work by accident — but the decisions must match the
    # sequential scan exactly, commit order included.  With K=8 cached
    # candidates the hot node goes dirty after the first commit and the
    # dirty-refresh keeps deciding it EXACTLY without re-sweeping; with
    # K=1 a commit leaves the other tasks no clean candidate, so the head
    # re-sweeps — at most once per commit.
    pol = get_policy(name)
    params = FlexParams.default()
    n, Q = 33, 24
    node = NodeState.zeros(n)._replace(
        est_usage=jnp.full((n, 2), 0.55).at[7].set(0.0),
        n_tasks=jnp.full((n,), 2, jnp.int32))
    reqs = jnp.full((Q, 2), 0.12)
    srcs = jnp.full((Q,), 3, jnp.int32)
    prios = jnp.zeros((Q,), jnp.int32)
    valid = jnp.ones((Q,), bool)
    pen = jnp.asarray(1.0)
    ns_s, pl_s = admission.admit_queue(pol, node, reqs, srcs, prios, valid,
                                       pen, params)
    ns_w, pl_w, rounds, sweeps = admission.admit_queue_wavefront(
        pol, node, reqs, srcs, prios, valid, pen, params, interpret=True,
        topk=topk, with_rounds=True)
    np.testing.assert_array_equal(np.asarray(pl_s), np.asarray(pl_w))
    for a, b in zip(ns_s, ns_w):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    placed = int((pl_w >= 0).sum())
    assert placed > 0
    if topk == 1:
        # identical tasks => identical candidates => ~one commit per
        # round; every epoch commits, so sweeps never exceed commits
        assert int(rounds) >= placed
        assert int(sweeps) <= placed
    else:
        # candidate fallback: hot-node contention resolves from the cache
        assert int(sweeps) < int(rounds)
        assert int(sweeps) <= placed // 4 + 1


@pytest.mark.parametrize("topk,expect_rounds", [(1, 0), (8, 0)])
def test_wavefront_all_infeasible_finalizes_in_one_sweep(topk,
                                                         expect_rounds):
    # No feasible node for anyone: every task finalizes -1 off the FIRST
    # sweep (feasibility is antitone in load, docs/kernels.md), at the
    # epoch head, for any K: no commit round is ever entered.
    pol = get_policy("flex-f")
    params = FlexParams.default()
    n, Q = 70, 16
    node = NodeState.zeros(n)._replace(est_usage=jnp.full((n, 2), 0.99))
    reqs = jnp.full((Q, 2), 0.5)
    valid = jnp.ones((Q,), bool)
    zeros = jnp.zeros((Q,), jnp.int32)
    ns_w, pl_w, rounds, sweeps = admission.admit_queue_wavefront(
        pol, node, reqs, zeros, zeros, valid, jnp.asarray(1.0), params,
        interpret=True, topk=topk, with_rounds=True)
    assert (np.asarray(pl_w) == -1).all()
    assert int(sweeps) == 1
    assert int(rounds) == expect_rounds
    np.testing.assert_array_equal(np.asarray(ns_w.reserved),
                                  np.asarray(node.reserved))


@pytest.mark.parametrize("name", KERNEL_POLICIES)
@pytest.mark.parametrize("n", [5, 100, 513])
def test_simulator_wavefront_matches_sequential(name, n):
    # Acceptance criterion: SimConfig(admission_mode="wavefront") is
    # decision-for-decision identical to the sequential scan at simulator
    # level — placements, admit slots and the rejection counter.
    cfg = WAVE_CFG._replace(n_nodes=n)
    ts = generate_calibrated(0, cfg.n_nodes, cfg.n_slots, 1.5)
    ref = run(ts, cfg, name)
    wav = run(ts, cfg._replace(admission_mode="wavefront",
                               kernel_interpret=True), name)
    np.testing.assert_array_equal(np.asarray(ref.placement),
                                  np.asarray(wav.placement))
    np.testing.assert_array_equal(np.asarray(ref.admit_slot),
                                  np.asarray(wav.admit_slot))
    np.testing.assert_array_equal(np.asarray(ref.metrics.n_rejected),
                                  np.asarray(wav.metrics.n_rejected))
    np.testing.assert_allclose(np.asarray(ref.metrics.usage),
                               np.asarray(wav.metrics.usage))


def test_wavefront_rejects_topk_below_one():
    # K=0 caches no candidate: there is no loop to run, and asking for one
    # is an error, not a silent fallback.
    pol = get_policy("flex-f")
    node = NodeState.zeros(5)
    Q = 4
    zeros = jnp.zeros((Q,), jnp.int32)
    with pytest.raises(ValueError, match="topk"):
        admission.admit_queue_wavefront(
            pol, node, jnp.full((Q, 2), 0.1), zeros, zeros,
            jnp.ones((Q,), bool), jnp.asarray(1.0), FlexParams.default(),
            interpret=True, topk=0)


def test_wavefront_reference_only_policy_falls_back():
    # admission_mode="wavefront" with a policy lacking kernel_inputs keeps
    # the sequential scan silently — same contract as use_kernel.
    ts = generate_calibrated(0, CFG.n_nodes, CFG.n_slots, 1.5)
    ref = run(ts, CFG, "least-fit")
    wav = run(ts, CFG._replace(admission_mode="wavefront"), "least-fit")
    np.testing.assert_array_equal(np.asarray(ref.placement),
                                  np.asarray(wav.placement))


def test_unknown_admission_mode_raises():
    ts = generate_calibrated(0, 5, 4, 1.0)
    cfg = SimConfig(n_nodes=5, n_slots=4, arrivals_per_slot=8,
                    retry_capacity=4, admission_mode="wavefart")
    with pytest.raises(ValueError, match="admission_mode"):
        run(ts, cfg, "flex-f")
