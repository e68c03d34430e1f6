"""The sequential admission path runs one loop step per ready queue entry.

``admit_queue`` moves the ready entries of a padded queue to the front, in
queue order, and runs ``admit_one`` on those alone.  A padded or deferred
entry adds ``0 * request`` and changes nothing, so the decisions and the
final ``NodeState`` must equal a scan of ``admit_one`` over every entry,
bit for bit: the reference below.  Under ``vmap`` the batch runs its
largest ready count, and a member's surplus steps are no-ops.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import admission, get_policy
from repro.api.protocols import policy_prepare_params
from repro.core import SimConfig, run
from repro.core.types import FlexParams, NodeState
from repro.traces import generate_calibrated

N, Q = 40, 48


def full_scan(policy, node, requests, srcs, priorities, valid, penalty,
              params, use_kernel=False, interpret=False):
    """``admit_one`` on every queue entry, ready or not."""

    def step(ns, xs):
        r, src, prio, ok = xs
        ctx = admission.PolicyContext(node=ns, penalty=penalty,
                                      params=params)
        return admission.admit_one(policy, ctx,
                                   admission.TaskView(r, src, prio), ok,
                                   use_kernel=use_kernel,
                                   interpret=interpret)

    return jax.lax.scan(step, node, (requests, srcs, priorities, valid))


def _node(key):
    ks = jax.random.split(key, 3)
    return NodeState.zeros(N)._replace(
        est_usage=jax.random.uniform(ks[0], (N, 2)) * 0.6,
        reserved=jax.random.uniform(ks[1], (N, 2)) * 0.1,
        n_tasks=jnp.full((N,), 2, jnp.int32),
        src_count=jax.random.randint(ks[2], (N, 64), 0, 3))


def _queue(key):
    ks = jax.random.split(key, 3)
    requests = jax.random.uniform(ks[0], (Q, 2), minval=0.02, maxval=0.25)
    srcs = jax.random.randint(ks[1], (Q,), 0, 64)
    priorities = jax.random.randint(ks[2], (Q,), 0, 3)
    return requests, srcs, priorities


def _valid(kind, key):
    if kind == "all":
        return jnp.ones((Q,), bool)
    if kind == "none":
        return jnp.zeros((Q,), bool)
    if kind == "head":          # retries then arrivals, padded at the tail
        return jnp.arange(Q) < 29
    # backoff-style: deferred entries leave holes in the middle
    return jax.random.bernoulli(key, 0.45, (Q,)).at[0].set(False)


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


CASES = [("flex-f", "all", False), ("flex-f", "none", False),
         ("flex-f", "head", False), ("flex-f", "holes", False),
         ("least-fit", "holes", False), ("flex-l", "holes", False),
         ("flex-f", "holes", True)]


@pytest.mark.parametrize("name,kind,use_kernel", CASES)
def test_compacted_equals_full_scan(name, kind, use_kernel):
    """Decisions and NodeState equal the full-width scan; the loop runs
    one step per ready entry.  FlexL's queue order is applied before the
    call, as the simulator does; the kernel runs through the
    interpreter."""
    policy = get_policy(name)
    params = policy_prepare_params(policy, FlexParams.default())
    k_node, k_queue, k_valid = jax.random.split(jax.random.PRNGKey(7), 3)
    node = _node(k_node)
    requests, srcs, priorities = _queue(k_queue)
    valid = _valid(kind, k_valid)
    order = getattr(policy, "queue_order", None)
    if order is not None:
        perm = order(requests, priorities, valid)
        requests, srcs, priorities, valid = (
            requests[perm], srcs[perm], priorities[perm], valid[perm])
    penalty = jnp.float32(1.2)
    flags = dict(use_kernel=use_kernel, interpret=use_kernel)
    got_node, got = admission.admit_queue(
        policy, node, requests, srcs, priorities, valid, penalty, params,
        **flags)
    steps = admission.loop_steps(policy, valid)
    want_node, want = full_scan(policy, node, requests, srcs, priorities,
                                valid, penalty, params, **flags)
    _assert_same((got_node, got), (want_node, want))
    assert int(steps) == int(jnp.sum(valid))
    assert (np.asarray(got)[~np.asarray(valid)] == -1).all()
    if kind in ("all", "holes"):
        assert (np.asarray(got) >= 0).any()   # the queue placed work


@pytest.mark.parametrize("name,batch_mode,want", [
    ("flex-f", False, 29), ("flex-f", True, 0), ("least-fit", True, 29)])
def test_loop_steps_follow_the_shape(name, batch_mode, want):
    """The wavefront shape runs no loop; a policy without the kernel
    hook falls back to the sequential loop even in batch mode."""
    valid = _valid("head", None)
    steps = admission.loop_steps(get_policy(name), valid,
                                 batch_mode=batch_mode)
    assert int(steps) == want


def test_vmapped_members_run_the_largest_ready_count():
    """A batch whose members hold 0, 11 and 37 ready entries: each
    member's decisions equal its own full scan, and the loop runs 37
    steps for all of them."""
    policy = get_policy("flex-f")
    params = FlexParams.default()
    node = _node(jax.random.PRNGKey(1))
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    queues = [_queue(k) for k in keys]
    counts = (0, 11, 37)
    valids = [jax.random.permutation(k, jnp.arange(Q) < c)
              for k, c in zip(keys, counts)]
    requests, srcs, priorities = (jnp.stack(x) for x in zip(*queues))
    valid = jnp.stack(valids)
    penalty = jnp.asarray([1.0, 1.2, 1.5], jnp.float32)

    def admit(r, s, p, v, pen):
        return (*admission.admit_queue(policy, node, r, s, p, v, pen,
                                       params),
                admission.loop_steps(policy, v))

    b_node, b_placed, b_steps = jax.vmap(admit)(requests, srcs, priorities,
                                                valid, penalty)
    assert np.asarray(b_steps).tolist() == [max(counts)] * len(counts)
    for i in range(len(counts)):
        want = full_scan(policy, node, requests[i], srcs[i], priorities[i],
                         valid[i], penalty[i], params)
        got = jax.tree.map(lambda x: x[i], (b_node, b_placed))
        _assert_same(got, want)


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(sub, "consts") and hasattr(sub, "jaxpr"):
                    yield from _eqns(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_vmapped_loop_keeps_one_while():
    """Batched, the loop's trip count is one scalar: the program holds a
    single ``while`` whose step count is the batch's largest, and no
    per-step ``select`` over the node state."""
    policy = get_policy("flex-f")
    params = FlexParams.default()
    node = _node(jax.random.PRNGKey(1))
    requests, srcs, priorities = _queue(jax.random.PRNGKey(3))
    valid = jnp.stack([jnp.arange(Q) < 5, jnp.arange(Q) < 9])

    def admit(v):
        return admission.admit_queue(policy, node, requests, srcs,
                                     priorities, v, jnp.float32(1.0), params)

    eqns = list(_eqns(jax.make_jaxpr(jax.vmap(admit))(valid).jaxpr))
    whiles = [e for e in eqns if e.primitive.name == "while"]
    assert len(whiles) == 1
    assert whiles[0].params["cond_jaxpr"].out_avals[0].shape == ()
    assert not [e for e in eqns if e.primitive.name == "select_n"
                and e.outvars[0].aval.shape[-2:] == (N, 64)]


SIM_CFG = SimConfig(n_nodes=37, n_slots=12, arrivals_per_slot=96,
                    retry_capacity=24)


@pytest.fixture(scope="module")
def sim_trace():
    return generate_calibrated(0, SIM_CFG.n_nodes, SIM_CFG.n_slots, 1.8)


@pytest.mark.parametrize("policy,backoff", [("flex-f", 0), ("flex-f", 2),
                                            ("flex-l", 0)])
def test_simulate_steps_equal_ready_entries(monkeypatch, sim_trace, policy,
                                            backoff):
    """``SlotMetrics.admit_steps`` is the slot's ready count: the queue
    entries handed to primary admission, read here by a spy on
    ``admit_queue`` (retry backoff leaves deferred entries out)."""
    seen = []
    real = admission.admit_queue

    def spy(pol, node, requests, srcs, priorities, valid, *args, **kw):
        jax.debug.callback(lambda v: seen.append(int(v)),
                           jnp.sum(valid, dtype=jnp.int32), ordered=True)
        return real(pol, node, requests, srcs, priorities, valid, *args,
                    **kw)

    monkeypatch.setattr(admission, "admit_queue", spy)
    # a configuration no other test compiles, so the spy is traced in
    res = run(sim_trace, SIM_CFG._replace(retry_backoff=backoff), policy)
    steps = np.asarray(res.metrics.admit_steps)
    assert steps.tolist() == seen
    assert steps.shape == (SIM_CFG.n_slots,) and steps.max() > 0
    assert (steps <= SIM_CFG.retry_capacity
            + SIM_CFG.arrivals_per_slot).all()
