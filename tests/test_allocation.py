"""WFS allocator (paper §3): the three cases + conservation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import allocation, wfs_allocate


def alloc(demand, request, placement=None, n_nodes=1):
    demand = jnp.asarray(demand, jnp.float32)
    request = jnp.asarray(request, jnp.float32)
    T = demand.shape[0]
    pl = (jnp.zeros((T,), jnp.int32) if placement is None
          else jnp.asarray(placement, jnp.int32))
    active = jnp.ones((T,), bool)
    a, node_usage = wfs_allocate(demand, request, pl, active, n_nodes)
    return np.asarray(a), np.asarray(node_usage)


def test_case1_demand_fits():
    d = [[0.2, 0.1], [0.3, 0.2]]
    r = [[0.5, 0.5], [0.1, 0.1]]
    a, u = alloc(d, r)
    np.testing.assert_allclose(a, d, atol=1e-5)


def test_case2_requests_guaranteed():
    # total demand > C, total request <= C: everyone gets min(d, r), the
    # leftover splits by weighted fair share
    d = [[0.8, 0.1], [0.7, 0.1]]
    r = [[0.4, 0.2], [0.4, 0.2]]
    a, u = alloc(d, r)
    assert (a[:, 0] >= 0.4 - 1e-5).all()
    assert u[0, 0] <= 1.0 + 1e-5
    # symmetric tasks -> equal split of the excess
    np.testing.assert_allclose(a[0], a[1], atol=1e-4)


def test_case3_oversubscribed_requests():
    d = [[0.9, 0.1], [0.9, 0.1], [0.9, 0.1]]
    r = [[0.6, 0.2], [0.6, 0.2], [0.6, 0.2]]
    a, u = alloc(d, r)
    assert u[0, 0] <= 1.0 + 1e-4          # capacity respected
    assert u[0, 0] >= 1.0 - 1e-3          # fully used (demand saturates)
    np.testing.assert_allclose(a[:, 0], a[0, 0], atol=1e-4)


def test_never_exceeds_demand():
    rng = np.random.default_rng(0)
    d = rng.uniform(0, 0.5, (20, 2)).astype(np.float32)
    r = rng.uniform(0, 0.5, (20, 2)).astype(np.float32)
    pl = rng.integers(0, 4, 20)
    a, u = alloc(d, r, pl, n_nodes=4)
    assert (a <= d + 1e-5).all()
    assert (u <= 1.0 + 1e-4).all()


def test_inactive_get_nothing():
    d = jnp.asarray([[0.5, 0.5], [0.5, 0.5]], jnp.float32)
    r = d
    a, u = wfs_allocate(d, r, jnp.asarray([0, 0], jnp.int32),
                        jnp.asarray([True, False]), 1)
    assert float(a[1].sum()) == 0.0


# --- active-set compaction: every rung gives the full T-row bits ----------

LADDER_T, LADDER_N = 8192, 64


def _ladder_case(n_active, capacity, seed=0):
    """An oversubscribed slot of LADDER_T tasks, n_active of them running."""
    rng = np.random.default_rng(seed)
    d = jnp.asarray(rng.uniform(0, 0.4, (LADDER_T, 2)), jnp.float32)
    r = jnp.asarray(rng.uniform(0, 0.3, (LADDER_T, 2)), jnp.float32)
    pl = jnp.asarray(rng.integers(-1, LADDER_N, LADDER_T), jnp.int32)
    act = np.zeros(LADDER_T, bool)
    act[rng.choice(LADDER_T, n_active, replace=False)] = True
    if capacity == "table":   # per-node (N, R) capacity, some nodes down
        cap = rng.uniform(0.3, 1.0, (LADDER_N, 2)).astype(np.float32)
        cap[rng.choice(LADDER_N, 8, replace=False)] = 0.0
        capacity = jnp.asarray(cap)
    return d, r, pl, jnp.asarray(act), capacity


def test_ladder_widths():
    assert allocation._ladder(LADDER_T) == (1024, 2048, 4096)
    assert allocation._ladder(584964) == (9216, 18432, 36864, 73728, 146432,
                                          292864)
    assert allocation._ladder(1024) == ()


@pytest.mark.parametrize("n_active,rows,capacity", [
    (0, 1024, 1.0),
    (1, 1024, 1.0),
    (1024, 1024, 1.0),        # at a rung's width
    (1025, 2048, 1.0),        # one over it
    (2048, 2048, 1.0),
    (2049, 4096, 1.0),
    (4096, 4096, 1.0),        # the widest compact rung
    (4097, LADDER_T, 1.0),    # above it: the full path
    (LADDER_T, LADDER_T, 1.0),
    (0, 1024, "table"),
    (700, 1024, "table"),
    (3000, 4096, "table"),
    (6000, LADDER_T, "table"),
])
def test_compacted_equals_full_rows(n_active, rows, capacity):
    d, r, pl, act, cap = _ladder_case(n_active, capacity)
    a0, u0 = wfs_allocate(d, r, pl, act, LADDER_N, cap)
    a1, u1, ran = jax.jit(allocation._wfs_compacted, static_argnums=4)(
        d, r, pl, act, LADDER_N, cap)
    assert int(ran) == rows
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a0))
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u0))


def test_compacted_vmap_takes_the_widest_rung():
    cases = [_ladder_case(n, 1.0, seed=n) for n in (300, 1500, 3500)]
    d, r, pl = cases[0][:3]
    acts = jnp.stack([c[3] for c in cases])
    a1, u1, ran = jax.vmap(
        lambda a: allocation._wfs_compacted(d, r, pl, a, LADDER_N))(acts)
    assert np.asarray(ran).tolist() == [4096] * 3
    for i, act in enumerate(acts):
        a0, u0 = wfs_allocate(d, r, pl, act, LADDER_N)
        np.testing.assert_array_equal(np.asarray(a1[i]), np.asarray(a0))
        np.testing.assert_array_equal(np.asarray(u1[i]), np.asarray(u0))
