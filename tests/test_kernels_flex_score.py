"""flex_score kernel vs reference across load regimes, incl. no-fit.

``interpret=True`` runs the REAL Pallas kernel logic (tiling, padding,
tail masking, cross-tile reduction) through the Pallas interpreter, so
these parity tests exercise the kernel path on CPU CI (docs/kernels.md).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flex_score.ops import (flex_pick_node,
                                          flex_pick_node_batch,
                                          flex_pick_node_batch_topk)
from repro.kernels.flex_score.ref import (pick_node_batch_ref,
                                          pick_node_batch_topk_ref,
                                          pick_node_ref)

pytestmark = pytest.mark.pallas_interpret


def _rand_state(N, scale, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    est = jax.random.uniform(ks[0], (N, 2)) * scale
    res = jax.random.uniform(ks[1], (N, 2)) * 0.05
    src = jax.random.uniform(ks[2], (N,))
    return est, res, src


def _assert_matches(N, tile, scale, **kw):
    est, res, src = _rand_state(N, scale)
    r = jnp.asarray([0.08, 0.1])
    for P in (1.0, 2.0):
        i_k, s_k, f_k = flex_pick_node(est, res, src, r, P, tile=tile,
                                       interpret=True, **kw)
        i_r, s_r, f_r = pick_node_ref(est, res, src, r, P, 1.0, 0.25, **kw)
        assert bool(f_k) == bool(f_r)
        if bool(f_r):
            assert int(i_k) == int(i_r)
            assert abs(float(s_k) - float(s_r)) < 1e-5
        else:
            assert int(i_k) == -1


@pytest.mark.parametrize("N,tile", [(256, 128), (1024, 256), (512, 512)])
@pytest.mark.parametrize("scale", [0.2, 0.8, 3.0])
def test_matches_ref(N, tile, scale):
    _assert_matches(N, tile, scale)


@pytest.mark.parametrize("N", [5, 100, 513])
@pytest.mark.parametrize("scale", [0.2, 0.8, 3.0])
def test_non_tile_multiple_matches_ref(N, scale):
    # N not a multiple of the tile: the wrapper zero-pads the node table
    # and the kernel masks the tail rows (no reference-path fallback).
    _assert_matches(N, 128, scale)
    _assert_matches(N, 512, scale)


@pytest.mark.parametrize("N,tile", [(256, 128), (513, 512)])
def test_all_infeasible_returns_minus_one(N, tile):
    # N=513/tile=512 covers the padding trap: zero-padded tail rows have
    # zero load and WOULD be feasible if the in-kernel row mask failed.
    est = jnp.ones((N, 2)) * 0.99
    i, s, f = flex_pick_node(est, jnp.zeros((N, 2)), jnp.zeros((N,)),
                             jnp.asarray([0.5, 0.5]), 1.0, tile=tile,
                             interpret=True)
    assert int(i) == -1 and not bool(f)


def _rand_batch(N, Q, scale, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    est = jax.random.uniform(ks[0], (N, 2)) * scale
    res = jax.random.uniform(ks[1], (N, 2)) * 0.05
    src = jax.random.uniform(ks[2], (Q, N))
    r = jax.random.uniform(ks[3], (Q, 2)) * 0.2
    return est, res, src, r


@pytest.mark.parametrize("N", [5, 100, 513, 1024])
@pytest.mark.parametrize("Q", [1, 7, 33])
def test_batch_matches_batch_ref(N, Q):
    # Batched Pallas (tiling + Q-padding + masked tail) vs the batched
    # einsum oracle: same winner and feasibility row for row.  Q=7/33
    # exercise the sublane padding (Q not a multiple of 8).
    est, res, src, r = _rand_batch(N, Q, 0.8)
    pen = jnp.full((Q,), 1.3)
    ones = jnp.ones((Q,))
    i_k, _, f_k = flex_pick_node_batch(est, res, src, r, pen, w_load=ones,
                                       w_src=ones * 0.25, cap=ones,
                                       tile=128, interpret=True)
    i_r, _, f_r = pick_node_batch_ref(est, res, src, r, pen, ones,
                                      ones * 0.25, cap=ones)
    assert (jnp.asarray(i_k) == jnp.asarray(i_r)).all()
    assert (jnp.asarray(f_k) == jnp.asarray(f_r)).all()


@pytest.mark.parametrize("scale", [0.2, 0.8, 3.0])
def test_batch_rows_match_per_task_kernel(scale):
    # Each row of the batched kernel must be the per-task kernel's answer
    # for that task — same argmax AND bit-identical best score (identical
    # float expressions, docs/kernels.md).
    N, Q = 513, 9
    est, res, src, r = _rand_batch(N, Q, scale)
    pen = 1.3
    i_b, s_b, f_b = flex_pick_node_batch(est, res, src, r, pen, w_load=1.0,
                                         w_src=0.25, cap=1.0, tile=128,
                                         interpret=True)
    for q in range(Q):
        i_1, s_1, f_1 = flex_pick_node(est, res, src[q], r[q], pen,
                                       tile=128, interpret=True)
        assert int(i_1) == int(i_b[q])
        assert bool(f_1) == bool(f_b[q])
        if bool(f_1):
            assert float(s_1) == float(s_b[q])


def test_batch_per_task_scalars():
    # penalty/cap/w_load/w_src vary per ROW of the packed task matrix: each
    # row must match a per-task call with those scalars.
    N, Q = 100, 6
    est, res, src, r = _rand_batch(N, Q, 0.8)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    pen = 1.0 + jax.random.uniform(ks[0], (Q,))
    cap = 0.7 + 0.3 * jax.random.uniform(ks[1], (Q,))
    w_load = jnp.where(jnp.arange(Q) % 2 == 0, 1.0, -1.0)  # incl. best-fit
    w_src = 0.25 * jax.random.uniform(ks[3], (Q,))
    i_b, _, f_b = flex_pick_node_batch(est, res, src, r, pen, w_load=w_load,
                                       w_src=w_src, cap=cap, tile=128,
                                       interpret=True)
    for q in range(Q):
        i_1, _, f_1 = flex_pick_node(est, res, src[q], r[q], pen[q],
                                     w_load=w_load[q], w_src=w_src[q],
                                     cap=cap[q], tile=128, interpret=True)
        assert int(i_1) == int(i_b[q])
        assert bool(f_1) == bool(f_b[q])


def test_batch_all_infeasible_rows():
    # Mixed queue: infeasible rows return -1 without disturbing feasible
    # ones; the zero-padded tail (N=513, tile=512) must never win.
    N, Q = 513, 8
    est = jnp.ones((N, 2)) * 0.99
    src = jnp.zeros((Q, N))
    r = jnp.where(jnp.arange(Q)[:, None] % 2 == 0, 0.5,
                  0.005) * jnp.ones((Q, 2))
    i_b, _, f_b = flex_pick_node_batch(est, jnp.zeros((N, 2)), src, r, 1.0,
                                       w_load=1.0, w_src=0.25, cap=1.0,
                                       tile=512, interpret=True)
    for q in range(Q):
        if q % 2 == 0:
            assert int(i_b[q]) == -1 and not bool(f_b[q])
        else:
            assert 0 <= int(i_b[q]) < N and bool(f_b[q])


@pytest.mark.parametrize("N", [5, 100, 513, 1024])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_topk_matches_topk_ref(N, k):
    # Tile-wise peel + cross-tile merge vs the full-table lax.top_k
    # oracle: identical candidate NODE lists column for column (scores
    # agree to fusion-ULP tolerance), including non-tile-multiple N and
    # k > per-tile feasible counts.
    Q = 9
    est, res, src, r = _rand_batch(N, Q, 0.8)
    pen = jnp.full((Q,), 1.3)
    ones = jnp.ones((Q,))
    i_k, s_k, f_k = flex_pick_node_batch_topk(est, res, src, r, pen,
                                              w_load=ones, w_src=ones * 0.25,
                                              cap=ones, k=k, tile=128,
                                              interpret=True)
    i_r, s_r, f_r = pick_node_batch_topk_ref(est, res, src, r, pen, ones,
                                             ones * 0.25, cap=ones, k=k)
    assert i_k.shape == i_r.shape == (Q, k)
    assert (jnp.asarray(i_k) == jnp.asarray(i_r)).all()
    assert (jnp.asarray(f_k) == jnp.asarray(f_r)).all()
    real = i_r >= 0
    assert jnp.abs(jnp.where(real, s_k - s_r, 0.0)).max() < 1e-5
    # empty slots are the (-1, NEG_INF) sentinel on both paths
    from repro.kernels.flex_score import NEG_INF
    assert (jnp.where(real, 0.0, s_k) <= jnp.where(real, 0.0,
                                                   NEG_INF / 2)).all()


def test_topk_k1_reduces_to_argmax_path():
    # K=1 must BE the existing batched argmax: same winner, bit-identical
    # best score (identical float expressions through the same kernel).
    for N, tile in [(5, 512), (300, 128), (513, 512), (1024, 256)]:
        Q = 7
        est, res, src, r = _rand_batch(N, Q, 0.8, seed=N)
        i_1, s_1, f_1 = flex_pick_node_batch(est, res, src, r, 1.3,
                                             w_load=1.0, w_src=0.25,
                                             cap=1.0, tile=tile,
                                             interpret=True)
        i_t, s_t, f_t = flex_pick_node_batch_topk(est, res, src, r, 1.3,
                                                  w_load=1.0, w_src=0.25,
                                                  cap=1.0, k=1, tile=tile,
                                                  interpret=True)
        assert i_t.shape == (Q, 1)
        assert (jnp.asarray(i_t[:, 0]) == jnp.asarray(i_1)).all()
        assert (jnp.asarray(f_t) == jnp.asarray(f_1)).all()
        feas = jnp.asarray(f_1)
        assert (jnp.where(feas, s_t[:, 0], 0.0)
                == jnp.where(feas, s_1, 0.0)).all()


def test_topk_column0_is_argmax_for_any_k():
    # The merged list is sorted (score desc, node idx asc), so column 0
    # equals the argmax decision for every k — the invariant the
    # wavefront candidate fallback builds on.
    N, Q = 513, 8
    est, res, src, r = _rand_batch(N, Q, 0.8)
    i_1, _, _ = flex_pick_node_batch(est, res, src, r, 1.3, w_load=1.0,
                                     w_src=0.25, cap=1.0, tile=128,
                                     interpret=True)
    for k in (2, 8, 16):
        i_t, s_t, _ = flex_pick_node_batch_topk(est, res, src, r, 1.3,
                                                w_load=1.0, w_src=0.25,
                                                cap=1.0, k=k, tile=128,
                                                interpret=True)
        assert (jnp.asarray(i_t[:, 0]) == jnp.asarray(i_1)).all()
        # sorted, and ties (if any) break toward the lower node index
        assert (jnp.asarray(s_t[:, :-1]) >= jnp.asarray(s_t[:, 1:])).all()


def test_topk_ties_break_toward_lowest_index():
    # All-equal node state: every feasible node scores identically, so
    # the candidate list must be exactly [0, 1, 2, ...] on both paths
    # (argmax first-occurrence, applied k-deep).
    N, Q, k = 300, 5, 6
    est = jnp.zeros((N, 2))
    res = jnp.zeros((N, 2))
    src = jnp.zeros((Q, N))
    r = jnp.full((Q, 2), 0.1)
    ones = jnp.ones((Q,))
    i_k, _, _ = flex_pick_node_batch_topk(est, res, src, r, ones,
                                          w_load=ones, w_src=ones * 0.25,
                                          cap=ones, k=k, tile=128,
                                          interpret=True)
    assert (jnp.asarray(i_k)
            == jnp.broadcast_to(jnp.arange(k), (Q, k))).all()


def test_topk_k_exceeds_feasible_count():
    # k > N: the real candidates lead the list, the rest are (-1,
    # NEG_INF) sentinels; mixed feasibility rows keep per-row counts.
    N, Q, k = 3, 4, 8
    est = jnp.asarray([[0.2, 0.2], [0.9, 0.9], [0.4, 0.4]])
    src = jnp.zeros((Q, N))
    r = jnp.where(jnp.arange(Q)[:, None] % 2 == 0, 0.3,
                  2.0) * jnp.ones((Q, 2))  # odd rows fit nowhere
    ones = jnp.ones((Q,))
    i_k, _, f_k = flex_pick_node_batch_topk(est, jnp.zeros((N, 2)), src, r,
                                            ones, w_load=ones,
                                            w_src=ones * 0.25, cap=ones,
                                            k=k, tile=512, interpret=True)
    i_r, _, f_r = pick_node_batch_topk_ref(est, jnp.zeros((N, 2)), src, r,
                                           ones, ones, ones * 0.25,
                                           cap=ones, k=k)
    assert (jnp.asarray(i_k) == jnp.asarray(i_r)).all()
    for q in range(Q):
        if q % 2 == 0:
            assert bool(f_k[q]) and (jnp.asarray(i_k[q, :2]) >= 0).all()
            assert (jnp.asarray(i_k[q, 3:]) == -1).all()
        else:
            assert not bool(f_k[q]) and (jnp.asarray(i_k[q]) == -1).all()


def test_topk_per_task_scalars():
    # penalty/cap/w_load/w_src vary per row; every row's k-list must match
    # a ref call with those scalars (incl. the best-fit w_load sign flip).
    N, Q, k = 100, 6, 4
    est, res, src, r = _rand_batch(N, Q, 0.8)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    pen = 1.0 + jax.random.uniform(ks[0], (Q,))
    cap = 0.7 + 0.3 * jax.random.uniform(ks[1], (Q,))
    w_load = jnp.where(jnp.arange(Q) % 2 == 0, 1.0, -1.0)
    w_src = 0.25 * jax.random.uniform(ks[3], (Q,))
    i_k, _, f_k = flex_pick_node_batch_topk(est, res, src, r, pen,
                                            w_load=w_load, w_src=w_src,
                                            cap=cap, k=k, tile=128,
                                            interpret=True)
    i_r, _, f_r = pick_node_batch_topk_ref(est, res, src, r, pen, w_load,
                                           w_src, cap=cap, k=k)
    assert (jnp.asarray(i_k) == jnp.asarray(i_r)).all()
    assert (jnp.asarray(f_k) == jnp.asarray(f_r)).all()


@pytest.mark.parametrize("N", [100, 513])
def test_cap_parameter_matches_ref(N):
    # Priority policies pass a per-task capacity bound through the packed
    # task vector; check it against the reference with the same cap.
    est, res, src = _rand_state(N, 0.8)
    r = jnp.asarray([0.08, 0.1])
    for cap in (0.7, 0.9):
        i_k, _, f_k = flex_pick_node(est, res, src, r, 1.2, cap=cap,
                                     tile=128, interpret=True)
        i_r, _, f_r = pick_node_ref(est, res, src, r, 1.2, 1.0, 0.25,
                                    cap=cap)
        assert bool(f_k) == bool(f_r)
        assert int(i_k) == int(i_r)
