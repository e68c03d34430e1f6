"""Flex ScheduleOne filter+score as a Pallas TPU kernel.

The paper parallelizes node filtering/scoring over p CPU threads (O(N/p),
§4.3).  The TPU-native form tiles the node table across VMEM blocks: each
grid step loads a lane-dense ``(R, tile)`` slab of load state, scores it
against a block of queued tasks on the VPU, and reduces per task to the
tile's best ``k`` (score, node) candidates; the tiny cross-tile merge
happens in jnp in the wrappers.

One kernel body serves all three entry points: the per-task decision
(``flex_score_tiles``, one task, k = 1), the batched wavefront argmax
(``flex_score_batch_tiles``, Q tasks, k = 1) and the top-K candidate
lists (``flex_score_batch_topk_tiles``).  With k = 1 the single peel IS
the argmax, so the three agree bit-for-bit by construction.

Layout and conventions are documented in docs/kernels.md
("TPU block-shape rules").  Points that matter for correctness:

  * The per-task scalars travel in ONE packed ``(Q, R + 4)`` task matrix
    ``[r_0..r_{R-1}, penalty, cap, w_load, w_src]`` per row so they stay
    traced values (policies derive e.g. ``cap`` from the task's priority
    class) instead of recompile-triggering static kernel parameters.
  * N need NOT be a multiple of ``tile``: the wrapper zero-pads the node
    table up to ``ntiles * tile`` and the kernel masks columns
    ``>= n_valid`` infeasible, so padding can never win the argmax.
  * Every block obeys the TPU tiling rule (last two block dims divisible
    by (8, 128) or equal to the array's): node tiles are a multiple of
    128 lanes unless one tile spans all of N, task blocks are
    ``Q_BLOCK`` rows or all of Q, and every value in the body is 2-D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Masking convention shared with repro.api.admission.NEG_INF and the
# reference oracle (ref.py): infeasible/padding scores are set to NEG_INF
# and "any feasible node" is decided by ``best > NEG_INF / 2``.  A finite
# sentinel (not -inf) keeps max/argmax NaN-free on every backend.
NEG_INF = -1e30

LANES = 128      # TPU lane width; node tiles short of N are multiples of it
Q_BLOCK = 256    # task rows per grid step once Q exceeds one block


def _topk_kernel(est_ref, res_ref, src_ref, task_ref, out_max_ref,
                 out_idx_ref, *, tile: int, n_valid: int, k: int):
    """Per-task top-``k`` (score, idx) of one (task block, node tile).

    The score plane is ``(bq, tile)``: tasks on sublanes, nodes on lanes.
    The resource axis is folded (R is tiny) instead of materializing a
    ``(bq, tile, R)`` cube.  The reduction peels the per-task maximum
    ``k`` times (max, first column holding it, mask that column to
    NEG_INF), so ties break toward the lowest node index — ``jnp.argmax``'s
    rule — and slot ``j`` holds the task's (j+1)-th best node in the tile,
    sorted by (score desc, node idx asc): the order the cross-tile merge
    in the wrapper relies on (docs/kernels.md, "Top-K candidate lists").
    """
    t = pl.program_id(0)
    est = est_ref[...].astype(jnp.float32)          # (R, tile)
    res = res_ref[...].astype(jnp.float32)          # (R, tile)
    src = src_ref[...].astype(jnp.float32)          # (bq, tile)
    task = task_ref[...].astype(jnp.float32)        # (bq, R + 4)
    R = est.shape[0]
    penalty = task[:, R:R + 1]                      # (bq, 1) columns
    cap = task[:, R + 1:R + 2]
    w_load = task[:, R + 2:R + 3]
    w_src = task[:, R + 3:R + 4]

    feasible = None
    maxload = None
    for j in range(R):
        load_j = penalty * est[j:j + 1, :] + res[j:j + 1, :]   # (bq, tile)
        fit_j = load_j + task[:, j:j + 1] <= cap
        feasible = fit_j if feasible is None else jnp.logical_and(feasible,
                                                                  fit_j)
        maxload = load_j if maxload is None else jnp.maximum(maxload, load_j)

    # Mask the zero-padded tail of the last tile (docs/kernels.md): a
    # column index >= n_valid is not a real node, never placeable.
    cols = jax.lax.broadcasted_iota(jnp.int32, src.shape, 1)
    feasible = jnp.logical_and(feasible, t * tile + cols < n_valid)
    score = -(w_load * maxload + w_src * src)
    score = jnp.where(feasible, score, NEG_INF)

    slot = jax.lax.broadcasted_iota(jnp.int32, out_max_ref.shape, 1)
    out_max = jnp.full(out_max_ref.shape, NEG_INF, jnp.float32)
    out_idx = jnp.full(out_idx_ref.shape, -1, jnp.int32)
    for j in range(k):
        best = jnp.max(score, axis=1, keepdims=True)            # (bq, 1)
        arg = jnp.min(jnp.where(score == best, cols, tile), axis=1,
                      keepdims=True)
        out_max = jnp.where(slot == j, best, out_max)
        out_idx = jnp.where(slot == j,
                            jnp.where(best > NEG_INF / 2, t * tile + arg, -1),
                            out_idx)
        if j + 1 < k:
            # Knock the winner out so the next peel finds the runner-up.
            # Once every real candidate is spent the peel keeps returning
            # NEG_INF slots (idx -1), so k may exceed tile or the feasible
            # count.
            score = jnp.where(cols == arg, NEG_INF, score)
    out_max_ref[...] = out_max
    out_idx_ref[...] = out_idx


def node_tiling(n: int, tile: int):
    """(tile, ntiles) for an N-node table under the TPU block-shape rule.

    One tile spanning all N nodes is always legal (a block dim equal to
    the array's); several tiles must each be a multiple of 128 lanes.
    """
    if tile >= n:
        return n, 1
    if tile % LANES:
        raise ValueError(
            f"flex_score: tile={tile} splits N={n} nodes into several "
            f"tiles, so it must be a multiple of {LANES} (TPU lane width)")
    return tile, pl.cdiv(n, tile)


def _score_topk(est, reserved, src_frac, task_mat, *, k, tile, interpret):
    """(ntiles, Q, k) per-tile candidate partials, scores and GLOBAL idx.

    est/reserved: (N, R); src_frac: (Q, N); task_mat: (Q, R + 4).
    """
    N, R = est.shape
    Q = task_mat.shape[0]
    tile, ntiles = node_tiling(N, tile)
    pad = ntiles * tile - N
    # Lane-dense node slabs: nodes on the lane axis, one row per resource.
    est_t = jnp.pad(est.astype(jnp.float32).T, ((0, 0), (0, pad)))
    res_t = jnp.pad(reserved.astype(jnp.float32).T, ((0, 0), (0, pad)))
    src_frac = src_frac.astype(jnp.float32)
    task_mat = task_mat.astype(jnp.float32)
    bq = Q if Q <= Q_BLOCK else Q_BLOCK
    qpad = (-Q) % bq
    # Padded task rows (all-zero) can at worst pick node 0; the wrapper
    # slices them off, so they never reach the caller.
    src_frac = jnp.pad(src_frac, ((0, qpad), (0, pad)))
    task_mat = jnp.pad(task_mat, ((0, qpad), (0, 0)))
    Qp = Q + qpad
    kernel = functools.partial(_topk_kernel, tile=tile, n_valid=N, k=k)
    out_spec = pl.BlockSpec((None, bq, k), lambda t, q: (t, q, 0))
    out_max, out_idx = pl.pallas_call(
        kernel,
        grid=(ntiles, Qp // bq),
        in_specs=[
            # The node slab's block index is constant along the inner
            # task axis, so it is fetched once per tile for all Q tasks.
            pl.BlockSpec((R, tile), lambda t, q: (0, t)),
            pl.BlockSpec((R, tile), lambda t, q: (0, t)),
            pl.BlockSpec((bq, tile), lambda t, q: (q, t)),
            pl.BlockSpec((bq, R + 4), lambda t, q: (q, 0)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((ntiles, Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((ntiles, Qp, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(est_t, res_t, src_frac, task_mat)
    return out_max[:, :Q], out_idx[:, :Q]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def flex_score_tiles(est, reserved, src_frac, task_vec, *, tile=512,
                     interpret=False):
    """Per-tile (max score, argmax) partials for one placement decision.

    est/reserved: (N, R); src_frac: (N,) or (N, 1); task_vec: (R+4,) or
    (1, R+4), packed as ``[r..., penalty, cap, w_load, w_src]``.  N is arbitrary: the node
    table is zero-padded to the next multiple of ``tile`` and the tail is
    masked infeasible inside the kernel.

    Returns (tile_max (ntiles,), tile_idx (ntiles,)) — tile_idx entries are
    GLOBAL node indices (or -1 when the whole tile is infeasible).
    """
    tmax, tidx = _score_topk(est, reserved, src_frac.reshape(1, -1),
                             task_vec.reshape(1, -1), k=1, tile=tile,
                             interpret=interpret)
    return tmax[:, 0, 0], tidx[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def flex_score_batch_tiles(est, reserved, src_frac, task_mat, *, tile=512,
                           interpret=False):
    """Per-tile (max score, argmax) partials for a WHOLE queue of tasks.

    est/reserved: (N, R); src_frac: (Q, N) — one same-source-fraction row
    per queued task; task_mat: (Q, R+4), each row packed as
    ``[r..., penalty, cap, w_load, w_src]`` (the per-task analogue of
    ``flex_score_tiles``'s single task vector).

    One node slab is loaded ONCE per tile and scored against all Q tasks
    (docs/kernels.md, "Batched wavefront admission").  N and Q are
    arbitrary (zero-padded + masked tail, pad rows sliced off).

    Returns (tile_max (ntiles, Q), tile_idx (ntiles, Q)); tile_idx holds
    GLOBAL node indices, -1 where a tile is infeasible for that task.
    """
    tmax, tidx = _score_topk(est, reserved, src_frac, task_mat, k=1,
                             tile=tile, interpret=interpret)
    return tmax[..., 0], tidx[..., 0]


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def flex_score_batch_topk_tiles(est, reserved, src_frac, task_mat, *, k=8,
                                tile=512, interpret=False):
    """Per-tile top-``k`` (score, idx) candidate partials for a whole queue.

    Same inputs and padding rules as ``flex_score_batch_tiles``; instead
    of one (max, argmax) pair per tile, each grid step emits its ``k``
    best candidates per task (sorted by score desc, node idx asc — see
    ``_topk_kernel``).

    Returns (tile_max (ntiles*k, Q), tile_idx (ntiles*k, Q)): row
    ``t*k + j`` holds tile ``t``'s (j+1)-th best candidate for each task,
    so the row order is tile-major — for equal scores, earlier rows hold
    lower global node indices, which the cross-tile merge in
    ``flex_pick_node_batch_topk`` relies on for exact argmax tie parity.
    Slots past a tile's feasible count are (NEG_INF, -1).
    """
    tmax, tidx = _score_topk(est, reserved, src_frac, task_mat, k=k,
                             tile=tile, interpret=interpret)
    ntiles, Q, _ = tmax.shape
    rows = lambda x: jnp.transpose(x, (0, 2, 1)).reshape(ntiles * k, Q)
    return rows(tmax), rows(tidx)
