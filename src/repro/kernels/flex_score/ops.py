"""Public wrapper: one Flex placement decision over the node table.

``flex_pick_node`` is the kernel/policy boundary documented in
docs/kernels.md: the policy layer (``repro.api.admission.pick_node``) hands
it the node-side arrays from a policy's ``kernel_inputs`` hook, and it
dispatches to the Pallas tile kernel on TPU (or in interpreter mode) with
the reference einsum everywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flex_score.flex_score import (NEG_INF,
                                                 flex_score_batch_topk_tiles,
                                                 flex_score_tiles)
from repro.kernels.flex_score.ref import (pick_node_batch_topk_ref,
                                          pick_node_ref)


def flex_pick_node(est, reserved, src_frac, r_task, penalty, *,
                   w_load=1.0, w_src=0.25, cap=1.0, tile=512,
                   interpret=False):
    """One fused filter+score+argmax placement decision (Alg. 3 lines 3-9).

    Args:
      est: (N, R) f32 — estimated node load L-hat (multiplied by ``penalty``
        in-kernel, eq. 9).
      reserved: (N, R) f32 — this-round reservations.
      src_frac: (N,) f32 — fraction of each node's tasks sharing the
        incoming task's source bucket (§4.3 spreading term).
      r_task: (R,) f32 (or scalar) — the task's declared request.
      penalty: scalar — current estimation penalty P.
      w_load / w_src: scalar score weights; the score is
        ``-(w_load * max_R(load) + w_src * src_frac)``.  May be traced
        values (they ride in the kernel's packed task vector).
      cap: scalar per-resource capacity bound (1.0 = full node; priority
        policies pass a task-dependent value).
      tile: nodes per VMEM block.  N need NOT be a multiple of ``tile`` —
        the tail tile is zero-padded and masked in-kernel.
      interpret: run the Pallas kernel through the Pallas interpreter
        (pure XLA ops, works on any backend).  This is the testing escape
        hatch: CPU CI exercises the REAL kernel logic — tiling, padding,
        masking, cross-tile reduction — without TPU hardware, and it is
        jit/scan-compatible, so whole simulator runs can flow through it
        (``SimConfig(kernel_interpret=True)``).

    Dispatch: Pallas when ``interpret=True`` or the default backend is TPU;
    otherwise the reference einsum (``pick_node_ref``) — same floats, same
    NEG_INF masking convention, bit-for-bit the same answer.

    Returns (node_idx or -1, best_score, any_feasible).
    """
    use_pallas = interpret or jax.default_backend() == "tpu"
    if not use_pallas:
        return pick_node_ref(est, reserved, src_frac, r_task, penalty,
                             w_load, w_src, cap=cap)
    task_vec = jnp.concatenate([
        jnp.asarray(r_task, jnp.float32).reshape(-1),
        jnp.asarray(penalty, jnp.float32).reshape(1),
        jnp.asarray(cap, jnp.float32).reshape(1),
        jnp.asarray(w_load, jnp.float32).reshape(1),
        jnp.asarray(w_src, jnp.float32).reshape(1),
    ]).reshape(1, -1)
    tmax, tidx = flex_score_tiles(est, reserved,
                                  src_frac.reshape(-1, 1).astype(jnp.float32),
                                  task_vec, tile=tile, interpret=interpret)
    t = jnp.argmax(tmax)
    best = tmax[t]
    any_feasible = best > NEG_INF / 2
    idx = jnp.where(any_feasible, tidx[t], -1).astype(jnp.int32)
    return idx, best, any_feasible


def flex_pick_node_batch_topk(est, reserved, src_frac, r_task, penalty, *,
                              w_load, w_src, cap, k=8, tile=512,
                              interpret=False):
    """Top-``k`` candidate lists for the whole queue in one batched pass.

    The wavefront-admission primitive (docs/kernels.md, "Batched
    wavefront admission" and "Top-K candidate lists"): every node tile is
    loaded once and scored against all Q queued tasks (k cheap VPU peels
    per tile), and each task walks away with its k best (score, node)
    candidates, so conflict-resolution rounds can fall back through the
    cached list instead of re-sweeping the table.

    Args:
      est / reserved: (N, R) f32 — node-side load state, shared by every
        task (commits within a wavefront round are applied between calls).
      src_frac: (Q, N) f32 — per-task same-source fraction rows.
      r_task: (Q, R) f32 — declared requests.
      penalty / w_load / w_src / cap: scalar or (Q,) — per-task scalars of
        the kernel template; scalars are broadcast to the queue.
      k: candidates per task (static).
      tile / interpret: as in ``flex_pick_node``.

    Dispatch mirrors ``flex_pick_node``: Pallas when ``interpret=True`` or
    on TPU, the batched reference otherwise.  The Pallas path emits
    per-tile k-lists and this wrapper K-way-merges them with
    ``jax.lax.top_k`` over the tile-major candidate axis; because
    per-tile lists and tile order are both (score desc, node idx asc),
    the merged list equals the full-table ``pick_node_batch_topk_ref``
    bit-for-bit, column for column — with k=1 column 0 is the batched
    argmax, row for row the per-task ``flex_pick_node`` decision.

    Returns (idx (Q, k), score (Q, k), any_feasible (Q,)); slots past a
    task's feasible-node count are (-1, NEG_INF).
    """
    r_task = jnp.asarray(r_task, jnp.float32)
    Q = r_task.shape[0]
    if r_task.shape != (Q, est.shape[1]) or src_frac.shape != (Q, est.shape[0]):
        raise ValueError(
            f"flex_pick_node_batch_topk: expected r_task (Q, R)="
            f"{Q, est.shape[1]} and src_frac (Q, N)={Q, est.shape[0]}, got "
            f"{r_task.shape} and {src_frac.shape}")
    bcast = lambda x: jnp.broadcast_to(
        jnp.asarray(x, jnp.float32).reshape(-1), (Q,))
    penalty, cap, w_load, w_src = map(bcast, (penalty, cap, w_load, w_src))
    use_pallas = interpret or jax.default_backend() == "tpu"
    if not use_pallas:
        return pick_node_batch_topk_ref(est, reserved,
                                        src_frac.astype(jnp.float32),
                                        r_task, penalty, w_load, w_src,
                                        cap=cap, k=k)
    task_mat = jnp.concatenate([
        r_task, penalty[:, None], cap[:, None],
        w_load[:, None], w_src[:, None]], axis=1)       # (Q, R+4)
    tmax, tidx = flex_score_batch_topk_tiles(est, reserved,
                                             src_frac.astype(jnp.float32),
                                             task_mat, k=k, tile=tile,
                                             interpret=interpret)
    # Cross-tile K-way merge: (ntiles*k, Q) candidates, tile-major, each
    # tile's block already sorted — top_k keeps the first occurrence on
    # ties, i.e. the lowest global node index (see the tile wrapper).
    best, pos = jax.lax.top_k(tmax.T, k)                # (Q, k) both
    idx = jnp.take_along_axis(tidx.T, pos, axis=1)
    idx = jnp.where(best > NEG_INF / 2, idx, -1).astype(jnp.int32)
    return idx, best, best[:, 0] > NEG_INF / 2
