"""The shared admission-control core (paper Alg. 3 ``ScheduleOne``).

One set of filter/score primitives used by BOTH execution substrates:

  * the discrete-time cluster simulator (`repro.core.simulator`) — jnp
    arrays inside a traced ``lax.scan``;
  * the continuous-batching serving engine (`repro.serving.engine`) —
    the same ``admit_queue`` behind the jitted per-policy entry
    :func:`make_queue_admitter`, replicas mapped onto ``NodeState`` with
    slot + KV resources (bit-identical placements:
    tests/test_serving_parity.py).

Every helper is written against the array *methods / operators* shared by
``numpy`` and ``jax.numpy`` (plus an explicit ``where`` dispatch), so the
two paths cannot drift apart again: an admission rule is expressed once.

Shapes are generic over the trailing resource axis: callers pass
``(N, R)`` loads with ``(R,)`` requests for any R.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import FlexParams, NodeState

NEG_INF = -1e30

# Effective load pinned onto drained/unavailable nodes (down, flapped-out,
# draining ahead of a fault, or a migration source): far above any capacity
# or oversubscription factor, so the capacity filter of EVERY load model
# rejects every candidate.  The single shared sentinel — the serving engine
# and the fault/migration offsets all import it from here.
DRAIN_LOAD = 1e6


def _xp(x):
    """numpy for eager numpy inputs, jax.numpy otherwise."""
    return np if isinstance(x, np.ndarray) else jnp


# ---------------------------------------------------------------------------
# Load models
# ---------------------------------------------------------------------------

def committed_load(requested, reserved):
    """RLB load: resources promised to running + just-admitted tasks."""
    return requested + reserved


def usage_load(est_usage, reserved, penalty):
    """ULB load (eq. 9): penalized estimate + this-round reservations."""
    return penalty * est_usage + reserved


def fault_load_offset(node_up, capacity, drain_load=DRAIN_LOAD):
    """(N,) load offset expressing node faults to EVERY admission policy.

    Down nodes get ``drain_load`` (``DRAIN_LOAD`` — far above any capacity
    or theta, so both load models reject every candidate);
    capacity-flapped nodes get the lost fraction ``1 - capacity``.
    Healthy nodes get exactly 0.0, so the identity schedule is
    bit-identical to no faults.
    """
    xp = _xp(capacity)
    return xp.where(node_up, 1.0 - capacity, drain_load).astype(capacity.dtype)


def mask_unavailable(node: "NodeState", offset) -> "NodeState":
    """Fold a per-node fault offset into a NodeState's reservations.

    ``reserved`` rides both load models — ``committed_load`` (RLB) and
    ``usage_load`` (ULB) — and the fused-kernel template's reserved plane,
    so one scatter makes crashed/degraded nodes unattractive (or
    unadmittable) to every registry policy and every execution mode with
    no policy-specific branches.  The offset is constant within a slot,
    which is exactly the admission-invariance the wavefront conflict
    checks assume (docs/kernels.md).
    """
    return node._replace(reserved=node.reserved + offset[:, None])


# ---------------------------------------------------------------------------
# Filter + score primitives
# ---------------------------------------------------------------------------

def fits(load, request, capacity):
    """Capacity filter: ``load + request <= capacity`` on every resource.

    load: (N, R); request: (R,) or scalar; capacity: scalar or broadcastable.
    Returns (N,) bool.
    """
    return (load + request <= capacity).all(axis=-1)


def dominant(load, capacity=None):
    """Dominant-resource share of a multi-resource load: max over R."""
    if capacity is not None:
        load = load / capacity
    return load.max(axis=-1)


def least_loaded_score(load, capacity=None):
    """Prefer the node whose dominant resource is least committed."""
    return -dominant(load, capacity)


def mask_infeasible(scores, feasible):
    """Infeasible nodes can never win the argmax."""
    xp = _xp(scores)
    return xp.where(feasible, scores, NEG_INF)


# ---------------------------------------------------------------------------
# Kernel/policy contract (docs/kernels.md)
# ---------------------------------------------------------------------------

class KernelInputs(NamedTuple):
    """What a policy hands the fused Pallas filter+score kernel.

    A policy opts into the kernel path by exposing an optional
    ``kernel_inputs(ctx, task) -> KernelInputs`` hook: the kernel then
    evaluates feasibility ``all_R(penalty * est_usage + reserved + r <= cap)``
    and score ``-(w_load * max_R(load) + w_src * src_frac)`` — the ULB
    filter (eq. 9) + Flex score (§4.3) family.  Any policy whose math fits
    that template (pick the scalars) gets the TPU hot path for free;
    policies without the hook always take the reference ``feasible``/
    ``score`` path.  All leaves may be traced values.
    """

    est_usage: jnp.ndarray   # (N, R) f32 — UNscaled load estimate L-hat
                             # (the kernel multiplies by penalty itself)
    reserved: jnp.ndarray    # (N, R) f32 — this-round reservations
    src_frac: jnp.ndarray    # (N,)   f32 — same-source fraction per node
    penalty: jnp.ndarray     # ()     f32 — estimation penalty P
    cap: jnp.ndarray         # ()     f32 — per-resource capacity bound
    w_load: jnp.ndarray      # ()     f32 — load-term score weight
    w_src: jnp.ndarray       # ()     f32 — same-source score weight


# ---------------------------------------------------------------------------
# Traced admission step (simulator side)
# ---------------------------------------------------------------------------

class TaskView(NamedTuple):
    """The slice of one task a placement policy may look at."""

    request: jnp.ndarray    # (R,) f32 — declared resources r_j
    src: jnp.ndarray        # ()   i32 — source hash bucket
    priority: jnp.ndarray   # ()   i32 — CLASS_* priority


class PolicyContext(NamedTuple):
    """Cluster state a policy sees when placing one task."""

    node: NodeState         # per-node aggregates (N leading axis)
    penalty: jnp.ndarray    # () f32 — current estimation penalty P
    params: FlexParams      # static algorithm parameters


def pick_node(policy, ctx: PolicyContext, task: TaskView, *,
              use_kernel: bool = False, interpret: bool = False):
    """One fused filter+score+argmax decision (Alg. 3 lines 3-9).

    The batched primitive behind ``admit_one``: reduces the whole node
    table to a single candidate.  When ``use_kernel`` is set AND the policy
    exposes the ``kernel_inputs`` hook (see ``KernelInputs``), the
    reduction dispatches to the Pallas tile kernel
    ``repro.kernels.flex_score.flex_pick_node`` (real Pallas on TPU or with
    ``interpret=True``; reference einsum elsewhere).  Otherwise it runs the
    policy's ``feasible``/``score`` hooks — the reference path.  Both
    flags are Python bools resolved at trace time, so the choice costs
    nothing inside ``jit``/``scan``.

    Returns (idx, any_feasible): ``idx`` is the winning node or -1 when no
    node is feasible.
    """
    kernel_inputs = getattr(policy, "kernel_inputs", None)
    if use_kernel and kernel_inputs is not None:
        from repro.kernels.flex_score.ops import flex_pick_node

        ki = kernel_inputs(ctx, task)
        idx, _, any_feasible = flex_pick_node(
            ki.est_usage, ki.reserved, ki.src_frac, task.request, ki.penalty,
            w_load=ki.w_load, w_src=ki.w_src, cap=ki.cap, interpret=interpret)
        return idx, any_feasible
    feasible = policy.feasible(ctx, task)
    scores = mask_infeasible(policy.score(ctx, task), feasible)
    any_feasible = jnp.any(feasible)
    idx = jnp.where(any_feasible, jnp.argmax(scores), -1).astype(jnp.int32)
    return idx, any_feasible


def admit_one(policy, ctx: PolicyContext, task: TaskView,
              valid: jnp.ndarray, *, use_kernel: bool = False,
              interpret: bool = False):
    """ScheduleOne: filter, score, place on argmax; -1 when nothing fits.

    All state updates are O(1) scatters so a long ``lax.scan`` over a task
    queue stays cheap (the O(N) filter/score reduction IS the algorithm —
    and it is the part ``use_kernel`` routes through the Pallas kernel,
    see ``pick_node``).  Returns (new NodeState, node idx).
    """
    node = ctx.node
    cand, any_feasible = pick_node(policy, ctx, task,
                                   use_kernel=use_kernel, interpret=interpret)
    ok = jnp.logical_and(any_feasible, valid)
    idx = jnp.where(ok, cand, -1).astype(jnp.int32)

    i = jnp.maximum(idx, 0)
    okf = ok.astype(jnp.float32)
    oki = ok.astype(jnp.int32)
    new_node = NodeState(
        est_usage=node.est_usage,
        reserved=node.reserved.at[i].add(okf * task.request),
        requested=node.requested.at[i].add(okf * task.request),
        n_tasks=node.n_tasks.at[i].add(oki),
        src_count=node.src_count.at[i, task.src].add(oki),
    )
    return new_node, idx


def admit_queue(policy, node: NodeState, requests, srcs, priorities,
                valid, penalty, params: FlexParams, *,
                use_kernel: bool = False, interpret: bool = False,
                batch_mode: bool = False, topk: int = 8,
                dedup_buckets: int = 64, tie_margin: float = 1e-5):
    """Admit a padded queue of tasks in queue order.

    requests: (Q, R); srcs/priorities/valid: (Q,).  Two execution shapes,
    decision-for-decision identical:

      * sequential (default): one ``lax.scan`` over ``admit_one`` — with
        ``use_kernel`` every decision in the scan body is one fused kernel
        call (policies without the ``kernel_inputs`` hook silently keep
        the reference path);
      * ``batch_mode=True``: wavefront rounds over the BATCHED kernel
        (``admit_queue_wavefront``) for kernel-hooked policies — the whole
        queue is scored per node-table sweep instead of one task per
        sweep.  ``topk``/``dedup_buckets``/``tie_margin`` tune that path
        (see ``admit_queue_wavefront``; they are ignored by the
        sequential scan).  Policies without the hook silently fall back
        to the sequential scan.

    Returns (NodeState, placements (Q,) — node idx or -1).
    """
    if batch_mode and getattr(policy, "kernel_inputs", None) is not None:
        return admit_queue_wavefront(policy, node, requests, srcs,
                                     priorities, valid, penalty, params,
                                     interpret=interpret, topk=topk,
                                     dedup_buckets=dedup_buckets,
                                     tie_margin=tie_margin)

    def step(ns, xs):
        r, src, prio, ok = xs
        ctx = PolicyContext(node=ns, penalty=penalty, params=params)
        return admit_one(policy, ctx, TaskView(r, src, prio), ok,
                         use_kernel=use_kernel, interpret=interpret)

    return jax.lax.scan(step, node, (requests, srcs, priorities, valid))


def make_queue_admitter(policy, params: FlexParams, *,
                        batch_mode: bool = False, use_kernel: bool = False,
                        interpret: bool = False, topk: int = 8,
                        dedup_buckets: int = 64, tie_margin: float = 1e-5):
    """Compile one reusable admission entry point for a fixed policy.

    The serving engine (and any other eager caller that admits queues
    repeatedly against changing state) should not re-trace
    :func:`admit_queue` per call: the policy object, the wavefront knobs
    and the static queue width fully determine the XLA program.  This
    wraps ``admit_queue`` in a ``jax.jit`` whose only traced inputs are
    the live cluster state — ``(node, requests, srcs, priorities, valid,
    penalty)`` — so each distinct padded queue width compiles once and
    every subsequent engine step is a single cached-executable launch.

    ``params`` is bound after the policy's ``prepare_params``
    normalization (e.g. ULB policies pin theta), exactly as the
    simulator does before its scan — but TRACED, not closed over, so
    every admitter for the same (policy, knobs) shares one jit cache:
    constructing many engines (the parity property suite builds
    hundreds) compiles each queue width once, not once per engine.

    Returns ``admit(node, requests, srcs, priorities, valid, penalty)
    -> (NodeState, placements (Q,))``.
    """
    from repro.api.protocols import policy_prepare_params

    prepared = policy_prepare_params(policy, params)
    fn = _shared_queue_admitter(policy, batch_mode, use_kernel, interpret,
                                topk, dedup_buckets, tie_margin)

    def admit(node, requests, srcs, priorities, valid, penalty):
        return fn(node, requests, srcs, priorities, valid, penalty, prepared)

    return admit


@functools.lru_cache(maxsize=64)
def _shared_queue_admitter(policy, batch_mode, use_kernel, interpret,
                           topk, dedup_buckets, tie_margin):
    """One jitted admit_queue per (policy, static knobs) — see
    :func:`make_queue_admitter`.  Policies are frozen dataclasses, so
    they hash; FlexParams rides in as a traced pytree."""

    @jax.jit
    def admit(node, requests, srcs, priorities, valid, penalty, params):
        return admit_queue(policy, node, requests, srcs, priorities,
                           valid, penalty, params,
                           use_kernel=use_kernel, interpret=interpret,
                           batch_mode=batch_mode, topk=topk,
                           dedup_buckets=dedup_buckets,
                           tie_margin=tie_margin)

    return admit


# ---------------------------------------------------------------------------
# Wavefront batched admission (docs/kernels.md, "Batched wavefront
# admission")
# ---------------------------------------------------------------------------

def _batched_kernel_inputs(policy, ctx: PolicyContext, tasks: TaskView):
    """vmap a policy's ``kernel_inputs`` hook over a whole task queue.

    Node-side arrays (``est_usage``/``reserved``) must be task-INDEPENDENT
    (they describe cluster state; ``out_axes=None`` enforces it — a hook
    that derives them from the task raises here and cannot take the
    wavefront path).  Per-task leaves come back batched: ``src_frac``
    becomes (Q, N); the four scalars broadcast to (Q,).
    """
    hook = policy.kernel_inputs
    out_axes = KernelInputs(est_usage=None, reserved=None, src_frac=0,
                            penalty=0, cap=0, w_load=0, w_src=0)
    return jax.vmap(lambda t: hook(ctx, t), out_axes=out_axes)(tasks)


def admit_queue_wavefront(policy, node: NodeState, requests, srcs,
                          priorities, valid, penalty, params: FlexParams, *,
                          interpret: bool = False, tile: int = 512,
                          tie_margin: float = 1e-5, topk: int = 8,
                          dedup_buckets: int = 64,
                          with_rounds: bool = False):
    """Admit the queue in conflict-resolution rounds over the batched kernel.

    Instead of Q sequential O(N) node-table sweeps (one kernel launch per
    task), ONE batched top-``topk`` sweep
    (``flex_pick_node_batch_topk``) caches every task's ``topk`` best
    (score, node) candidates, and conflict-resolution rounds then fall
    back through the cached list instead of re-launching the kernel: per
    round the longest provably-safe prefix of pending tasks commits its
    current candidates, a commit marks its node *dirty*, and a task whose
    candidate went dirty slides to its next clean cached entry.  The node
    table is swept ONCE per queue in the common case; a guarded re-sweep
    runs only when the head pending task exhausts its cached candidates
    or a dirtied node provably threatens its candidate score (the same
    beat-check machinery that guards intra-round commits).  The number of
    sweeps drops from Q (sequential) or #rounds (the ``topk=0`` legacy
    loop below) to #epochs: one on low-conflict queues, ~Q/(3K) under
    conflict-heavy Flex scoring where each sweep's lists go stale after
    ~3K commits dirty the shared least-loaded frontier
    (docs/kernels.md cost model; BENCH_scheduler_throughput.json).

    With ``topk=0`` the pre-candidate-cache behavior is kept: every
    conflict round re-sweeps the node table with the argmax kernel
    (``flex_pick_node_batch``) — one sweep per round.  This path exists
    for comparison benchmarks and as an escape hatch; decisions are
    identical either way.

    A **score-bucket dedup** (``dedup_buckets`` > 0) additionally shrinks
    each sweep: under the kernel template a task's whole (N,) score row
    is determined by its ``(r, penalty, cap, w_load, w_src, src)`` tuple,
    so duplicate-heavy queues (repeated job shapes from the same source —
    the common trace regime) collapse onto ``Q_eff`` ≤ ``dedup_buckets``
    distinct rows: the kernel scores one representative per bucket and
    the candidate lists scatter back to the full queue.  When the queue
    holds more than ``dedup_buckets`` distinct rows the sweep falls back
    to full width (a traced ``lax.cond``, both shapes static).  Under
    Flex scoring with queue-constant ``FlexParams`` and per-class caps,
    distinctness is driven by (request vector, src bucket) — ≤ 64
    distinct rows whenever job shapes repeat across the
    ``NUM_SRC_BUCKETS`` = 64 sources.

    Committed decisions are decision-for-decision identical to the
    sequential ``lax.scan`` (the parity argument, proved in
    docs/kernels.md):

      * a task whose SWEEP sees NO feasible node finalizes -1
        immediately: commits only ever ADD load, and the capacity filter
        is antitone in load, so no later state can make it feasible —
        whatever earlier still-pending tasks end up doing;
      * a pending task's current candidate is its first cached entry
        whose node is still CLEAN (not committed-to since the sweep).
        Clean nodes are untouched since the sweep, so the cached score is
        the node's true current score, the list order is the true current
        order among clean nodes, and any clean node outside the list is
        dominated by the list tail (or was infeasible at sweep time and
        stays so).  Ties need no margin here: the merged list is sorted
        (score desc, node idx asc), exactly ``jnp.argmax``'s rule;
      * pending tasks commit as a PREFIX in queue order, cut at the first
        task that is "unsafe": it exhausted its cached candidates, a
        DIRTY node's current score could reach its candidate's score
        (dirty-beat — the candidate-invalidation check), its candidate
        node was already picked by an earlier pending task this round
        (dup), or some earlier pending task i's candidate node, AFTER i's
        commit, could reach its candidate's score (beat).  For a task
        inside that prefix, the sequential scan would have seen exactly
        the round-start state plus one commit on each earlier prefix
        candidate: every node is then either clean (cached order applies),
        dirty from an earlier round (dirty-beat checked it against the
        true current state), or committed this round by a dup-free
        earlier prefix task (beat checked its post-commit state) — none
        reaches the candidate's score, so the sequential argmax IS the
        cached candidate.  (A commit CAN raise a node's score for other
        tasks — the same-source fraction dilutes, and best-fit flips the
        sign of ``w_load`` — which is why both beat checks are evaluated,
        not assumed away, and why "no earlier task picked the same node"
        alone would be unsound.)

    Both beat checks recompute candidate scores with the canonical
    kernel-template arithmetic and flag anything within ``tie_margin``
    (relative) of the candidate score.  Over-flagging is safe — the task
    rolls to the next round or triggers a re-sweep and is re-decided
    exactly by the kernel — so the margin absorbs mul/add-fusion ULP
    differences between the Pallas and jnp flavors of the same float
    expressions.

    Exactness of the checks (and of the dedup key) assumes the hook maps
    onto node state canonically: ``est_usage`` unaffected by admissions,
    ``reserved`` tracking ``node.reserved``, ``src_frac`` equal to
    ``src_count[:, src] / max(n_tasks, 1)`` whenever ``w_src != 0``, and
    the four scalars admission-invariant.  All built-in kernel policies
    qualify; a custom hook that violates this must keep ``batch_mode``
    off.

    Queue-width caveat: the conflict checks materialize a few (Q, Q) f32
    planes per round (no N axis).  That is trivial next to the (Q, N)
    kernel sweep while Q << N, but at paper-scale padded queues
    (``retry_capacity + arrivals_per_slot`` = 5120 > N = 4000) it becomes
    the dominant allocation (~100 MB per plane).  Wavefront targets
    kernel-launch-bound backends at moderate queue widths; keep
    ``admission_mode="sequential"`` when Q approaches N, or shrink the
    slot queue.

    Returns (NodeState, placements (Q,)) — plus (rounds, sweeps) when
    ``with_rounds`` (static flag) is set: ``rounds`` counts commit
    rounds, ``sweeps`` counts node-table sweeps (kernel launches); the
    legacy ``topk=0`` loop launches once per round, so there
    rounds == sweeps.
    """
    from repro.kernels.flex_score.ops import (flex_pick_node_batch,
                                              flex_pick_node_batch_topk)

    requests = jnp.asarray(requests, jnp.float32)
    Q, R = requests.shape
    N = node.n_tasks.shape[0]
    pos = jnp.arange(Q, dtype=jnp.int32)
    tasks = TaskView(request=requests, src=srcs, priority=priorities)

    def _commit_state(ns, commit, cc):
        """Apply a round's commit prefix to the node aggregates."""
        okf = commit.astype(jnp.float32)
        oki = commit.astype(jnp.int32)
        return NodeState(
            est_usage=ns.est_usage,
            reserved=ns.reserved.at[cc].add(okf[:, None] * requests),
            requested=ns.requested.at[cc].add(okf[:, None] * requests),
            n_tasks=ns.n_tasks.at[cc].add(oki),
            src_count=ns.src_count.at[cc, srcs].add(oki),
        )

    def _post_commit_beat(ns, ki, cc, ref_sc, lead):
        """beat: would node c_i, AFTER task i's commit, reach task q's
        candidate score?  Evaluated for all (q, i) pairs with the
        canonical kernel-template arithmetic; each prefix node receives
        exactly one commit, so row i is node c_i's true post-commit
        state.  The node axis N never appears, but the check IS O(Q^2)
        memory per round (a few (Q, Q) f32 planes) — see the queue-width
        caveat in the docstring."""
        est_i = ki.est_usage[cc]                      # (Q, R)
        res_i = ns.reserved[cc] + requests            # (Q, R) post-commit
        feas_qi = None
        maxl_qi = None
        for j in range(R):
            l_j = ki.penalty[:, None] * est_i[:, j][None, :] \
                + res_i[:, j][None, :]
            fit_j = l_j + requests[:, j][:, None] <= ki.cap[:, None]
            feas_qi = fit_j if feas_qi is None else feas_qi & fit_j
            maxl_qi = l_j if maxl_qi is None else jnp.maximum(maxl_qi, l_j)
        same_src = srcs[:, None] == srcs[None, :]     # [q, i]
        # src_count[c_i, s_q], read as a row gather of the (Q, 64) slice:
        # the same values as a (Q, Q) gather of single elements, which
        # takes 305 ms against 0.2 ms at Q = 5120 on a TPU v5e.
        cnt_qi = ns.src_count[cc].T[srcs]
        src_qi = ((cnt_qi + same_src).astype(jnp.float32)
                  / jnp.maximum(ns.n_tasks[cc] + 1, 1)
                  .astype(jnp.float32)[None, :])
        s_qi = -(ki.w_load[:, None] * maxl_qi + ki.w_src[:, None] * src_qi)
        s_qi = jnp.where(feas_qi, s_qi, NEG_INF)
        margin = tie_margin * (1.0 + jnp.abs(ref_sc))
        beats = s_qi >= (ref_sc - margin)[:, None]
        earlier_lead = lead[None, :] & (pos[None, :] < pos[:, None])
        return jnp.any(beats & earlier_lead, axis=1)

    if topk == 0:
        # Legacy loop (PR 3): one full batched argmax sweep per round.
        def round_body(state):
            ns, pending, placement, rounds = state
            ctx = PolicyContext(node=ns, penalty=penalty, params=params)
            ki = _batched_kernel_inputs(policy, ctx, tasks)
            cand, best, feas = flex_pick_node_batch(
                ki.est_usage, ki.reserved, ki.src_frac, requests, ki.penalty,
                w_load=ki.w_load, w_src=ki.w_src, cap=ki.cap, tile=tile,
                interpret=interpret)

            # Tasks with no feasible node finalize -1 now (placement
            # already -1); the rest are this round's wavefront.
            pending_f = pending & feas
            cc = jnp.clip(cand, 0, N - 1)

            # dup: an earlier pending task already picked this node.
            first_at = jnp.full((N,), Q, jnp.int32).at[cc].min(
                jnp.where(pending_f, pos, Q))
            dup = pending_f & (first_at[cc] < pos)
            lead = pending_f & ~dup   # first picker of each candidate node

            beat = _post_commit_beat(ns, ki, cc, best, lead)

            # Commit the prefix before the first unsafe task; everything
            # after it rolls to the next round (its decision could change
            # theirs).
            unsafe = pending_f & (dup | beat)
            first_unsafe = jnp.min(jnp.where(unsafe, pos, Q))
            commit = pending_f & (pos < first_unsafe)

            ns = _commit_state(ns, commit, cc)
            placement = jnp.where(commit, cand, placement)
            return ns, pending_f & ~commit, placement, rounds + 1

        init = (node, valid, jnp.full((Q,), -1, jnp.int32),
                jnp.zeros((), jnp.int32))
        node, _, placement, rounds = jax.lax.while_loop(
            lambda s: jnp.any(s[1]), round_body, init)
        if with_rounds:
            return node, placement, rounds, rounds
        return node, placement

    # ------------------------------------------------------------------
    # Candidate-caching path: sweep once per EPOCH, fall back through the
    # cached top-K lists between sweeps.
    # ------------------------------------------------------------------
    K = int(topk)
    use_dedup = 0 < int(dedup_buckets) < Q

    def _sweep(ns):
        """One batched top-K kernel pass over the whole queue under ns.

        Returns (cand_idx (Q, K), cand_sc (Q, K), ki); with dedup, only
        one representative per distinct score-bucket reaches the kernel
        and the lists are scattered back (identical rows — identical
        candidates, bit-for-bit)."""
        ctx = PolicyContext(node=ns, penalty=penalty, params=params)
        ki = _batched_kernel_inputs(policy, ctx, tasks)

        def full(_):
            ci, cs, _f = flex_pick_node_batch_topk(
                ki.est_usage, ki.reserved, ki.src_frac, requests,
                ki.penalty, w_load=ki.w_load, w_src=ki.w_src, cap=ki.cap,
                k=K, tile=tile, interpret=interpret)
            return ci, cs

        if not use_dedup:
            ci, cs = full(None)
            return ci, cs, ki

        # Score-bucket dedup: a task's score row is a function of
        # (r, penalty, cap, w_load, w_src, src) under the canonical hook
        # mapping, so equal key rows share one kernel row.
        B = int(dedup_buckets)
        key = jnp.concatenate([
            requests, ki.penalty[:, None], ki.cap[:, None],
            ki.w_load[:, None], ki.w_src[:, None],
            jnp.asarray(srcs, jnp.int32).astype(jnp.float32)[:, None],
        ], axis=1)                                        # (Q, R+5)
        eq = jnp.all(key[:, None, :] == key[None, :, :], axis=-1)
        first_occ = jnp.argmax(eq, axis=1).astype(jnp.int32)
        is_canon = first_occ == pos
        rank = jnp.cumsum(is_canon.astype(jnp.int32)) - 1
        n_unique = jnp.sum(is_canon.astype(jnp.int32))
        bucket_of = rank[first_occ]                       # (Q,)
        # Compact gather list: bucket b -> its representative task (pad
        # slots keep task 0 — scored redundantly, scattered to no one).
        slot_to_task = jnp.zeros((B,), jnp.int32).at[
            jnp.where(is_canon & (rank < B), rank, B)].set(pos, mode="drop")

        def deduped(_):
            g = slot_to_task
            ci, cs, _f = flex_pick_node_batch_topk(
                ki.est_usage, ki.reserved, ki.src_frac[g], requests[g],
                ki.penalty[g], w_load=ki.w_load[g], w_src=ki.w_src[g],
                cap=ki.cap[g], k=K, tile=tile, interpret=interpret)
            bo = jnp.clip(bucket_of, 0, B - 1)
            return ci[bo], cs[bo]

        ci, cs = jax.lax.cond(n_unique <= B, deduped, full, None)
        return ci, cs, ki

    def epoch(state):
        ns0, pending0, placement0, rounds0, sweeps0 = state
        cand_idx, cand_sc, ki = _sweep(ns0)
        # Tasks with no feasible node at sweep time finalize -1 now
        # (placement already -1): commits only ever ADD load and the
        # capacity filter is antitone in load.
        pending0 = pending0 & (cand_idx[:, 0] >= 0)
        cip = jnp.clip(cand_idx, 0, N - 1)                # gather-safe

        def round_body(s):
            ns, pending, placement, rounds, dnodes, dcnt, _stall = s
            # Clean candidate: first cached entry whose node is clean
            # (not committed-to since the sweep) — its cached score is
            # exact under the current state.
            dirty_mask = jnp.zeros((N,), bool).at[dnodes].set(
                True, mode="drop")
            usable = (cand_idx >= 0) & ~dirty_mask[cip]   # (Q, K)
            has = jnp.any(usable, axis=1)
            p = jnp.argmax(usable, axis=1)
            cand1 = jnp.take_along_axis(cand_idx, p[:, None], axis=1)[:, 0]
            sc1 = jnp.take_along_axis(cand_sc, p[:, None], axis=1)[:, 0]

            # Dirty refresh (candidate invalidation): recompute every
            # dirtied node's CURRENT score per task with the canonical
            # kernel-template arithmetic.  Dirty nodes are the only ones
            # whose cached scores are stale, and the compact dirty list
            # keeps this an O(Q^2) check with no N axis.
            dn = jnp.clip(dnodes, 0, N - 1)               # (Q,) padded
            dval = pos < dcnt
            est_d = ki.est_usage[dn]                      # (Q, R)
            res_d = ns.reserved[dn]
            feas_qd = None
            maxl_qd = None
            for j in range(R):
                l_j = ki.penalty[:, None] * est_d[:, j][None, :] \
                    + res_d[:, j][None, :]
                fit_j = l_j + requests[:, j][:, None] <= ki.cap[:, None]
                feas_qd = fit_j if feas_qd is None else feas_qd & fit_j
                maxl_qd = l_j if maxl_qd is None else jnp.maximum(maxl_qd,
                                                                  l_j)
            src_qd = (ns.src_count[dn].T[srcs]
                      .astype(jnp.float32)
                      / jnp.maximum(ns.n_tasks[dn], 1)
                      .astype(jnp.float32)[None, :])
            s_qd = -(ki.w_load[:, None] * maxl_qd
                     + ki.w_src[:, None] * src_qd)
            s_qd = jnp.where(feas_qd & dval[None, :], s_qd, NEG_INF)

            # Best and second-best DISTINCT dirty node per task (the same
            # node can sit in the list twice; duplicates carry the same
            # refreshed score and must not veto decisiveness).
            s_dbest = jnp.max(s_qd, axis=1)               # (Q,)
            c_dbest = dn[jnp.argmax(s_qd, axis=1)]
            s_dsecond = jnp.max(
                jnp.where(dn[None, :] != c_dbest[:, None], s_qd, NEG_INF),
                axis=1)
            m_db = tie_margin * (1.0 + jnp.abs(s_dbest))
            tail_real = cand_idx[:, K - 1] >= 0
            # dirty_ok: a dirty node wins when its refreshed score clears
            # the best clean alternative AND the runner-up dirty node by
            # the margin (strict domination needs no tie-break, so
            # jnp-vs-kernel ULP flavor cannot flip the argmax).  The
            # clean alternative is bounded by the first usable entry —
            # or, for a task whose cached list is exhausted (all K
            # entries dirty), by the sweep's K-th score: every unlisted
            # node scored below it then and clean nodes haven't moved.
            # (Post-commit rises of nodes committed THIS round are the
            # beat check's job, pre-commit bounds this one's.)
            clean_bound = jnp.where(
                has, sc1, jnp.where(tail_real, cand_sc[:, K - 1], NEG_INF))
            dirty_ok = ((s_dbest > NEG_INF / 2)
                        & (s_dbest - m_db > clean_bound)
                        & (s_dbest - m_db > s_dsecond))

            # In-round dup displacement: a task whose first-choice node is
            # already claimed by an EARLIER pending task slides to its next
            # unclaimed cached entry, so frontier contention resolves
            # inside one round instead of one commit per round.  Claims
            # come only from tasks that cannot take the dirty route
            # (~dirty_ok): the node's first claimant then provably keeps
            # its pick, so every skipped entry is either committed by that
            # claimant this round — and the post-commit beat check below
            # evaluates exactly its score after that commit, flagging the
            # displaced task if it could still reach the displaced score —
            # or the claimant is unsafe and the prefix cuts before the
            # displaced task anyway.
            cc1 = jnp.clip(cand1, 0, N - 1)
            first_at1 = jnp.full((N,), Q, jnp.int32).at[cc1].min(
                jnp.where(pending & has & ~dirty_ok, pos, Q))
            taken = usable & (first_at1[cip] < pos[:, None])
            usable2 = usable & ~taken
            has2 = jnp.any(usable2, axis=1)
            p2 = jnp.argmax(usable2, axis=1)
            cand = jnp.take_along_axis(cand_idx, p2[:, None], axis=1)[:, 0]
            sc2 = jnp.take_along_axis(cand_sc, p2[:, None], axis=1)[:, 0]

            # Decide each task's candidate, clean-vs-dirty, with every
            # comparison conservative by the relative tie margin:
            #   * clean wins when no dirty node comes within the margin
            #     of the (displaced) cached score — the cached list order
            #     then IS the current argmax order among clean nodes;
            #   * a dirty node wins when dirty_ok holds (above);
            #   * anything in between is ambiguous: the task blocks, and
            #     if it heads the queue the epoch stalls into a guarded
            #     re-sweep that re-decides it exactly.
            m_sc = tie_margin * (1.0 + jnp.abs(sc2))
            clean_ok = has2 & (s_dbest < sc2 - m_sc)
            use_dirty = ~clean_ok & dirty_ok
            cand = jnp.where(use_dirty, c_dbest, cand)
            sc = jnp.where(use_dirty, s_dbest, sc2)
            decided = clean_ok | use_dirty
            cc = jnp.clip(cand, 0, N - 1)

            live = pending & decided
            # dup: an earlier live task already picked this node.
            first_at = jnp.full((N,), Q, jnp.int32).at[cc].min(
                jnp.where(live, pos, Q))
            dup = live & (first_at[cc] < pos)
            lead = live & ~dup

            beat = _post_commit_beat(ns, ki, cc, sc, lead)

            # Commit the prefix before the first unsafe task.  A blocked
            # head (ambiguous clean-vs-dirty or exhausted list) commits
            # nothing and raises the stall flag — the epoch ends and a
            # fresh sweep re-decides it exactly.
            unsafe = pending & (~decided | dup | beat)
            first_unsafe = jnp.min(jnp.where(unsafe, pos, Q))
            commit = pending & (pos < first_unsafe)
            oki = commit.astype(jnp.int32)

            ns = _commit_state(ns, commit, cc)
            placement = jnp.where(commit, cand, placement)
            # Freshly dirtied nodes join the compact list (appends stay
            # < Q: each of the queue's Q tasks commits at most once).
            tpos = jnp.where(commit, dcnt + jnp.cumsum(oki) - 1, Q)
            dnodes = dnodes.at[tpos].set(cc, mode="drop")
            dcnt = dcnt + jnp.sum(oki)
            pending = pending & ~commit
            stall = jnp.any(pending) & ~jnp.any(commit)
            return ns, pending, placement, rounds + 1, dnodes, dcnt, stall

        inner = (ns0, pending0, placement0, rounds0,
                 jnp.full((Q,), N, jnp.int32), jnp.zeros((), jnp.int32),
                 jnp.zeros((), bool))
        ns, pending, placement, rounds, _, _, _ = jax.lax.while_loop(
            lambda s: jnp.any(s[1]) & ~s[6], round_body, inner)
        return ns, pending, placement, rounds, sweeps0 + 1

    init = (node, valid, jnp.full((Q,), -1, jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    node, _, placement, rounds, sweeps = jax.lax.while_loop(
        lambda s: jnp.any(s[1]), epoch, init)
    if with_rounds:
        return node, placement, rounds, sweeps
    return node, placement
