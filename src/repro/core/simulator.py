"""Vectorized discrete-time cluster simulator (paper §5 evaluation substrate).

Replaces the paper's event-driven Go Kubernetes simulator with a slot-based
JAX program: one ``lax.scan`` over 5-minute slots (the Google trace's usage
sampling period), an inner loop over the ready entries of the slot's
scheduling queue.
A 4000-node / 700k-task / 24-h evaluation is ONE compiled XLA program.

Placement is pluggable: the simulator is generic over a
``repro.api.PlacementPolicy`` object (plus an ``Estimator`` and a
``PenaltyController``), all static jit arguments.  A policy is named by
its registry name or passed as an object (``repro.api.resolve_policy``).

Per-slot pipeline (semantics match Kubernetes + Alg. 3):
  0. with faults (``SimConfig(faults=...)`` or an explicit
     ``fault_schedule``): evict tasks resident on crashed nodes back into
     the retry queue with exponential backoff, and — when the degradation
     controller's windowed cluster-QoS trend signals pressure — shed up to
     ``degrade_evict`` resident tasks, reclaimed/low-safety-cap tasks
     first (``repro.faults.degrade``); shed tasks drop into the reclaim
     pool when reclamation is on, else rejoin the retry queue
  1. recompute node aggregates from task lifetimes (handles task finishes)
  2. evolve each task's demand process (AR(1) around its mean, clipped at
     peak); fault surges multiply resident tasks' demand
  3. run the WFS allocator (per-node capacity honours fault flaps)
     -> realized usage per node, QoS q_j and Q(t); evicted tasks count as
     QoS violators in their eviction slot (an eviction IS a broken SLO)
  4. PeriodicEstimationPenaltyUpdate on the controller state
  5. refresh the load estimator, clear reservations; crashed/flapped nodes
     fold their lost capacity into ``reserved``
     (``admission.mask_unavailable``) so every policy avoids them
  5.5 with ``SimConfig(migration=...)``: live migration — tasks resident
     on draining (``FaultSchedule.draining`` advance warning) or
     overloaded nodes re-place onto healthy nodes through the SAME
     ``admit_queue`` path (the registered ``migrate`` policy), bounded by
     a per-slot bandwidth budget and an in-flight pool; successes keep
     their progress at ``migrate_cost`` extra slots of runtime, pool
     overflow falls back to the evict-to-retry path
  6. order the queue via the policy's queue_order hook (FIFO when absent)
     and admit retries + this slot's arrivals sequentially; tasks inside
     their backoff window (``SimConfig.retry_backoff``) stay queued
     without consuming an attempt
  7. with ``SimConfig(reclamation=True)``: merge permanently-dropped tasks
     into a bounded pool and re-admit it against PREDICTED headroom
     (allocation minus predicted usage minus a penalty-derived safety
     margin) via the ``reclaim`` policy — through the same
     ``admit_queue_wavefront`` path as primary admission

Each pass runs under a ``jax.named_scope`` (``flex.slot.aggregates``,
``flex.slot.admit``, ...), so a device trace shows it as the ops' path
(docs/kernels.md, "Named scopes").

``faults=None`` with ``retry_backoff=0`` (the defaults) compiles the exact
pre-fault program — bit-identical decisions (tests/test_faults.py asserts
the identity schedule matches it too).

Estimators are the stateful ``init_state``/``refresh`` pair of
``repro.estimators`` (windowed estimators carry static ring buffers
through the scan); legacy stateless estimators are adapted
bit-identically.  ``SimConfig(estimator="quantile")`` selects one by
registry name.

Execution substrate of step 6 (the hot path): every pass admits through
``admission.admit_queue``.  With ``SimConfig(use_kernel=True)`` every
ScheduleOne decision in the inner loop dispatches to the fused Pallas
filter+score kernel (``repro.kernels.flex_score``) for policies that
expose the ``kernel_inputs`` hook — one kernel call per placement, the
whole decision step compiles into the loop body.
``SimConfig(admission_mode="wavefront")`` replaces the per-task loop with
conflict-resolution rounds over the BATCHED kernel: one top-K sweep caches
per-task candidate lists and the rounds fall back through them,
re-sweeping only when a candidate list is provably stale (decisions stay
bit-identical to the sequential loop — docs/kernels.md; the sweep's
tuning is the admission core's constants).  ``kernel_interpret=True``
runs either kernel through the Pallas interpreter (pure XLA) so CPU tests
exercise the identical tiling/masking logic; see docs/kernels.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import allocation, qos
from repro.core.types import (
    NUM_RESOURCES,
    NUM_SRC_BUCKETS,
    FlexParams,
    NodeState,
    SimConfig,
    SimResult,
    SlotMetrics,
    TaskSet,
)
# Deliberately module-level despite the package cycle (repro.api.experiment
# imports this module): only the MODULE object is bound here — on the
# api-first import direction it is still partially initialized, which is
# fine because its attributes are touched at trace time only.  Importing
# names (classes/functions) from repro.api at this level would break that
# direction of the cycle.
from repro.api import admission
from repro.faults import degrade as _degrade
from repro.faults import injection as _inject

# fold_in data for the dedicated fault-sampling stream: outside [0, n_slots)
# for any plausible horizon, so the per-slot demand-noise stream
# (fold_in(key, slot)) is untouched and faults=None stays bit-identical.
_FAULT_STREAM = 0x7FFFFFFF
# dedicated stream for the per-task retry-jitter table (same reasoning:
# outside the slot range, so retry_jitter=0 stays bit-identical)
_JITTER_STREAM = 0x7FFFFFFE


def build_arrival_table(arrival: np.ndarray, n_slots: int,
                        width: int) -> np.ndarray:
    """(S, width) table of task indices arriving at each slot; -1 padded.

    Host-side preprocessing (numpy) — the simulator scans over this table.
    """
    arrival = np.asarray(arrival)
    table = np.full((n_slots, width), -1, dtype=np.int32)
    order = np.argsort(arrival, kind="stable")
    slots = arrival[order]
    start = 0
    for s in range(n_slots):
        end = start
        while end < len(slots) and slots[end] == s:
            end += 1
        take = min(end - start, width)
        table[s, :take] = order[start:start + take]
        start = end
    return table


def _node_aggregates(ts: TaskSet, placement, admit_slot, slot, n_nodes,
                     duration=None):
    """Recompute per-node request/count/src aggregates for the active set.

    ``duration`` overrides ``ts.duration`` (the migration pass charges
    ``migrate_cost`` extra slots of runtime per completed move).
    """
    placed = placement >= 0
    dur = ts.duration if duration is None else duration
    active = placed & (admit_slot < slot) & (slot <= admit_slot + dur)
    seg = jnp.clip(jnp.where(active, placement, 0), 0, n_nodes - 1)
    maskf = active.astype(jnp.float32)

    requested = jax.ops.segment_sum(ts.request * maskf[:, None], seg, n_nodes)
    n_tasks = jax.ops.segment_sum(active.astype(jnp.int32), seg, n_nodes)
    joint = seg * NUM_SRC_BUCKETS + ts.src
    src_count = jax.ops.segment_sum(
        active.astype(jnp.int32), joint, n_nodes * NUM_SRC_BUCKETS
    ).reshape(n_nodes, NUM_SRC_BUCKETS)
    return active, seg, requested, n_tasks, src_count


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "policy", "est", "ctrl_impl"),
)
def simulate_core(
    ts: TaskSet,
    arrival_table: jnp.ndarray,   # (S, A) i32 from build_arrival_table
    cfg: SimConfig,
    policy,                       # PlacementPolicy (hashable, static)
    params: FlexParams,
    key: jax.Array,
    est,                          # Estimator (hashable, static)
    ctrl_impl,                    # PenaltyController (hashable, static)
    fault_schedule=None,          # repro.faults.FaultSchedule (traced) or None
) -> SimResult:
    from repro.api.protocols import policy_queue_order

    if cfg.admission_mode not in ("sequential", "wavefront"):
        raise ValueError(
            f"unknown SimConfig.admission_mode {cfg.admission_mode!r}; "
            f"expected 'sequential' or 'wavefront'")
    n_nodes, n_slots = cfg.n_nodes, cfg.n_slots
    T = ts.num_tasks
    Qr = cfg.retry_capacity
    queue_order = policy_queue_order(policy)

    # Fault gating is PYTHON-LEVEL: faults=None traces the exact legacy
    # program (bit-identical decisions, zero overhead).
    fcfg = cfg.faults
    faults_on = fcfg is not None or fault_schedule is not None
    jitter_on = cfg.retry_jitter > 0
    backoff_on = faults_on or cfg.retry_backoff > 0 or jitter_on
    if jitter_on:
        # per-task deterministic jitter table, fold_in'd from task id on a
        # dedicated stream: desynchronizes post-crash retry storms without
        # touching the demand-noise or fault-sampling streams
        jit_tab = _inject.jitter_table(
            jax.random.fold_in(key, _JITTER_STREAM), T, cfg.retry_jitter)
    degrade_on = bool(faults_on and fcfg is not None and fcfg.degrade)
    if faults_on and fault_schedule is None:
        fault_schedule = _inject.sample_schedule(
            fcfg, jax.random.fold_in(key, _FAULT_STREAM), n_slots, n_nodes)
    if degrade_on:
        thr = (jnp.float32(fcfg.degrade_threshold)
               if fcfg.degrade_threshold > 0 else params.qos_target)
    # Degrade victims are shed INTO the reclaim pool when reclamation is
    # on (the penalty-gated reclaim pass re-admits them once pressure
    # clears); without reclamation they rejoin the retry queue + backoff.
    shed_to_pool = degrade_on and cfg.reclamation

    # Live migration (repro.migration): Python-gated exactly like faults —
    # migration=None traces the legacy program bit-identically.
    mcfg = cfg.migration
    migration_on = mcfg is not None
    if migration_on and not faults_on:
        raise ValueError(
            "SimConfig.migration requires fault injection (SimConfig.faults "
            "or an explicit fault_schedule): the migration pass is driven by "
            "the schedule's drain/crash tables")
    if migration_on:
        from repro.api.policies import MigratePolicy

        migrate_policy = MigratePolicy(margin_scale=mcfg.margin_scale)
        if fault_schedule.draining is None:
            # legacy schedules predate the drain table: all-False
            fault_schedule = fault_schedule._replace(
                draining=jnp.zeros((n_slots, n_nodes), bool))
        mig_B = max(min(int(mcfg.bandwidth), int(mcfg.pool_size)), 0)

    # Estimator-drift guard (repro.guard): Python-gated exactly like
    # faults/migration — guard=None traces the legacy program bit-identically.
    gcfg = cfg.guard
    guard_on = gcfg is not None
    if guard_on:
        from repro.guard import watchdog as _wd

    init = dict(
        node=NodeState.zeros(n_nodes),
        ctrl=ctrl_impl.init(params),
        est=est.init_state(n_nodes),
        placement=jnp.full((T,), -1, jnp.int32),
        admit_slot=jnp.full((T,), -1, jnp.int32),
        attempts=jnp.zeros((T,), jnp.int32),
        qos_ok=jnp.zeros((T,), jnp.int32),
        active_cnt=jnp.zeros((T,), jnp.int32),
        noise=jnp.zeros((T,), jnp.float32),
        retry=jnp.full((Qr,), -1, jnp.int32),
        n_rejected=jnp.zeros((), jnp.int32),
    )
    if cfg.reclamation:
        from repro.api.policies import ReclaimPolicy

        reclaim_policy = ReclaimPolicy(margin_scale=cfg.reclaim_margin)
        init["pool"] = jnp.full((cfg.reclaim_pool,), -1, jnp.int32)
        init["n_reclaimed"] = jnp.zeros((), jnp.int32)
    if backoff_on:
        init["next_try"] = jnp.zeros((T,), jnp.int32)
    if faults_on:
        init["n_fault_evicted"] = jnp.zeros((), jnp.int32)
    if degrade_on:
        init["qos_win"] = jnp.ones((fcfg.qos_window,), jnp.float32)
        init["n_degrade_evicted"] = jnp.zeros((), jnp.int32)
    if degrade_on and cfg.reclamation:
        init["reclaimed"] = jnp.zeros((T,), bool)
    if migration_on:
        init["mig_pool"] = jnp.full((mcfg.pool_size,), -1, jnp.int32)
        init["extra_slots"] = jnp.zeros((T,), jnp.int32)
        init["n_migrated"] = jnp.zeros((), jnp.int32)
        init["n_migration_failed"] = jnp.zeros((), jnp.int32)
    if guard_on:
        init["g_win"] = _wd.init_window(gcfg.window, NUM_RESOURCES)
        init["g_state"] = jnp.zeros((), jnp.int32)   # CLOSED
        init["g_timer"] = jnp.zeros((), jnp.int32)
        init["n_guard_deferred"] = jnp.zeros((), jnp.int32)

    demand_scale = jnp.asarray(cfg.demand_scale, jnp.float32)

    def _compact_ids(mask, width):
        """Ids of set tasks, lowest index first, (width,) padded with -1."""
        ids = allocation._active_ids(mask, width)
        return jnp.where(ids < T, ids, -1)

    def slot_step(carry, xs):
        if migration_on:
            slot, arrivals, slot_up, slot_cap, slot_mult, slot_drain = xs
        elif faults_on:
            slot, arrivals, slot_up, slot_cap, slot_mult = xs
        else:
            slot, arrivals = xs  # arrivals: (A,) i32

        # migrated tasks run migrate_cost extra slots (the transfer re-run)
        dur = (ts.duration + carry["extra_slots"] if migration_on
               else ts.duration)

        placement_in = carry["placement"]
        admit_in = carry["admit_slot"]
        attempts = carry["attempts"]
        if backoff_on:
            next_try = carry["next_try"]

        # --- 0. fault + degradation evictions ------------------------------
        # Before the aggregates, so freed capacity is admissible this slot.
        if faults_on:
            with jax.named_scope("flex.slot.faults"):
                resident = (placement_in >= 0) & (slot <= admit_in + dur)
                on_down = resident & ~slot_up[jnp.clip(placement_in, 0,
                                                       n_nodes - 1)]
                n_fault_ev = (carry["n_fault_evicted"]
                              + jnp.sum(on_down.astype(jnp.int32)))
                evict_mask = on_down
                degrade_mask = jnp.zeros((T,), bool)
                if degrade_on:
                    pressure = _degrade.under_pressure(carry["qos_win"], thr)
                    reclaimed = (carry["reclaimed"] if cfg.reclamation
                                 else jnp.zeros((T,), bool))
                    rank = _degrade.victim_rank(ts.priority, reclaimed,
                                                fcfg.degrade_spare_production)
                    degrade_mask = _degrade.select_victims(
                        resident & ~on_down & pressure, rank, admit_in,
                        n_slots, fcfg.degrade_evict)
                    evict_mask = on_down | degrade_mask
                    n_degrade_ev = (carry["n_degrade_evicted"]
                                    + jnp.sum(degrade_mask.astype(jnp.int32)))
                forced_retry = on_down
                if migration_on:
                    # Drain sources: fault-announced warning windows, plus —
                    # when overload_threshold > 0 — nodes whose previous-slot
                    # dominant estimate marks them as hotspots.  Down nodes are
                    # not sources (their residents were just crash-evicted).
                    drain_src = slot_drain
                    if mcfg.overload_threshold > 0:
                        drain_src = drain_src | (
                            jnp.max(carry["est"].est, axis=-1)
                            > mcfg.overload_threshold)
                    drain_src = drain_src & slot_up
                    want = (resident & ~evict_mask
                            & drain_src[jnp.clip(placement_in, 0,
                                                 n_nodes - 1)])
                    # Revalidate carried pool entries (node recovered / task
                    # finished / crash-evicted this slot -> silently leave),
                    # then merge newly-draining residents in, valid-first.
                    pool_prev = carry["mig_pool"]
                    ppqi = jnp.maximum(pool_prev, 0)
                    pool_keep = (pool_prev >= 0) & want[ppqi]
                    in_pool = jnp.zeros((T,), jnp.int32).at[ppqi].max(
                        pool_keep.astype(jnp.int32)).astype(bool)
                    merged_m = jnp.concatenate([
                        jnp.where(pool_keep, pool_prev, -1),
                        _compact_ids(want & ~in_pool, mcfg.pool_size)])
                    merged_m = merged_m[jnp.argsort(merged_m < 0, stable=True)]
                    mig_pool = merged_m[:mcfg.pool_size]
                    # Pool OVERFLOW cannot be moved before the fault lands:
                    # fall back to the evict-to-retry path (PR 8 semantics).
                    mig_over = merged_m[mcfg.pool_size:]
                    over_mask = jnp.zeros((T,), jnp.int32).at[
                        jnp.maximum(mig_over, 0)].max(
                            (mig_over >= 0).astype(jnp.int32)).astype(bool)
                    n_mig_failed = (
                        carry["n_migration_failed"]
                        + jnp.sum((mig_over >= 0).astype(jnp.int32)))
                    evict_mask = evict_mask | over_mask
                    forced_retry = forced_retry | over_mask
                placement_in = jnp.where(evict_mask, -1, placement_in)
                admit_in = jnp.where(evict_mask, -1, admit_in)
                # Evictions routed through the retry queue consume an attempt
                # and arm the exponential backoff (generalizing max_retries);
                # pool-shed victims wait on the reclaim pass instead.
                retry_evict = forced_retry if shed_to_pool else evict_mask
                attempts = attempts + retry_evict.astype(jnp.int32)
                ev_delay = _inject.backoff_delay(
                    attempts, cfg.retry_backoff, cfg.retry_backoff_cap)
                if jitter_on:
                    ev_delay = ev_delay + jit_tab
                next_try = jnp.where(retry_evict, slot + 1 + ev_delay,
                                     next_try)
                evict_requeue = retry_evict & (attempts <= cfg.max_retries)
            evict_exhausted = retry_evict & (attempts > cfg.max_retries)

        # --- 1. node aggregates for the active set -----------------------
        with jax.named_scope("flex.slot.aggregates"):
            active, seg, requested, n_tasks, src_count = _node_aggregates(
                ts, placement_in, admit_in, slot, n_nodes,
                dur if migration_on else None)

        # --- 2. demand process: AR(1) around the task mean ----------------
        with jax.named_scope("flex.slot.demand"):
            k_slot = jax.random.fold_in(key, slot)
            white = jax.random.normal(k_slot, (T,), jnp.float32)
            noise = ts.ar_rho * carry["noise"] + jnp.sqrt(
                jnp.maximum(1.0 - ts.ar_rho ** 2, 0.0)) * white
            demand = jnp.clip(
                ts.mean_usage + ts.std_usage * noise[:, None],
                0.0, ts.peak_usage) * demand_scale
            if faults_on:
                # black-swan surge: resident tasks on surging nodes spike
                task_mult = jnp.where(active, slot_mult[seg], 1.0)
                demand = demand * task_mult[:, None]
            demand = jnp.minimum(demand, 1.0)  # a task never exceeds one node

        # --- 3. allocation + QoS ------------------------------------------
        with jax.named_scope("flex.slot.allocate"):
            wfs_cap = (jnp.where(slot_up, slot_cap, 0.0) if faults_on else 1.0)
            alloc, node_usage, alloc_rows = allocation._wfs_compacted(
                demand, ts.request, placement_in, active, n_nodes,
                capacity=wfs_cap, iters=cfg.wfs_iters)
            q_task = qos.task_qos(alloc, demand, ts.request)
            if faults_on:
                # an eviction IS a broken SLO: victims count as active
                # violators in their eviction slot
                q_cluster = qos.cluster_qos(q_task & ~evict_mask,
                                            active | evict_mask)
            else:
                q_cluster = qos.cluster_qos(q_task, active)

            qos_ok = carry["qos_ok"] + (q_task & active).astype(jnp.int32)
            active_cnt = carry["active_cnt"] + active.astype(jnp.int32)

        # --- 4. penalty controller ----------------------------------------
        with jax.named_scope("flex.slot.penalty"):
            ctrl = ctrl_impl.update(carry["ctrl"], q_cluster, params)

            # --- 4.5 estimator-drift watchdog + breaker --------------------
            # The estimate refreshed LAST slot is what admission judged this
            # slot's active set by, so its error against this slot's realized
            # usage is the one-slot-ahead drift of analysis.estimator_error.
            # Runs BEFORE the refresh (the refreshed estimate hasn't been used
            # yet) and the resulting state governs THIS slot's passes.
            reclaim_penalty = ctrl.penalty
            if guard_on:
                g_err = _wd.drift_sample(carry["est"].est, node_usage)
                g_win = _wd.push_errors(carry["g_win"], g_err)
                g_err_q = _wd.trip_statistic(g_win, gcfg.err_quantile)
                g_state, g_timer, _ = _wd.breaker_step(
                    carry["g_state"], carry["g_timer"], g_err_q, gcfg)
                g_open = g_state == _wd.OPEN
                # confidence-gated reclamation: the reclaim/migrate passes see
                # a drift-scaled penalty, tightening their 1 - margin * P cap
                # continuously while the breaker is still closed (slot-constant
                # scalar -> rides the kernel cap template, wavefront sound)
                reclaim_penalty = ctrl.penalty * _wd.penalty_scale(g_err_q,
                                                                   gcfg)
                n_guard_def = carry["n_guard_deferred"]

        # --- 5. estimator refresh ------------------------------------------
        with jax.named_scope("flex.slot.estimate"):
            k_est = jax.random.fold_in(k_slot, 1)
            est_state = est.refresh(carry["est"], node_usage, k_est)
            est_adm = est_state.est
            if guard_on:
                # safe mode while OPEN: admission judges nodes by the estimate
                # blended back toward their residents' REQUESTED aggregates
                # (metrics keep reporting the raw estimate)
                est_adm = _wd.blend_estimate(est_state.est, requested,
                                             g_open, gcfg)
            node = NodeState(
                est_usage=est_adm,
                reserved=jnp.zeros_like(node_usage),
                requested=requested,
                n_tasks=n_tasks,
                src_count=src_count,
            )
            if faults_on:
                if migration_on:
                    # Proactive drain: draining/overloaded nodes stop admitting
                    # (DRAIN_LOAD on their reserved row), which simultaneously
                    # excludes them as migration TARGETS — the kernel's cap
                    # filter rejects them for every task, wavefront/dedup sound
                    # because the offset is node-side (docs/kernels.md,
                    # "Source-exclusion cap").
                    avail = slot_up & ~drain_src
                    f_off = admission.fault_load_offset(avail, slot_cap)
                else:
                    f_off = admission.fault_load_offset(slot_up, slot_cap)
                node = admission.mask_unavailable(node, f_off)

        # --- 5.5 live migration off draining nodes -------------------------
        # Runs BEFORE primary admission: keeping resident work beats
        # admitting new work.  Successes re-place next slot (the task still
        # runs on its source this slot) with admit_slot UNCHANGED — progress
        # kept — at migrate_cost extra slots of runtime.
        if migration_on:
            with jax.named_scope("flex.slot.migrate"):
                n_migrated = carry["n_migrated"]
                extra_slots = carry["extra_slots"]
                if mig_B > 0:
                    attempt = mig_pool[:mig_B]     # bandwidth budget this slot
                    avalid = attempt >= 0
                    aqi = jnp.maximum(attempt, 0)
                    node, m_idx = admission.admit_queue(
                        migrate_policy, node, ts.request[aqi], ts.src[aqi],
                        ts.priority[aqi], avalid, reclaim_penalty, params,
                        use_kernel=cfg.use_kernel,
                        interpret=cfg.kernel_interpret, batch_mode=True)
                    m_ok = avalid & (m_idx >= 0)
                    # scatter-max via helpers: padded entries (aqi clamped to
                    # 0) contribute no-op zeros instead of racing task 0's
                    # entry
                    moved = jnp.zeros((T,), jnp.int32).at[aqi].max(
                        m_ok.astype(jnp.int32)).astype(bool)
                    target = jnp.zeros((T,), jnp.int32).at[aqi].max(
                        jnp.where(m_ok, m_idx, 0))
                    placement_in = jnp.where(moved, target, placement_in)
                    extra_slots = extra_slots + jnp.where(
                        moved, jnp.int32(mcfg.migrate_cost), 0)
                    n_migrated = n_migrated + jnp.sum(m_ok.astype(jnp.int32))
                    # successes leave the pool; failures retry next slot
                    head_ok = jnp.concatenate([
                        m_ok, jnp.zeros((mcfg.pool_size - mig_B,), bool)])
                    mig_pool = jnp.where(head_ok, -1, mig_pool)
                    mig_pool = mig_pool[jnp.argsort(mig_pool < 0, stable=True)]

        # --- 6. scheduling: retries first, then new arrivals ---------------
        with jax.named_scope("flex.slot.admit"):
            queue_ids = jnp.concatenate([carry["retry"], arrivals])  # (Qr+A,)
            if queue_order is not None:
                # policy-defined priority queue (e.g. FlexL's LRF order, §4.3)
                pre_valid = queue_ids >= 0
                pre_qi = jnp.maximum(queue_ids, 0)
                order = queue_order(ts.request[pre_qi], ts.priority[pre_qi],
                                    pre_valid)
                queue_ids = queue_ids[order]
            valid = queue_ids >= 0
            qi = jnp.maximum(queue_ids, 0)
            if backoff_on:
                # tasks inside their backoff window stay queued, no attempt
                ready = valid & (slot >= next_try[qi])
                if faults_on:
                    # A retry against a cluster with NO admitting node is a
                    # guaranteed-infeasible attempt: defer eligibility
                    # (deferred tasks stay queued WITHOUT consuming an
                    # attempt, exactly
                    # like the backoff window) until at least one node admits.
                    any_admit = jnp.any(avail if migration_on else slot_up)
                    ready = ready & any_admit
            else:
                ready = valid
            wavefront = cfg.admission_mode == "wavefront"
            node, placed_idx = admission.admit_queue(
                policy, node, ts.request[qi], ts.src[qi], ts.priority[qi],
                ready, ctrl.penalty, params,
                use_kernel=cfg.use_kernel, interpret=cfg.kernel_interpret,
                batch_mode=wavefront)
            admit_steps = admission.loop_steps(policy, ready,
                                               batch_mode=wavefront)

        # --- placements, retry queue, fault-eviction merge ----------------
        with jax.named_scope("flex.slot.retry"):
            ok = ready & (placed_idx >= 0)
            # scatter placements (unique ids per slot; -1 slots write a
            # no-op max)
            cand_pl = jnp.where(ok, placed_idx, -1)
            cand_sl = jnp.where(ok, slot, -1)
            placement = placement_in.at[qi].max(cand_pl)
            admit_slot = admit_in.at[qi].max(cand_sl)

            # retry bookkeeping
            failed = ready & (placed_idx < 0)
            attempts = attempts.at[qi].add(failed.astype(jnp.int32))
            if backoff_on:
                delay = _inject.backoff_delay(
                    attempts[qi], cfg.retry_backoff, cfg.retry_backoff_cap)
                if jitter_on:
                    delay = delay + jit_tab[qi]
                # max-scatter: invalid queue slots (qi clamped to 0) contribute
                # a no-op 0 instead of clobbering task 0's entry, and per-task
                # next_try is monotone (later failures -> later slots + larger
                # delays), so max IS the latest write.
                next_try = next_try.at[qi].max(
                    jnp.where(failed, slot + 1 + delay, 0))
            eligible = failed & (attempts[qi] <= cfg.max_retries)
            if backoff_on:
                eligible = eligible | (valid & ~ready)   # deferred stay queued
            retry_order = jnp.argsort(~eligible, stable=True)  # eligible 1st
            sorted_ids = queue_ids[retry_order]
            n_eligible = jnp.sum(eligible.astype(jnp.int32))
            pos = jnp.arange(Qr, dtype=jnp.int32)
            new_retry = jnp.where(pos < n_eligible, sorted_ids[:Qr], -1)
            exhausted = failed & (attempts[qi] > cfg.max_retries)
            n_dropped = (jnp.sum(exhausted.astype(jnp.int32))
                         + jnp.maximum(n_eligible - Qr, 0))

            # merge fault-evicted tasks into the rebuilt retry queue (they were
            # resident, so they are NOT in this slot's queue): valid-first
            # stable compaction keeps FIFO order, overflow drops or pools.
            if faults_on:
                ev_ids = _compact_ids(evict_requeue, Qr)
                ev_lost = (jnp.sum(evict_requeue.astype(jnp.int32))
                           - jnp.sum((ev_ids >= 0).astype(jnp.int32)))
                merged_r = jnp.concatenate([new_retry, ev_ids])
                merged_r = merged_r[jnp.argsort(merged_r < 0, stable=True)]
                merge_over = merged_r[Qr:]                       # overflow ids
                new_retry = merged_r[:Qr]
                n_dropped = (n_dropped + ev_lost
                             + jnp.sum((evict_exhausted).astype(jnp.int32))
                             + jnp.sum((merge_over >= 0).astype(jnp.int32)))

        # --- 7. headroom reclamation (opt-in) ------------------------------
        if cfg.reclamation:
            with jax.named_scope("flex.slot.reclaim"):
                # Permanently-dropped tasks (out of retries, or retry-queue
                # overflow) enter a bounded pool instead of being rejected;
                # only POOL overflow counts into n_rejected.
                rank = jnp.argsort(retry_order)     # queue pos -> sorted pos
                pooled = exhausted | (eligible & (rank >= Qr))
                parts = [carry["pool"], jnp.where(pooled, queue_ids, -1)]
                if faults_on:
                    # fault evictions feed the pool too: retry overflow,
                    # exhausted evictions, and degrade-shed victims
                    pool_evict = evict_exhausted
                    if shed_to_pool:
                        pool_evict = pool_evict | degrade_mask
                    parts += [merge_over, _compact_ids(pool_evict, Qr)]
                merged = jnp.concatenate(parts)
                merged = merged[jnp.argsort(merged < 0, stable=True)]
                pool = merged[:cfg.reclaim_pool]
                n_rejected = carry["n_rejected"] + (
                    jnp.sum((merged >= 0).astype(jnp.int32))
                    - jnp.sum((pool >= 0).astype(jnp.int32)))
                if faults_on:
                    n_rejected = n_rejected + ev_lost

                # Re-admit the pool against predicted headroom: the reclaim
                # policy judges nodes by P * L-hat + reserved against the
                # penalty-derived cap, and the decisions run through the SAME
                # admit_queue_wavefront path as primary admission (the
                # reclaim policy's kernel_inputs hook + batch_mode).
                pvalid = pool >= 0
                pqi = jnp.maximum(pool, 0)
                if guard_on:
                    # breaker gating: full pool while CLOSED, suspended while
                    # OPEN, a bounded head-of-pool trickle while HALF_OPEN (the
                    # pool is compacted valid-first, so the head is FIFO)
                    g_allow = (jnp.arange(cfg.reclaim_pool, dtype=jnp.int32)
                               < _wd.reclaim_width(g_state, cfg.reclaim_pool,
                                                   gcfg))
                    n_guard_def = n_guard_def + jnp.sum(
                        (pvalid & ~g_allow).astype(jnp.int32))
                    pvalid = pvalid & g_allow
                node, r_idx = admission.admit_queue(
                    reclaim_policy, node, ts.request[pqi], ts.src[pqi],
                    ts.priority[pqi], pvalid, reclaim_penalty, params,
                    use_kernel=cfg.use_kernel, interpret=cfg.kernel_interpret,
                    batch_mode=True)
                r_ok = pvalid & (r_idx >= 0)
                placement = placement.at[pqi].max(jnp.where(r_ok, r_idx, -1))
                admit_slot = admit_slot.at[pqi].max(jnp.where(r_ok, slot, -1))
                n_reclaimed = (carry["n_reclaimed"]
                               + jnp.sum(r_ok.astype(jnp.int32)))
                pool = jnp.where(r_ok, -1, pool)
                pool = pool[jnp.argsort(pool < 0, stable=True)]
                if degrade_on:
                    # remember reclaim-admitted tasks: first in line when the
                    # degradation controller needs victims (low safety cap)
                    reclaimed_now = (reclaimed.astype(jnp.int32)
                                     .at[pqi].max(r_ok.astype(jnp.int32)))
                    reclaimed = reclaimed_now.astype(bool)
        else:
            n_rejected = carry["n_rejected"] + n_dropped
            n_reclaimed = jnp.zeros((), jnp.int32)

        # --- metrics --------------------------------------------------------
        with jax.named_scope("flex.slot.metrics"):
            gate = cfg.record_node_usage
            empty = jnp.zeros((0, NUM_RESOURCES), jnp.float32)
            req_total = jnp.sum(node.requested + node.reserved, axis=0)
            if faults_on:
                req_total = req_total - jnp.sum(f_off)  # undo fault offset
            zero_i = jnp.zeros((), jnp.int32)
            metrics = SlotMetrics(
                usage=jnp.sum(node_usage, axis=0) / n_nodes,
                requested=req_total / n_nodes,
                qos=q_cluster,
                penalty=ctrl.penalty,
                usage_std=jnp.std(node_usage, axis=0),
                usage_mean=jnp.mean(node_usage, axis=0),
                n_running=jnp.sum(active.astype(jnp.int32)),
                n_rejected=n_rejected,
                node_usage=node_usage if gate else empty,
                est_usage=jnp.sum(est_state.est, axis=0) / n_nodes,
                node_est=est_state.est if gate else empty,
                node_requested=requested if gate else empty,
                n_reclaimed=n_reclaimed,
                n_fault_evicted=n_fault_ev if faults_on else zero_i,
                n_degrade_evicted=n_degrade_ev if degrade_on else zero_i,
                degraded=(pressure.astype(jnp.int32) if degrade_on
                          else zero_i),
                n_migrated=n_migrated if migration_on else zero_i,
                n_migration_failed=n_mig_failed if migration_on else zero_i,
                # guard leaves are EMPTY (stacked (S, 0)) when guard=None —
                # guard_report raises on .size == 0 and summarize degrades
                # gracefully, mirroring the node_usage gating above
                guard_tripped=(g_state if guard_on
                               else jnp.zeros((0,), jnp.int32)),
                n_guard_deferred=(n_guard_def if guard_on
                                  else jnp.zeros((0,), jnp.int32)),
                guard_err_q=(g_err_q if guard_on
                             else jnp.zeros((0,), jnp.float32)),
                alloc_rows=alloc_rows,
                admit_steps=admit_steps,
            )

        new_carry = dict(
            node=node, ctrl=ctrl, est=est_state, placement=placement,
            admit_slot=admit_slot, attempts=attempts, qos_ok=qos_ok,
            active_cnt=active_cnt, noise=noise, retry=new_retry,
            n_rejected=n_rejected,
        )
        if cfg.reclamation:
            new_carry["pool"] = pool
            new_carry["n_reclaimed"] = n_reclaimed
        if backoff_on:
            new_carry["next_try"] = next_try
        if faults_on:
            new_carry["n_fault_evicted"] = n_fault_ev
        if degrade_on:
            new_carry["qos_win"] = _degrade.push_window(carry["qos_win"],
                                                        q_cluster)
            new_carry["n_degrade_evicted"] = n_degrade_ev
        if degrade_on and cfg.reclamation:
            new_carry["reclaimed"] = reclaimed
        if migration_on:
            new_carry["mig_pool"] = mig_pool
            new_carry["extra_slots"] = extra_slots
            new_carry["n_migrated"] = n_migrated
            new_carry["n_migration_failed"] = n_mig_failed
        if guard_on:
            new_carry["g_win"] = g_win
            new_carry["g_state"] = g_state
            new_carry["g_timer"] = g_timer
            new_carry["n_guard_deferred"] = n_guard_def
        return new_carry, metrics

    slots = jnp.arange(n_slots, dtype=jnp.int32)
    if faults_on:
        xs = (slots, arrival_table, fault_schedule.node_up,
              fault_schedule.capacity, fault_schedule.demand_mult)
        if migration_on:
            xs = xs + (fault_schedule.draining,)
    else:
        xs = (slots, arrival_table)
    final, metrics = jax.lax.scan(slot_step, init, xs)

    return SimResult(
        metrics=metrics,
        placement=final["placement"],
        admit_slot=final["admit_slot"],
        qos_ok_slots=final["qos_ok"],
        active_slots=final["active_cnt"],
    )


def _resolve(policy, params, estimator, est_noise_std, controller,
             cfg: SimConfig | None = None):
    """Normalize the open-API knobs into static jit arguments.

    Estimator precedence: an explicit ``estimator`` argument (object or
    registry name) wins, then a non-empty ``SimConfig.estimator``, then
    ``"current"``.
    """
    from repro.api.policies import (AimdPenaltyController, resolve_estimator)
    from repro.api.protocols import (policy_default_params,
                                     policy_prepare_params)
    from repro.api.registry import resolve_policy

    policy = resolve_policy(policy)
    if params is None:
        params = policy_default_params(policy)
    params = policy_prepare_params(policy, params)
    if estimator is None:
        estimator = (cfg.estimator if cfg is not None and cfg.estimator
                     else "current")
    est = resolve_estimator(estimator, est_noise_std)
    ctrl_impl = controller if controller is not None else AimdPenaltyController()
    return policy, params, est, ctrl_impl


def simulate(ts: TaskSet, arrival_table: jnp.ndarray, cfg: SimConfig,
             policy, params: FlexParams, key: jax.Array,
             est_noise_std: float = 0.0, estimator=None, controller=None,
             fault_schedule=None) -> SimResult:
    """Jitted simulation with policy/estimator/controller normalization.

    ``policy`` may be a registry name or a PlacementPolicy object;
    ``estimator`` takes a ``repro.estimators`` registry name or an
    estimator object (stateful or legacy stateless), and
    ``SimConfig(estimator=...)`` selects one from the config when it is
    None (``"current"`` when both are unset).
    ``fault_schedule`` injects an explicit ``repro.faults.FaultSchedule``
    (overrides the sampling that ``SimConfig(faults=...)`` would do).
    """
    policy, params, est, ctrl_impl = _resolve(
        policy, params, estimator, est_noise_std, controller, cfg)
    return simulate_core(ts, arrival_table, cfg, policy, params, key,
                         est, ctrl_impl, fault_schedule)


def run(ts: TaskSet, cfg: SimConfig, policy,
        params: FlexParams | None = None, seed: int = 0,
        **kw) -> SimResult:
    """Convenience entry point: host-side table build + jitted simulate."""
    table = build_arrival_table(np.asarray(ts.arrival), cfg.n_slots,
                                cfg.arrivals_per_slot)
    return simulate(ts, jnp.asarray(table), cfg, policy, params,
                    jax.random.PRNGKey(seed), **kw)
