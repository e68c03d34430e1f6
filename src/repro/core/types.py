"""Core pytree types for the Flex resource manager.

All resource quantities are normalized to a single node's capacity
(C = 1.0 per resource).  Resources are indexed [CPU, MEM] (R = 2) but every
function is written generically over the trailing resource axis.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

# Resource axis indices.
CPU = 0
MEM = 1
NUM_RESOURCES = 2

# Priority classes (mirrors the Google-trace classification in the paper §2.2).
CLASS_BATCH = 0
CLASS_PRODUCTION = 1
CLASS_SYSTEM = 2
NUM_CLASSES = 3

# Number of hash buckets for task "sources" (users/jobs).  The Flex scoring
# rule prefers nodes with fewer tasks from the same source (§4.3).
NUM_SRC_BUCKETS = 64


class FlexParams(NamedTuple):
    """Static algorithm parameters (Table 1 + §5.1 defaults)."""

    qos_target: jnp.ndarray    # rho, cluster QoS target (paper: 0.99)
    alpha: jnp.ndarray         # multiplicative decrease constant (paper: 0.99)
    beta: jnp.ndarray          # additive-increase constant (paper: 1.0)
    p_init: jnp.ndarray        # initial estimation penalty (paper: 1.5)
    p_min: jnp.ndarray         # lower bound for P (paper: 1.0)
    p_max: jnp.ndarray         # upper clamp for P (beyond C/min-usage P is inert)
    theta: jnp.ndarray         # oversubscription factor for request feasibility
    w_load: jnp.ndarray        # scoring weight: prefer low load
    w_src: jnp.ndarray         # scoring weight: prefer few same-source tasks

    @staticmethod
    def default(
        qos_target: float = 0.99,
        alpha: float = 0.99,
        beta: float = 1.0,
        p_init: float = 1.5,
        p_min: float = 1.0,
        p_max: float = 16.0,
        theta: float = 1.0,
        w_load: float = 1.0,
        w_src: float = 0.25,
    ) -> "FlexParams":
        f = lambda x: jnp.asarray(x, jnp.float32)
        return FlexParams(
            qos_target=f(qos_target), alpha=f(alpha), beta=f(beta),
            p_init=f(p_init), p_min=f(p_min), p_max=f(p_max), theta=f(theta),
            w_load=f(w_load), w_src=f(w_src),
        )


class NodeState(NamedTuple):
    """Per-node cluster state (all shapes lead with N = num nodes)."""

    est_usage: jnp.ndarray   # (N, R) f32 — estimated load L-hat (from estimator)
    reserved: jnp.ndarray    # (N, R) f32 — requests reserved since last estimate refresh
    requested: jnp.ndarray   # (N, R) f32 — sum of requests of running tasks (R_i)
    n_tasks: jnp.ndarray     # (N,)   i32 — running task count
    src_count: jnp.ndarray   # (N, NUM_SRC_BUCKETS) i32 — running tasks per source bucket

    @staticmethod
    def zeros(n_nodes: int) -> "NodeState":
        return NodeState(
            est_usage=jnp.zeros((n_nodes, NUM_RESOURCES), jnp.float32),
            reserved=jnp.zeros((n_nodes, NUM_RESOURCES), jnp.float32),
            requested=jnp.zeros((n_nodes, NUM_RESOURCES), jnp.float32),
            n_tasks=jnp.zeros((n_nodes,), jnp.int32),
            src_count=jnp.zeros((n_nodes, NUM_SRC_BUCKETS), jnp.int32),
        )


class ControllerState(NamedTuple):
    """State of the estimation-penalty feedback controller (Alg. 3)."""

    penalty: jnp.ndarray   # () f32 — current P
    prev_qos: jnp.ndarray  # () f32 — Q(t-1)

    @staticmethod
    def init(params: FlexParams) -> "ControllerState":
        return ControllerState(
            penalty=jnp.asarray(params.p_init, jnp.float32),
            prev_qos=jnp.asarray(1.0, jnp.float32),
        )


class TaskSet(NamedTuple):
    """A workload trace: struct-of-arrays over T tasks.

    Usage at slot t for task j is materialized lazily:
      usage[j, t] = clip(mean[j] + std[j] * eps(j, t), 0, peak[j])
    where eps is a counter-based standard normal (no storage).
    """

    arrival: jnp.ndarray    # (T,) i32 — arrival slot
    duration: jnp.ndarray   # (T,) i32 — lifetime in slots (>= 1)
    request: jnp.ndarray    # (T, R) f32 — requested resources r_j
    mean_usage: jnp.ndarray  # (T, R) f32 — mean of the demand process
    std_usage: jnp.ndarray   # (T, R) f32 — std of the demand process
    peak_usage: jnp.ndarray  # (T, R) f32 — clip ceiling for demand
    ar_rho: jnp.ndarray     # (T,) f32 — AR(1) temporal correlation of demand
    priority: jnp.ndarray   # (T,) i32 — CLASS_*
    src: jnp.ndarray        # (T,) i32 — source bucket in [0, NUM_SRC_BUCKETS)

    @property
    def num_tasks(self) -> int:
        return self.arrival.shape[0]


class SimConfig(NamedTuple):
    """Static simulation configuration (§5.1)."""

    n_nodes: int = 4000
    n_slots: int = 288           # 24 h at 5-minute slots (trace sampling period)
    arrivals_per_slot: int = 4096  # static arrival-buffer width
    retry_capacity: int = 1024     # static retry-queue width
    wfs_iters: int = 4             # progressive-filling iterations for WFS
    demand_scale: float = 1.0      # §5.6 sensitivity knob (scales demand, not request)
    record_node_usage: bool = False  # keep (S, N, R) per-node usage in SlotMetrics
    use_kernel: bool = False       # route ScheduleOne through the fused Pallas
                                   # filter+score kernel (docs/kernels.md); policies
                                   # without the kernel_inputs hook keep the
                                   # reference path
    kernel_interpret: bool = False  # run that kernel via the Pallas interpreter
                                    # (pure XLA — CPU parity tests / debugging)
    admission_mode: str = "sequential"  # "sequential": one ScheduleOne scan step
                                        # per task; "wavefront": batched
                                        # conflict-resolution rounds over the
                                        # whole queue (docs/kernels.md) —
                                        # decision-identical, fewer node sweeps.
                                        # Policies without the kernel_inputs
                                        # hook keep the sequential scan.
    max_retries: int = 16          # admission failures before a task is dropped
                                   # (counted into n_rejected); static for jit
    estimator: str = ""            # registry name for the load estimator
                                   # (repro.estimators) when the caller passes
                                   # no estimator; "" = "current"
    reclamation: bool = False      # per-slot headroom-reclamation pass:
                                   # re-admit dropped tasks against predicted
                                   # usage via the 'reclaim' policy through
                                   # admit_queue_wavefront (docs/api.md,
                                   # "Headroom reclamation")
    reclaim_margin: float = 0.1    # safety-margin scale: the reclaim pass
                                   # caps nodes at 1 - reclaim_margin * P,
                                   # so QoS pressure (rising penalty P)
                                   # automatically backs reclamation off
    reclaim_pool: int = 256        # static width of the dropped-task pool
                                   # the reclaim pass draws from; pool
                                   # overflow counts into n_rejected
    retry_backoff: int = 0         # exponential retry backoff base: a task
                                   # whose admission failed a times waits
                                   # min(retry_backoff * 2**(a-1),
                                   # retry_backoff_cap) slots before its
                                   # next attempt.  0 = legacy fixed
                                   # re-queue (retry next slot),
                                   # bit-identical to pre-backoff decisions
    retry_backoff_cap: int = 64    # upper bound on the backoff delay (slots)
    retry_jitter: int = 0          # deterministic per-task retry jitter:
                                   # each task adds a fixed offset in
                                   # [0, retry_jitter] (fold_in'd from its
                                   # id) to every backoff delay, so a mass
                                   # crash doesn't produce a synchronized
                                   # retry storm.  0 = no jitter,
                                   # bit-identical to pre-jitter decisions
    faults: "object | None" = None  # repro.faults.FaultConfig: deterministic
                                    # fault injection + the QoS-pressure
                                    # degradation controller.  None =
                                    # bit-identical to the fault-free path
                                    # (docs/api.md, "Faults & degradation")
    migration: "object | None" = None  # repro.migration.MigrationConfig:
                                       # live re-placement of tasks resident
                                       # on draining/overloaded nodes through
                                       # the shared admission core (requires
                                       # faults; docs/api.md, "Migration").
                                       # None = bit-identical to the
                                       # migration-free path
    guard: "object | None" = None  # repro.guard.GuardConfig: estimator-
                                   # drift watchdog + circuit breaker
                                   # making overcommit misprediction-safe
                                   # (docs/api.md, "Guard").  None =
                                   # bit-identical to the unguarded path


class SlotMetrics(NamedTuple):
    """Per-slot time series emitted by the simulator (leading axis n_slots)."""

    usage: jnp.ndarray        # (S, R) cluster total usage / capacity
    requested: jnp.ndarray    # (S, R) cluster total admitted requests / capacity
    qos: jnp.ndarray          # (S,) Q(t)
    penalty: jnp.ndarray      # (S,) P
    usage_std: jnp.ndarray    # (S, R) std of per-node usage (load-balance metric)
    usage_mean: jnp.ndarray   # (S, R) mean of per-node usage
    n_running: jnp.ndarray    # (S,) running tasks
    n_rejected: jnp.ndarray   # (S,) cumulative rejected tasks
    node_usage: jnp.ndarray   # (S, N, R) per-node usage (machine-level analysis);
                              # (S, 0, R) unless SimConfig.record_node_usage —
                              # the O(S*N*R) array is opt-in
    est_usage: jnp.ndarray    # (S, R) cluster mean load estimate L-hat (the
                              # estimate admission used this slot)
    node_est: jnp.ndarray     # (S, N, R) per-node estimate (estimator-error
                              # analysis); (S, 0, R) unless record_node_usage
    node_requested: jnp.ndarray  # (S, N, R) per-node running requests
                                 # (overprovisioning / zombie-node analysis);
                                 # (S, 0, R) unless record_node_usage
    n_reclaimed: jnp.ndarray  # (S,) cumulative tasks admitted by the
                              # reclamation pass (0 unless SimConfig.reclamation)
    n_fault_evicted: jnp.ndarray    # (S,) cumulative tasks evicted by node
                                    # crashes (0 unless SimConfig.faults)
    n_degrade_evicted: jnp.ndarray  # (S,) cumulative tasks shed by the
                                    # degradation controller
    degraded: jnp.ndarray     # (S,) i32 — 1 while the degradation
                              # controller is in its pressure (shedding) mode
    n_migrated: jnp.ndarray   # (S,) cumulative tasks live-migrated off
                              # draining/overloaded nodes (0 unless
                              # SimConfig.migration)
    n_migration_failed: jnp.ndarray  # (S,) cumulative migration failures:
                                     # in-flight pool overflow falling back
                                     # to the evict-to-retry path
    guard_tripped: jnp.ndarray  # (S,) i32 breaker state governing the slot
                                # (0 closed / 1 open / 2 half-open); (S, 0)
                                # f32/i32 empty unless SimConfig.guard —
                                # guard_report raises without it
    n_guard_deferred: jnp.ndarray  # (S,) cumulative reclaim candidates
                                   # deferred by the breaker (suspension +
                                   # trickle clipping); (S, 0) unless guard
    guard_err_q: jnp.ndarray  # (S,) windowed drift-error quantile the
                              # breaker acted on; (S, 0) unless guard
    alloc_rows: jnp.ndarray   # (S,) i32 task rows the WFS allocator ran on:
                              # the ladder rung holding the running set, T
                              # on the full path (core/allocation.py)
    admit_steps: jnp.ndarray  # (S,) i32 steps of the sequential admission
                              # loop: one per ready queue entry, the batch's
                              # largest count under vmap (api/admission.py);
                              # 0 on the wavefront path


class SimResult(NamedTuple):
    metrics: SlotMetrics
    placement: jnp.ndarray      # (T,) i32 — node index or -1 (never admitted)
    admit_slot: jnp.ndarray     # (T,) i32 — slot the task was admitted, or -1
    qos_ok_slots: jnp.ndarray   # (T,) i32 — #slots the task met its QoS
    active_slots: jnp.ndarray   # (T,) i32 — #slots the task was running
