"""Continuous-batching serving engine whose admission control IS Flex.

This is the paper's scenario re-instantiated for LLM inference:

  node      -> inference replica (a model instance with a KV-token budget)
  request r -> prompt_len + max_tokens the client DECLARES (over-estimated,
               exactly like Google-trace resource requests)
  usage L   -> prompt_len + tokens actually generated so far (the real,
               growing KV footprint)
  QoS q_j   -> request finishes without eviction
  penalty P -> Alg. 3 feedback on the cluster QoS signal

Admission runs through the SAME core as the discrete-time simulator
(``repro.api.admission`` + the policy registry), so the serving path and
the simulator cannot drift apart.  ``EngineConfig.policy`` is a policy
name registered in ``repro.api.registry`` or a policy object, resolved by
the simulator's own ``resolve_policy``; unknown names raise ``KeyError``
with the registered list.  The two the serving benchmark compares:

  ``least-fit``: admit only if the DECLARED footprints of all
    co-resident requests fit the replica budget (request-based baseline);
  ``flex-f`` (the default): admit if ``P * estimated usage + reserved +
    r`` fits — usage-based ULB placement with the estimation-penalty
    controller and same-source spreading.

Replicas are mapped onto the simulator's :class:`NodeState` with TWO
resources, both normalized to capacity 1.0 (the canonical hook mapping
the wavefront conflict checks assume, docs/kernels.md):

  axis 0 (the "CPU" slot)  -> active-request slots / max_active_per_replica
  axis 1 (the "MEM" slot)  -> KV tokens / kv_budget_tokens

so the slot cap ``n_active < max_active_per_replica`` is just the
capacity filter on axis 0, and LRF-style queue orders (``flex-l``,
``flex-priority``) sort by the KV footprint exactly as they sort by
memory in the cluster.  Requests carry a ``src`` bucket (client/tenant
hash) and a ``priority`` class, so same-source spreading and
priority-aware headroom work unchanged.

Three admission execution modes (``EngineConfig.admission_mode``), all
decision-identical:

  ``"eager"``      — one ``feasible``/``score`` evaluation per request,
    eager jnp on the replica table: the pre-batching engine structure,
    kept as the reference baseline the serving benchmark measures
    speedups against;
  ``"sequential"`` — one jitted ``admit_queue`` call per step: the
    loop of ``admit_one`` over its ready entries, whole pending queue
    per launch;
  ``"wavefront"``  — ``admit_queue(batch_mode=True)``: the batched
    top-K candidate kernel with conflict-resolution rounds, scoring the
    whole queue per node-table sweep (tuned by the admission core's
    constants).  The default.

Straggler mitigation: replicas report a step-time EMA; slow replicas get
their load ESTIMATE inflated by ``straggler_weight * max(ema/mean - 1,
0)`` (in capacity units), so they both score worse and admit less — and
replicas slower than ``drain_slowdown``x the mean are drained outright
(load pinned above any capacity).  Folding the penalty into the load
(instead of bolting a per-node term onto the score, as the pre-batching
engine did) is what lets every admission mode share the kernel template
bit-for-bit.

When a replica overflows (demands exceed the budget), the most recently
admitted requests are evicted and re-queued — the QoS violation the
controller reacts to.  Eviction invariants (tests/test_serving_engine.py):
newest-admission-first victim order, evicted requests re-enter the queue
FIFO-stable ahead of fresh arrivals, the eviction counter is monotone,
and no request is ever both ``done`` and resident.

With ``EngineConfig(migration=...)`` (requires ``faults``) crashes are
ANNOUNCED ``FaultConfig.warn_slots`` steps ahead and the drain pass moves
residents' KV-token fraction to healthy replicas through the registered
``migrate`` policy — progress kept at a ``migrate_cost`` transfer-latency
stall — and the overflow path tries migrate-then-shed before paying the
evict-and-restart tax (docs/api.md, "Migration").

The engine is transport/model agnostic: ``decode_fn`` is any callable
that advances each replica one decode step (the real-model driver in
``launch/serve.py`` plugs a jitted model.decode in; unit tests use a
stub).  Open-loop arrival driving lives in ``repro.serving.stream``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import admission
from repro.api.protocols import policy_prepare_params, policy_queue_order
from repro.api.registry import resolve_policy
from repro.core.penalty import update_penalty
from repro.core.types import (
    CLASS_PRODUCTION,
    CPU,
    MEM,
    NUM_SRC_BUCKETS,
    ControllerState,
    FlexParams,
    NodeState,
)
from repro.estimators import resolve_estimator

# Engine resource axes on the shared (N, R) NodeState (see module doc).
SLOT_AXIS = CPU   # active-request slots, normalized by max_active_per_replica
KV_AXIS = MEM     # KV tokens, normalized by kv_budget_tokens

# Effective load pinned onto drained replicas: the shared sentinel from the
# admission core (one constant for engine drains, fault offsets, and
# migration source exclusion — satellite of ISSUE 9).
_DRAIN_LOAD = admission.DRAIN_LOAD

ADMISSION_MODES = ("eager", "sequential", "wavefront")


@dataclasses.dataclass
class Request:
    rid: int
    prompt_len: int
    max_tokens: int            # declared budget (the "request")
    true_tokens: int           # actual generation length (hidden "demand")
    src: int = 0               # client/tenant hash bucket (same-source rule)
    priority: int = 0          # CLASS_* (flex-priority headroom)
    generated: int = 0
    replica: int = -1
    evictions: int = 0
    done: bool = False
    stall: int = 0             # transfer-latency steps left after a live
                               # migration (no tokens generated while > 0)
    migrations: int = 0        # completed live migrations (progress kept)

    @property
    def declared_footprint(self) -> int:
        return self.prompt_len + self.max_tokens

    @property
    def current_footprint(self) -> int:
        return self.prompt_len + self.generated


@dataclasses.dataclass
class EngineConfig:
    n_replicas: int = 4
    kv_budget_tokens: int = 8192       # per-replica KV capacity
    policy: "str | object" = "flex-f"  # registry name or policy object
    estimator: "str | object" = "current"  # repro.estimators registry name
                                           # (or estimator object) feeding the
                                           # FLEX load estimate L-hat
    max_active_per_replica: int = 64
    straggler_weight: float = 0.5      # load inflation per unit slowdown
    drain_slowdown: float = 3.0        # drain replicas this much slower
    qos_target: float = 0.99
    admission_mode: str = "wavefront"  # "eager" | "sequential" | "wavefront"
    admit_batch: int = 256             # static pad width per admission call;
                                       # longer queues admit in chunks that
                                       # carry the reservation state exactly
    kernel_interpret: bool = False     # run Pallas kernels via the interpreter
                                       # (CPU parity testing; off = reference
                                       # einsum on non-TPU backends)
    faults: "object | None" = None     # repro.faults.FaultConfig: replica
                                       # crash/recover windows, straggler
                                       # storms, and QoS-pressure admission
                                       # brownout (``degrade=True``).  None =
                                       # bit-identical to the fault-free
                                       # engine (docs/api.md, "Faults &
                                       # degradation")
    migration: "object | None" = None  # repro.migration.MigrationConfig:
                                       # crashes announce ``warn_slots``
                                       # steps ahead and residents move
                                       # their KV-token fraction to a
                                       # healthy replica (progress kept, a
                                       # ``migrate_cost`` stall) instead of
                                       # the evict+restart path; overflow
                                       # tries migrate-then-shed.  Requires
                                       # ``faults``.  None = bit-identical
                                       # to the migration-free engine
                                       # (docs/api.md, "Migration")
    guard: "object | None" = None      # repro.guard.GuardConfig: estimator-
                                       # drift watchdog + circuit breaker —
                                       # while OPEN, estimator-driven
                                       # (sub-production) admission defers
                                       # brownout-style and the estimate
                                       # snapshot blends toward declared
                                       # footprints.  None = bit-identical
                                       # to the unguarded engine
                                       # (docs/api.md, "Guard")


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    admitted: int = 0
    finished: int = 0
    evicted_events: int = 0
    qos_series: List[float] = dataclasses.field(default_factory=list)
    penalty_series: List[float] = dataclasses.field(default_factory=list)
    util_series: List[float] = dataclasses.field(default_factory=list)
    tokens_generated: int = 0
    decisions: int = 0         # admission decisions evaluated (incl. blocked)
    admit_latency_s: List[float] = dataclasses.field(default_factory=list)
                               # wall seconds per admission pass (one per step
                               # with a non-empty queue)
    fault_evictions: int = 0   # requests evicted by replica crashes
    brownout_steps: int = 0    # steps the brownout controller was engaged
    brownout_deferred: int = 0  # admission decisions deferred by brownout
    migrations: int = 0        # live migrations completed (progress kept)
    migration_failed: int = 0  # migration candidates that fell back to the
                               # evict-and-restart path (no feasible target
                               # before the fault landed / budget exceeded)
    guard_trips: int = 0       # breaker transitions into OPEN (drift trips)
    guard_open_steps: int = 0  # steps spent with the breaker OPEN
    guard_deferred: int = 0    # admission decisions deferred by the breaker
                               # (suspension while OPEN + trickle clipping
                               # while HALF_OPEN)


class ServeEngine:
    def __init__(self, cfg: EngineConfig,
                 decode_fn: Optional[Callable[[int, List[Request]], float]]
                 = None,
                 flex_params: Optional[FlexParams] = None,
                 seed: int = 0):
        if cfg.admission_mode not in ADMISSION_MODES:
            raise ValueError(
                f"unknown admission_mode {cfg.admission_mode!r}; "
                f"one of {ADMISSION_MODES}")
        self.cfg = cfg
        self.policy = resolve_policy(cfg.policy)
        self.decode_fn = decode_fn or self._stub_decode
        base = flex_params or FlexParams.default(
            qos_target=cfg.qos_target,
            theta=getattr(self.policy, "default_theta", 1.0))
        self.params = policy_prepare_params(self.policy, base)
        self.ctrl = ControllerState.init(self.params)
        self.queue: Deque[Request] = deque()
        self.active: Dict[int, List[Request]] = {
            i: [] for i in range(cfg.n_replicas)}
        self.step_time_ema = np.ones(cfg.n_replicas)
        self.stats = EngineStats()
        self._ever_violated: set = set()
        self._rng = np.random.default_rng(seed)
        # Fault injection (repro.faults): eager per-step sampling from a
        # DEDICATED rng stream, so cfg.faults=None engines consume exactly
        # the same randomness as before (bit-identical parity).
        self._down_until = np.full(cfg.n_replicas, -1, np.int64)
        self._storm_slowdown = np.ones(cfg.n_replicas)
        self._storm_until = np.full(cfg.n_replicas, -1, np.int64)
        if cfg.faults is not None:
            self._fault_rng = np.random.default_rng((seed + 1) * 0x5EED)
        # Live migration (repro.migration): crashes announce warn_slots
        # steps ahead (down window [_down_from, _down_until)); residents
        # of announced replicas re-place through the shared admission core
        # via the registered "migrate" policy, keeping their progress.
        if cfg.migration is not None and cfg.faults is None:
            raise ValueError(
                "EngineConfig.migration requires EngineConfig.faults: the "
                "migration pass is driven by the crash announcements")
        self._down_from = np.full(cfg.n_replicas, -1, np.int64)
        self._mig_left = 0
        if cfg.migration is not None:
            from repro.api.policies import MigratePolicy

            self._migrate_fn = admission.make_queue_admitter(
                MigratePolicy(margin_scale=cfg.migration.margin_scale),
                self.params,
                batch_mode=cfg.admission_mode == "wavefront",
                interpret=cfg.kernel_interpret)
        # Load estimator (same registry as the simulator): refreshed once
        # per round from measured KV footprints; ``_usage_snap`` holds its
        # estimate — for the default "current" estimator that is exactly
        # the measured usage (token counts are integers, so the float32
        # round-trip through the estimator state is lossless).
        self.estimator = resolve_estimator(cfg.estimator)
        self._est_state = self.estimator.init_state(cfg.n_replicas, 1)
        self._est_key = jax.random.PRNGKey(seed)
        self._usage_snap = np.zeros(cfg.n_replicas)
        self._declared_snap = np.zeros(cfg.n_replicas)
        # Estimator-drift guard (repro.guard): the SAME jnp watchdog as the
        # simulator scan, run eagerly once per step on the KV-footprint
        # estimate.  Consumes no randomness, so guard=None engines are
        # bit-identical structurally (parity-tested in tests/test_guard.py).
        if cfg.guard is not None:
            from repro.guard import watchdog as _wdmod

            self._wd = _wdmod
            self._g_win = _wdmod.init_window(cfg.guard.window, 1)
            self._g_state = _wdmod.CLOSED
            self._g_timer = 0
            self._g_err_q = 0.0
        # One compiled admission entry per engine (jit re-specializes per
        # padded queue width): the engine-side batched front-end onto the
        # shared admission core.
        self._admit_fn = admission.make_queue_admitter(
            self.policy, self.params,
            batch_mode=cfg.admission_mode == "wavefront",
            interpret=cfg.kernel_interpret)
        # driver hooks (real-model serving wires prefill/KV surgery here)
        self.on_admit: Optional[Callable[[Request], None]] = None
        self.on_evict: Optional[Callable[[Request], None]] = None

    # ---------------- replica state -> NodeState ----------------

    def _usage(self) -> np.ndarray:
        return np.array([sum(r.current_footprint for r in self.active[i])
                         for i in range(self.cfg.n_replicas)], float)

    def _declared(self) -> np.ndarray:
        return np.array([sum(r.declared_footprint for r in self.active[i])
                         for i in range(self.cfg.n_replicas)], float)

    def _straggler_extra(self) -> np.ndarray:
        """(N,) load inflation, in capacity units, from the step-time EMA.

        Crashed replicas (fault injection) are drained outright through the
        same mechanism: the drain load rides both the estimate and the
        declared load in ``node_state``, so every policy and execution
        mode rejects them with no engine-specific branches — the engine
        analogue of ``admission.mask_unavailable``.
        """
        cfg = self.cfg
        rel = self.step_time_ema / max(float(self.step_time_ema.mean()), 1e-9)
        extra = cfg.straggler_weight * np.maximum(rel - 1.0, 0.0)
        if cfg.drain_slowdown > 0:
            extra = np.where(rel >= cfg.drain_slowdown, _DRAIN_LOAD, extra)
        if cfg.faults is not None:
            extra = np.where(self._down_until > self.stats.steps,
                             _DRAIN_LOAD, extra)
        return extra.astype(np.float32)

    # ---------------- fault injection (repro.faults) ----------------

    def _inject_faults(self):
        """Sample this step's replica crashes + straggler storms.

        Crashes: every resident request of a newly-down replica is evicted
        and re-queued FIFO-stable (restart semantics, same bookkeeping as
        the overflow path) — each one a QoS violation the controller sees.
        Storms: stormed replicas report ``storm_slowdown``-inflated decode
        step times, so the EXISTING straggler mitigation (EMA load
        inflation + drain) is what reacts — fault injection exercises it,
        it does not replace it.
        """
        fc = self.cfg.faults
        t = self.stats.steps
        rng = self._fault_rng
        n = self.cfg.n_replicas
        up = self._down_until <= t
        crash = up & (rng.random(n) < fc.crash_rate)
        if fc.burst_slot >= 0 and t == fc.burst_slot:
            burst = np.zeros(n, bool)
            burst[:int(round(fc.burst_frac * n))] = True
            crash |= up & burst
        if self.cfg.migration is not None:
            # With migration on, a sampled crash is ANNOUNCED warn_slots
            # steps ahead: the replica keeps decoding through the warning
            # window (down window [_down_from, _down_until)) while the
            # drain pass moves its residents; whatever is still resident
            # when the crash LANDS pays the legacy evict-and-restart tax.
            # Same rng draws as the legacy path — stream parity.
            warn = max(int(fc.warn_slots), 0)
            self._down_from = np.where(crash, t + warn, self._down_from)
            self._down_until = np.where(
                crash, t + warn + max(int(fc.crash_duration), 1),
                self._down_until)
            land = (self._down_from == t) & (self._down_until > t)
            evict_replicas = np.flatnonzero(land)
        else:
            self._down_until = np.where(
                crash, t + max(int(fc.crash_duration), 1), self._down_until)
            evict_replicas = np.flatnonzero(crash)
        for i in evict_replicas:
            victims = self.active[int(i)]
            self.active[int(i)] = []
            if self.cfg.migration is not None:
                # residents still here at landing could not be moved in
                # time: the migrate attempt failed into the legacy path
                self.stats.migration_failed += len(victims)
            evicted = []
            for victim in reversed(victims):     # newest admission first
                victim.evictions += 1
                victim.replica = -1
                victim.generated = 0             # restart (no KV migration)
                victim.done = False
                self._ever_violated.add(victim.rid)
                self.stats.fault_evictions += 1
                if self.on_evict is not None:
                    self.on_evict(victim)
                evicted.append(victim)
            # extendleft reverses: victims return in admission order at
            # the head of the queue, ahead of fresh arrivals
            self.queue.extendleft(evicted)
        calm = self._storm_until <= t
        storm = calm & (rng.random(n) < fc.storm_rate)
        self._storm_until = np.where(
            storm, t + max(int(fc.storm_duration), 1), self._storm_until)
        self._storm_slowdown = np.where(
            self._storm_until > t, fc.storm_slowdown, 1.0)

    def _brownout_pressure(self) -> bool:
        """Windowed cluster-QoS trend below the pressure threshold?"""
        fc = self.cfg.faults
        if fc is None or not fc.degrade:
            return False
        window = self.stats.qos_series[-int(fc.qos_window):]
        if not window:
            return False
        thr = (fc.degrade_threshold if fc.degrade_threshold > 0
               else self.cfg.qos_target)
        return float(np.mean(window)) < thr

    def node_state(self) -> NodeState:
        """The replica table as the simulator's NodeState (see module doc).

        Built from the ROUND-START snapshots (``_usage_snap`` /
        ``_declared_snap``): requests admitted this round are accounted
        via the reservation scatters of ``admit_one``/``_commit_state``
        only, never double-counted — the paper's stale-measurement
        semantics, shared with the simulator slot loop.
        """
        cfg = self.cfg
        n = cfg.n_replicas
        kv_cap = float(cfg.kv_budget_tokens)
        slot_cap = float(cfg.max_active_per_replica)
        n_active = np.array([len(self.active[i]) for i in range(n)],
                            np.float32)
        s_extra = self._straggler_extra()

        est = np.zeros((n, 2), np.float32)
        est[:, KV_AXIS] = self._usage_snap / kv_cap + s_extra
        reserved = np.zeros((n, 2), np.float32)
        reserved[:, SLOT_AXIS] = n_active / slot_cap
        requested = np.zeros((n, 2), np.float32)
        requested[:, KV_AXIS] = self._declared_snap / kv_cap + s_extra
        src_count = np.zeros((n, NUM_SRC_BUCKETS), np.int32)
        for i in range(n):
            for r in self.active[i]:
                src_count[i, r.src % NUM_SRC_BUCKETS] += 1
        return NodeState(
            est_usage=jnp.asarray(est),
            reserved=jnp.asarray(reserved),
            requested=jnp.asarray(requested),
            n_tasks=jnp.asarray(n_active.astype(np.int32)),
            src_count=jnp.asarray(src_count),
        )

    def _task_arrays(self, reqs: List[Request]):
        """(Q, 2) request vectors + (Q,) src/priority for the shared core."""
        cfg = self.cfg
        kv_cap = float(cfg.kv_budget_tokens)
        slot_cap = float(cfg.max_active_per_replica)
        q = len(reqs)
        r = np.zeros((q, 2), np.float32)
        r[:, KV_AXIS] = [req.declared_footprint / kv_cap for req in reqs]
        r[:, SLOT_AXIS] = 1.0 / slot_cap
        srcs = np.array([req.src % NUM_SRC_BUCKETS for req in reqs], np.int32)
        prios = np.array([req.priority for req in reqs], np.int32)
        return r, srcs, prios

    # ---------------- admission (the Flex core) ----------------

    def _guard_observe(self, measured: np.ndarray):
        """One watchdog step: drift of LAST round's estimate vs this round's
        measured usage (the one-slot-ahead error the simulator monitors),
        normalized to KV-capacity units.  Runs BEFORE the estimator refresh
        — the refreshed estimate hasn't gated any admission yet."""
        gcfg = self.cfg.guard
        kv_cap = float(self.cfg.kv_budget_tokens)
        prev = np.asarray(self._est_state.est[:, :1], float) / kv_cap
        err = self._wd.drift_sample(
            jnp.asarray(prev, jnp.float32),
            jnp.asarray(measured[:, None] / kv_cap, jnp.float32))
        self._g_win = self._wd.push_errors(self._g_win, err)
        err_q = self._wd.trip_statistic(self._g_win, gcfg.err_quantile)
        was_open = self._g_state == self._wd.OPEN
        s, t, _ = self._wd.breaker_step(
            jnp.int32(self._g_state), jnp.int32(self._g_timer), err_q, gcfg)
        self._g_state, self._g_timer = int(s), int(t)
        self._g_err_q = float(err_q)
        if self._g_state == self._wd.OPEN and not was_open:
            self.stats.guard_trips += 1
        if self._g_state == self._wd.OPEN:
            self.stats.guard_open_steps += 1

    def _guard_penalty(self) -> float:
        """Penalty for the migrate pass: confidence-scaled while guarded
        (the engine analogue of the simulator's reclaim/migrate-cap
        tightening — still a per-pass scalar, kernel-cap sound)."""
        pen = float(self.ctrl.penalty)
        if self.cfg.guard is not None:
            pen *= float(self._wd.penalty_scale(
                jnp.float32(self._g_err_q), self.cfg.guard))
        return pen

    def refresh_snapshots(self):
        """Advance the estimator on measured usage; refresh round snapshots."""
        measured = self._usage()
        if self.cfg.guard is not None:
            self._guard_observe(measured)
        key = jax.random.fold_in(self._est_key, self.stats.steps)
        self._est_state = self.estimator.refresh(
            self._est_state, jnp.asarray(measured[:, None], jnp.float32), key)
        self._usage_snap = np.asarray(self._est_state.est[:, 0], float)
        self._declared_snap = self._declared()
        if (self.cfg.guard is not None
                and self._g_state == self._wd.OPEN):
            # safe mode: this round's admission judges replicas by the
            # estimate blended toward DECLARED footprints (blend_estimate
            # semantics; the raw estimator state keeps evolving untouched)
            w = float(self.cfg.guard.open_blend)
            self._usage_snap = self._usage_snap + w * np.maximum(
                self._declared_snap - self._usage_snap, 0.0)

    def _admit_eager(self, node: NodeState, r: np.ndarray, srcs: np.ndarray,
                     prios: np.ndarray, order: np.ndarray,
                     penalty, valid: np.ndarray) -> np.ndarray:
        """Per-request reference loop: one feasible/score/argmax per task.

        The pre-batching engine structure, expressed through the SAME
        policy hooks and admit-one state updates as the scan — the
        baseline the serving benchmark compares the batched modes
        against.
        """
        placements = np.full(len(r), -1, np.int32)
        pen = jnp.asarray(penalty, jnp.float32)
        for k in order:
            k = int(k)
            if not valid[k]:
                continue
            task = admission.TaskView(
                request=jnp.asarray(r[k]),
                src=jnp.asarray(int(srcs[k]), jnp.int32),
                priority=jnp.asarray(int(prios[k]), jnp.int32))
            ctx = admission.PolicyContext(node=node, penalty=pen,
                                          params=self.params)
            feasible = self.policy.feasible(ctx, task)
            if not bool(jnp.any(feasible)):
                continue
            scores = admission.mask_infeasible(
                self.policy.score(ctx, task), feasible)
            i = int(jnp.argmax(scores))
            placements[k] = i
            req = jnp.asarray(r[k])
            node = node._replace(
                reserved=node.reserved.at[i].add(req),
                requested=node.requested.at[i].add(req),
                n_tasks=node.n_tasks.at[i].add(1),
                src_count=node.src_count.at[i, int(srcs[k])].add(1))
        return placements

    def _admit_batched(self, node: NodeState, r: np.ndarray, srcs: np.ndarray,
                       prios: np.ndarray, order: np.ndarray,
                       penalty, valid_mask: np.ndarray) -> np.ndarray:
        """One jitted admit_queue launch per static-width chunk.

        Chunks carry the updated NodeState (reservations included), so a
        queue longer than ``admit_batch`` is admitted exactly as one
        sequential pass would.
        """
        q = len(r)
        w = int(self.cfg.admit_batch)
        placements = np.full(q, -1, np.int32)
        pen = jnp.asarray(penalty, jnp.float32)
        for lo in range(0, q, w):
            idx = order[lo:lo + w]
            q_eff = len(idx)
            # Pad to the next power of two (floor 8, cap admit_batch) so
            # jit compiles a handful of widths, not one per queue length.
            pad = min(w, max(8, 1 << (q_eff - 1).bit_length()))
            sl = np.zeros((pad, 2), np.float32)
            sl[:q_eff] = r[idx]
            ss = np.zeros(pad, np.int32)
            ss[:q_eff] = srcs[idx]
            pp = np.zeros(pad, np.int32)
            pp[:q_eff] = prios[idx]
            valid = np.arange(pad) < q_eff
            valid[:q_eff] &= valid_mask[idx]     # brownout-deferred requests
            node, pl = self._admit_fn(node, jnp.asarray(sl), jnp.asarray(ss),
                                      jnp.asarray(pp), jnp.asarray(valid),
                                      pen)
            placements[idx] = np.asarray(pl[:q_eff])
        return placements

    def admit_pending(self) -> int:
        """Admit as many queued requests as fit this round (one pass).

        Applies the policy's ``queue_order`` hook (LRF/priority queues),
        admits through the configured execution mode, and applies the
        placements: admitted requests join their replica's active list in
        admission order; blocked requests stay queued in FIFO order.
        Returns the number of requests admitted.
        """
        if not self.queue:
            return 0
        reqs = list(self.queue)
        r, srcs, prios = self._task_arrays(reqs)
        valid = np.ones(len(reqs), bool)
        if self._brownout_pressure():
            # graceful degradation: under sustained QoS pressure, defer
            # CLASS_BATCH admissions (they stay queued FIFO-stable) and
            # let production traffic through — expressed as the shared
            # core's validity mask, no new admission branch.
            valid &= prios >= CLASS_PRODUCTION
            self.stats.brownout_steps += 1
            self.stats.brownout_deferred += int((~valid).sum())
        if self.cfg.guard is not None and self._g_state != self._wd.CLOSED:
            # circuit breaker: while OPEN, estimator-driven (sub-production)
            # admission defers brownout-style — production still lands,
            # judged against the blended (declared-based) snapshots; while
            # HALF_OPEN, a bounded FIFO-head trickle of deferred traffic
            # probes whether the estimator recovered.
            before = valid.copy()
            allow = prios >= CLASS_PRODUCTION
            if self._g_state == self._wd.HALF_OPEN:
                trickle = np.zeros(len(reqs), bool)
                trickle[:int(self.cfg.guard.probe_reclaim)] = True
                allow = allow | trickle
            valid &= allow
            self.stats.guard_deferred += int((before & ~valid).sum())
        order = np.arange(len(reqs))
        hook = policy_queue_order(self.policy)
        if hook is not None:
            order = np.asarray(hook(jnp.asarray(r), jnp.asarray(prios),
                                    jnp.asarray(valid)))
        node = self.node_state()
        penalty = float(self.ctrl.penalty)

        t0 = time.perf_counter()
        if self.cfg.admission_mode == "eager":
            placements = self._admit_eager(node, r, srcs, prios, order,
                                           penalty, valid)
        else:
            placements = self._admit_batched(node, r, srcs, prios, order,
                                             penalty, valid)
        self.stats.admit_latency_s.append(time.perf_counter() - t0)
        self.stats.decisions += int(valid.sum())

        admitted = 0
        for k in order:
            i = int(placements[k])
            if i < 0:
                continue
            req = reqs[int(k)]
            req.replica = i
            self.active[i].append(req)
            self.stats.admitted += 1
            admitted += 1
            if self.on_admit is not None:
                self.on_admit(req)
        self.queue = deque(req for req in reqs if req.replica < 0)
        return admitted

    # ---------------- live migration (repro.migration) ----------------

    def _in_flight(self) -> int:
        """Requests still paying their transfer-latency stall."""
        return sum(1 for rs in self.active.values()
                   for r in rs if r.stall > 0)

    def _try_migrate(self, cands: List[Request],
                     extra_off: "np.ndarray | None" = None) -> List[Request]:
        """Re-place candidate requests through the shared admission core.

        One ``migrate``-policy admitter call over the candidates: successes
        move their KV-token fraction to the target replica — ``generated``
        (the progress) is KEPT, the request pays ``migrate_cost`` stalled
        decode steps (the transfer latency) instead of a restart.  Bounded
        by the per-step bandwidth budget and the in-flight pool
        (``pool_size``); ``extra_off`` adds per-replica reserved offsets
        (the overflow path excludes its source this way — draining sources
        already ride ``_straggler_extra`` at the drain load).  Returns the
        requests that moved; the rest stay put for the caller to handle.
        """
        mig = self.cfg.migration
        room = int(mig.pool_size) - self._in_flight()
        take = cands[:max(min(self._mig_left, room,
                              int(self.cfg.admit_batch)), 0)]
        if not take:
            return []
        r, srcs, prios = self._task_arrays(take)
        node = self.node_state()
        if extra_off is not None:
            node = admission.mask_unavailable(
                node, jnp.asarray(extra_off, jnp.float32))
        q_eff = len(take)
        pad = min(int(self.cfg.admit_batch),
                  max(8, 1 << (q_eff - 1).bit_length()))
        sl = np.zeros((pad, 2), np.float32)
        sl[:q_eff] = r
        ss = np.zeros(pad, np.int32)
        ss[:q_eff] = srcs
        pp = np.zeros(pad, np.int32)
        pp[:q_eff] = prios
        valid = np.arange(pad) < q_eff
        _, pl = self._migrate_fn(node, jnp.asarray(sl), jnp.asarray(ss),
                                 jnp.asarray(pp), jnp.asarray(valid),
                                 jnp.asarray(self._guard_penalty(),
                                             jnp.float32))
        pl = np.asarray(pl[:q_eff])
        moved = []
        for k, req in enumerate(take):
            tgt = int(pl[k])
            if tgt < 0:
                continue
            src_rep = req.replica
            self.active[src_rep].remove(req)
            self.active[tgt].append(req)
            req.replica = tgt
            req.stall = int(mig.migrate_cost)
            req.migrations += 1
            # move the KV-token fraction between the round snapshots so
            # the SAME round's admission sees the transfer (the engine's
            # reservation-scatter semantics, applied across replicas)
            self._usage_snap[src_rep] -= req.current_footprint
            self._usage_snap[tgt] += req.current_footprint
            self._declared_snap[src_rep] -= req.declared_footprint
            self._declared_snap[tgt] += req.declared_footprint
            self.stats.migrations += 1
            self._mig_left -= 1
            moved.append(req)
        return moved

    def _migrate_draining(self):
        """Drain pass: move residents of announced-crash replicas.

        Announced replicas already carry the drain load in
        ``_straggler_extra`` (``_down_until > t`` spans the warning
        window), so they are excluded both as admission targets and as
        migration targets with no extra masking — the engine analogue of
        the simulator's source-exclusion offsets (docs/kernels.md).
        Oldest residents first: they have the most progress to lose.
        """
        t = self.stats.steps
        draining = np.flatnonzero((self._down_from > t)
                                  & (self._down_until > t))
        cands = [r for i in draining
                 for r in self.active[int(i)] if r.stall == 0]
        if cands:
            self._try_migrate(cands)

    # ---------------- decode + overflow handling ----------------

    def _stub_decode(self, replica: int, reqs: List[Request]) -> float:
        """Stand-in decode: advances counters; returns simulated step time."""
        return 1.0 + 0.05 * len(reqs)

    def _step_replica(self, i: int):
        reqs = self.active[i]
        if not reqs:
            return
        dt = self.decode_fn(i, reqs)
        if self.cfg.faults is not None:
            # straggler storm: the replica actually runs this much slower;
            # the EMA below is how the mitigation finds out
            dt *= float(self._storm_slowdown[i])
        self.step_time_ema[i] = 0.8 * self.step_time_ema[i] + 0.2 * dt
        for r in reqs:
            if r.stall > 0:
                r.stall -= 1          # transfer latency: no token this step
                continue
            if not r.done:
                r.generated += 1
                self.stats.tokens_generated += 1
                if r.generated >= r.true_tokens:
                    r.done = True
        # overflow: real usage exceeded the budget -> evict newest first
        usage = sum(r.current_footprint for r in reqs)
        cap = self.cfg.kv_budget_tokens
        if usage > cap and self.cfg.migration is not None:
            # migrate-then-shed (ISSUE 9): move the newest admissions off
            # the overflowing replica first; only what cannot move pays
            # the evict-and-restart tax below.
            cands, freed = [], 0.0
            for r2 in reversed(reqs):
                if r2.stall > 0:
                    continue
                cands.append(r2)
                freed += r2.current_footprint
                if usage - freed <= cap:
                    break
            off = np.zeros(self.cfg.n_replicas, np.float32)
            off[i] = _DRAIN_LOAD           # the source is never a target
            moved = self._try_migrate(cands, extra_off=off)
            self.stats.migration_failed += len(cands) - len(moved)
            usage = sum(r.current_footprint for r in reqs)
        evicted = []
        while usage > cap and reqs:
            victim = reqs.pop()           # LIFO: newest admission pays
            usage -= victim.current_footprint
            victim.evictions += 1
            victim.replica = -1
            victim.generated = 0          # restart (no KV migration)
            victim.done = False
            self._ever_violated.add(victim.rid)
            self.stats.evicted_events += 1
            if self.on_evict is not None:
                self.on_evict(victim)
            evicted.append(victim)
        # Re-queue FIFO-stable: victims were popped newest-first, so
        # extendleft (which reverses) restores their original admission
        # order at the head of the queue, ahead of fresh arrivals.
        self.queue.extendleft(evicted)
        # retire finished
        done = [r for r in reqs if r.done]
        self.active[i] = [r for r in reqs if not r.done]
        self.stats.finished += len(done)

    # ---------------- main loop ----------------

    def submit(self, req: Request):
        self.queue.append(req)

    def step(self):
        cfg = self.cfg
        if cfg.faults is not None:
            self._inject_faults()
        self.refresh_snapshots()
        if cfg.migration is not None:
            self._mig_left = int(cfg.migration.bandwidth)
            self._migrate_draining()
        self.admit_pending()

        for i in range(cfg.n_replicas):
            self._step_replica(i)

        # cluster QoS: active+finished requests that were never evicted
        n_seen = max(self.stats.admitted, 1)
        q = 1.0 - len(self._ever_violated) / n_seen
        self.ctrl = update_penalty(self.ctrl, q, self.params)
        self.stats.qos_series.append(float(q))
        self.stats.penalty_series.append(float(self.ctrl.penalty))
        self.stats.util_series.append(
            float(self._usage().sum())
            / (cfg.n_replicas * cfg.kv_budget_tokens))
        self.stats.steps += 1

    def run(self, steps: int):
        for _ in range(steps):
            self.step()
        return self.stats
