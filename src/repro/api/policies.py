"""Built-in policies, estimators and controllers (paper §4 + extensions).

The four paper policies (LeastFit, Oversub, FlexF, FlexL) are expressed
through the shared admission helpers with exactly the seed repo's math.
Two extra policies (``best-fit-usage``, ``flex-priority``) demonstrate the
open registry: neither exists in the paper.

All objects are frozen dataclasses — hashable, so each one can be a
static ``jax.jit`` argument.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.api import admission
from repro.api.admission import PolicyContext, TaskView
from repro.api.registry import register_policy
from repro.core import estimator as _est
from repro.core import penalty as _penalty
from repro.core.types import (
    CLASS_PRODUCTION,
    MEM,
    ControllerState,
    FlexParams,
)


def _flex_src_frac(ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
    """Fraction of a node's tasks sharing the incoming task's source.

    Same-source tasks are likely to peak together (§4.3), so Flex scoring
    spreads them.
    """
    node = ctx.node
    return node.src_count[:, task.src].astype(jnp.float32) / (
        jnp.maximum(node.n_tasks, 1).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Request-based policies (RLB, eq. 4-5)
# ---------------------------------------------------------------------------

@register_policy("least-fit")
@dataclasses.dataclass(frozen=True)
class LeastFitPolicy:
    """Kubernetes-style LeastFit: request-based filter + least-requested score.

    ``pin_theta`` pins the oversubscription factor regardless of the
    caller's FlexParams (the paper baseline runs at theta = 1).
    """

    name = "least-fit"
    pin_theta: float | None = 1.0
    default_theta: float = 1.0

    def prepare_params(self, params: FlexParams) -> FlexParams:
        if self.pin_theta is None:
            return params
        return params._replace(
            theta=jnp.asarray(self.pin_theta, jnp.float32))

    def feasible(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        committed = admission.committed_load(ctx.node.requested,
                                             ctx.node.reserved)
        return admission.fits(committed, task.request, ctx.params.theta)

    def score(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        committed = admission.committed_load(ctx.node.requested,
                                             ctx.node.reserved)
        return admission.least_loaded_score(committed, ctx.params.theta)


@register_policy("oversub")
@dataclasses.dataclass(frozen=True)
class OversubPolicy(LeastFitPolicy):
    """LeastFit with requests oversubscribed by theta (paper: 2.0).

    theta is NOT pinned: it comes from FlexParams so sweeps can scan it.
    """

    name = "oversub"
    pin_theta: float | None = None
    default_theta: float = 2.0


# ---------------------------------------------------------------------------
# Usage-based policies (ULB, eq. 9 + Alg. 3)
# ---------------------------------------------------------------------------

@register_policy("flex-f")
@dataclasses.dataclass(frozen=True)
class FlexFifoPolicy:
    """FlexF: penalized-usage filter, load + same-source score, FIFO queue."""

    name = "flex-f"
    pin_theta: float | None = 1.0
    default_theta: float = 1.0

    def prepare_params(self, params: FlexParams) -> FlexParams:
        if self.pin_theta is None:
            return params
        return params._replace(
            theta=jnp.asarray(self.pin_theta, jnp.float32))

    def _load(self, ctx: PolicyContext) -> jnp.ndarray:
        return admission.usage_load(ctx.node.est_usage, ctx.node.reserved,
                                    ctx.penalty)

    def feasible(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        return admission.fits(self._load(ctx), task.request, 1.0)

    def score(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        load_term = admission.dominant(self._load(ctx))
        src_frac = _flex_src_frac(ctx, task)
        return -(ctx.params.w_load * load_term
                 + ctx.params.w_src * src_frac)

    def kernel_inputs(self, ctx: PolicyContext,
                      task: TaskView) -> admission.KernelInputs:
        """Fused-kernel mapping of the ULB filter + Flex score
        (docs/kernels.md); numerically identical to feasible+score."""
        return admission.KernelInputs(
            est_usage=ctx.node.est_usage,
            reserved=ctx.node.reserved,
            src_frac=_flex_src_frac(ctx, task),
            penalty=ctx.penalty,
            cap=jnp.asarray(1.0, jnp.float32),
            w_load=ctx.params.w_load,
            w_src=ctx.params.w_src,
        )


@register_policy("flex-l")
@dataclasses.dataclass(frozen=True)
class FlexLrfPolicy(FlexFifoPolicy):
    """FlexL: FlexF scoring behind an LRF (largest memory request first)
    priority queue (§4.3)."""

    name = "flex-l"

    def queue_order(self, requests: jnp.ndarray, priorities: jnp.ndarray,
                    valid: jnp.ndarray) -> jnp.ndarray:
        mem_req = jnp.where(valid, requests[:, MEM], -jnp.inf)
        return jnp.argsort(-mem_req)


@register_policy("best-fit-usage")
@dataclasses.dataclass(frozen=True)
class BestFitUsagePolicy(FlexFifoPolicy):
    """Usage-based BEST fit: pack the fullest feasible node.

    Consolidates load onto few nodes (the energy-aware packing objective
    of e.g. Buyya et al.) at the cost of load balance — the mirror image
    of Flex's least-loaded score, sharing its penalized-usage filter.
    """

    name = "best-fit-usage"

    def score(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        return admission.dominant(self._load(ctx))

    def kernel_inputs(self, ctx: PolicyContext,
                      task: TaskView) -> admission.KernelInputs:
        # The kernel score -(w_load * max(load) + w_src * src) with
        # w_load = -1, w_src = 0 is exactly +dominant(load): best fit.
        return super().kernel_inputs(ctx, task)._replace(
            w_load=jnp.asarray(-1.0, jnp.float32),
            w_src=jnp.asarray(0.0, jnp.float32))


@register_policy("flex-priority")
@dataclasses.dataclass(frozen=True)
class PriorityFlexPolicy(FlexFifoPolicy):
    """Priority-class-aware Flex: protect CLASS_PRODUCTION tasks.

    Production/system tasks see the full node capacity; batch tasks may
    only fill nodes up to ``1 - headroom``, keeping slack for the demand
    spikes of latency-sensitive tenants.  The queue is ordered
    production-first (then LRF by memory within a class).
    """

    name = "flex-priority"
    headroom: float = 0.1

    def feasible(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        return admission.fits(self._load(ctx), task.request, self._cap(task))

    def _cap(self, task: TaskView) -> jnp.ndarray:
        return jnp.where(task.priority >= CLASS_PRODUCTION,
                         1.0, 1.0 - self.headroom)

    def kernel_inputs(self, ctx: PolicyContext,
                      task: TaskView) -> admission.KernelInputs:
        # Priority-dependent capacity rides in the kernel's task vector.
        return super().kernel_inputs(ctx, task)._replace(
            cap=self._cap(task).astype(jnp.float32))

    def queue_order(self, requests: jnp.ndarray, priorities: jnp.ndarray,
                    valid: jnp.ndarray) -> jnp.ndarray:
        is_prod = (priorities >= CLASS_PRODUCTION).astype(jnp.float32)
        key = jnp.where(valid, 2.0 * is_prod + requests[:, MEM], -jnp.inf)
        return jnp.argsort(-key)


@register_policy("reclaim")
@dataclasses.dataclass(frozen=True)
class ReclaimPolicy(FlexFifoPolicy):
    """Headroom reclamation: second-chance admission against PREDICTED usage.

    The simulator's reclamation pass (``SimConfig(reclamation=True)``)
    re-admits tasks the primary policy dropped, judging each node by its
    predicted usage instead of its allocation: feasible iff
    ``P * L-hat + reserved + r <= 1 - margin_scale * P``.  The safety
    margin is DERIVED FROM THE LIVE PENALTY CONTROLLER — when QoS
    violations push the penalty P up, the reclaimable cap shrinks on both
    sides of the inequality and reclamation backs off automatically;
    when the estimator earns trust (P at ``p_min``), the pass may fill
    nodes up to ``1 - margin_scale`` of capacity.  Scoring is inherited
    from FlexF (least-loaded + same-source spreading), and the traced cap
    rides the kernel template's ``cap`` scalar, so reclamation reuses
    ``admit_queue_wavefront`` unchanged — no second admission code path.
    """

    name = "reclaim"
    margin_scale: float = 0.1

    def _cap(self, ctx: PolicyContext) -> jnp.ndarray:
        return jnp.maximum(1.0 - self.margin_scale * ctx.penalty, 0.0)

    def feasible(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        return admission.fits(self._load(ctx), task.request, self._cap(ctx))

    def kernel_inputs(self, ctx: PolicyContext,
                      task: TaskView) -> admission.KernelInputs:
        # Same template as FlexF with the penalty-derived cap; the cap is
        # admission-invariant within a pass (penalty updates once per
        # slot), so the wavefront soundness invariants hold.
        return super().kernel_inputs(ctx, task)._replace(
            cap=self._cap(ctx).astype(jnp.float32))


@register_policy("migrate")
@dataclasses.dataclass(frozen=True)
class MigratePolicy(FlexFifoPolicy):
    """Live-migration target selection: re-place a RESIDENT task off a
    draining/overloaded node (``repro.migration``, ISSUE 9).

    Target scoring is inherited from FlexF (least-loaded + same-source
    spreading — a migrating task should land where a fresh admission
    would), with an optional penalty-derived safety cap
    ``1 - margin_scale * P`` riding the kernel template's ``cap`` scalar
    exactly like the reclaim pass: under QoS pressure the migration pass
    targets conservatively, with a trusted estimator it may fill nodes.

    **Source exclusion** needs no per-task node plane: every migration
    source in a pass is a draining (or overloaded) node, and the pass
    folds ``admission.DRAIN_LOAD`` into those nodes' ``reserved`` rows
    (``admission.mask_unavailable`` — the same offset mechanism as fault
    masking) before admitting.  The kernel cap filter
    ``all_R(P * est + reserved + r <= cap)`` then rejects every source for
    every task, because any finite cap sits far below ``DRAIN_LOAD``.
    The offset is node-side and admission-invariant within the pass, so
    all wavefront/dedup soundness invariants carry over unchanged
    (docs/kernels.md, "Source-exclusion cap").
    """

    name = "migrate"
    margin_scale: float = 0.0

    def _cap(self, ctx: PolicyContext) -> jnp.ndarray:
        return jnp.maximum(1.0 - self.margin_scale * ctx.penalty, 0.0)

    def feasible(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        return admission.fits(self._load(ctx), task.request, self._cap(ctx))

    def kernel_inputs(self, ctx: PolicyContext,
                      task: TaskView) -> admission.KernelInputs:
        return super().kernel_inputs(ctx, task)._replace(
            cap=self._cap(ctx).astype(jnp.float32))


@register_policy("flex-brownout")
@dataclasses.dataclass(frozen=True)
class BrownoutPolicy(FlexFifoPolicy):
    """QoS-pressure brownout through the registry: batch capacity shrinks
    with the live penalty.

    The degradation story (``repro.faults``) expressed as a pure policy:
    CLASS_BATCH tasks may only fill nodes up to
    ``clip(1 - brownout_scale * (P - p_min), floor, 1)`` while
    production/system tasks keep the full capacity.  QoS violations push
    the penalty P up, so batch admissions brown out automatically under
    pressure and recover as the controller earns trust back — no
    controller wiring, no new enum branches.  The priority- and
    penalty-dependent cap rides the kernel template's per-task ``cap``
    scalar (admission-invariant within a slot), so the policy runs
    unchanged through every execution mode including
    ``admit_queue_wavefront``.
    """

    name = "flex-brownout"
    brownout_scale: float = 0.25
    floor: float = 0.2

    def _cap(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        batch_cap = jnp.clip(
            1.0 - self.brownout_scale * (ctx.penalty - ctx.params.p_min),
            self.floor, 1.0)
        return jnp.where(task.priority >= CLASS_PRODUCTION, 1.0, batch_cap)

    def feasible(self, ctx: PolicyContext, task: TaskView) -> jnp.ndarray:
        return admission.fits(self._load(ctx), task.request,
                              self._cap(ctx, task))

    def kernel_inputs(self, ctx: PolicyContext,
                      task: TaskView) -> admission.KernelInputs:
        return super().kernel_inputs(ctx, task)._replace(
            cap=self._cap(ctx, task).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Estimators (protocol wrappers over repro.core.estimator)
#
# These stateless classes predate the repro.estimators subsystem and are
# kept for backward compatibility: any object with the legacy
# ``refresh(prev_est, node_usage, key)`` hook still works everywhere an
# estimator is accepted (adapted bit-identically by
# ``repro.estimators.base.as_stateful``).  New code should register
# stateful estimators with ``repro.estimators.register_estimator``.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CurrentUsageEstimator:
    """The paper's estimator: L-hat = measured current usage.

    ``noise_std`` adds multiplicative measurement noise so tests and
    benches can stress the penalty controller with a *bad* estimator.
    """

    noise_std: float = 0.0

    def refresh(self, prev_est: jnp.ndarray, node_usage: jnp.ndarray,
                key: jax.Array) -> jnp.ndarray:
        return _est.current_usage(node_usage, key, self.noise_std)


@dataclasses.dataclass(frozen=True)
class EwmaEstimator:
    """EWMA smoothing (the related work's standard choice)."""

    decay: float = 0.7

    def refresh(self, prev_est: jnp.ndarray, node_usage: jnp.ndarray,
                key: jax.Array) -> jnp.ndarray:
        return _est.ewma(prev_est, node_usage, self.decay)


ESTIMATORS = {
    "current": CurrentUsageEstimator,
    "ewma": EwmaEstimator,
}


def resolve_estimator(est, noise_std: float = 0.0):
    """str | Estimator -> stateful Estimator (str honours the noise knob).

    Delegates to the ``repro.estimators`` registry — names resolve to the
    stateful built-ins there (``current``/``ewma`` are bit-identical to
    the legacy classes above), and estimator objects of either
    convention are adapted to the stateful ``init_state``/``refresh``
    contract.
    """
    from repro.estimators.registry import resolve_estimator as _resolve
    return _resolve(est, noise_std)


# ---------------------------------------------------------------------------
# Penalty controllers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AimdPenaltyController:
    """The paper's AIMD-style controller (Alg. 3 lines 19-25)."""

    def init(self, params: FlexParams) -> ControllerState:
        return ControllerState.init(params)

    def update(self, ctrl: ControllerState, qos: jnp.ndarray,
               params: FlexParams) -> ControllerState:
        return _penalty.update_penalty(ctrl, qos, params)
