"""Trace analysis reproducing the paper's §2.2 (Figures 1-5).

Each function returns plain numpy summaries suitable for the benchmark CSV
outputs; all heavy lifting stays in jnp.
"""
from __future__ import annotations

import warnings
from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.core.qos import recovery_slots
from repro.core.types import SimResult, TaskSet

_CLASS_NAMES = {0: "batch", 1: "production", 2: "system"}

_NEEDS_NODE_SERIES = (
    "needs the per-node series (SlotMetrics.{field} is empty); run the "
    "simulation with SimConfig(record_node_usage=True)")


def cdf(x: jnp.ndarray, qs=(0.1, 0.25, 0.5, 0.75, 0.9, 0.99)) -> Dict[str, float]:
    x = jnp.ravel(x)
    return {f"p{int(q * 100)}": float(jnp.quantile(x, q)) for q in qs}


def cluster_level(result: SimResult) -> Dict[str, float]:
    """Fig. 1: total usage / total request vs. cluster capacity."""
    m = result.metrics
    return {
        "avg_usage_cpu": float(jnp.mean(m.usage[:, 0])),
        "avg_usage_mem": float(jnp.mean(m.usage[:, 1])),
        "avg_request_cpu": float(jnp.mean(m.requested[:, 0])),
        "avg_request_mem": float(jnp.mean(m.requested[:, 1])),
    }


def machine_level(result: SimResult) -> Dict[str, float]:
    """Fig. 2/3: distribution of per-node usage over (node, slot) samples."""
    u = result.metrics.node_usage  # (S, N, R)
    if u.size == 0:
        raise ValueError(
            "machine_level " + _NEEDS_NODE_SERIES.format(field="node_usage"))
    out = {}
    for r, name in ((0, "cpu"), (1, "mem")):
        ratios = u[..., r]
        out.update({f"usage_to_cap_{name}_{k}": v
                    for k, v in cdf(ratios).items()})
        out[f"frac_idle_{name}"] = float(jnp.mean(ratios < 0.01))
        out[f"frac_below_half_{name}"] = float(jnp.mean(ratios < 0.5))
    return out


def task_level(ts: TaskSet) -> Dict[str, float]:
    """Fig. 4/5: usage-vs-request statistics, overall and per class."""
    out = {}
    mean_ratio = ts.mean_usage / jnp.maximum(ts.request, 1e-6)
    peak_ratio = ts.peak_usage / jnp.maximum(ts.request, 1e-6)
    std_over_mean = ts.std_usage / jnp.maximum(ts.mean_usage, 1e-6)
    for r, name in ((0, "cpu"), (1, "mem")):
        out[f"mean_usage_over_request_{name}"] = float(jnp.mean(mean_ratio[:, r]))
        out[f"peak_usage_over_request_{name}"] = float(jnp.mean(peak_ratio[:, r]))
        out[f"std_over_mean_{name}"] = float(jnp.mean(std_over_mean[:, r]))
        for cls in (0, 1, 2):
            m = ts.priority == cls
            denom = jnp.maximum(jnp.sum(m), 1)
            out[f"{_CLASS_NAMES[cls]}_mean_ratio_{name}"] = float(
                jnp.sum(jnp.where(m, mean_ratio[:, r], 0.0)) / denom)
            out[f"{_CLASS_NAMES[cls]}_peak_ratio_{name}"] = float(
                jnp.sum(jnp.where(m, peak_ratio[:, r], 0.0)) / denom)
    return out


def load_balance(result: SimResult) -> Dict[str, float]:
    """Fig. 9: normalized std of per-node memory usage over time."""
    m = result.metrics
    norm_std = m.usage_std / jnp.maximum(m.usage_mean, 1e-6)
    return {
        "mean_norm_std_cpu": float(jnp.mean(norm_std[:, 0])),
        "mean_norm_std_mem": float(jnp.mean(norm_std[:, 1])),
    }


def estimator_error(result: SimResult) -> Dict[str, float]:
    """Estimator-error CDFs: one-slot-ahead L-hat vs realized usage.

    The estimate refreshed at slot t is what admission at slot t uses to
    place tasks that become active at t+1, so the natural alignment is
    ``est[t]`` against ``usage[t+1]`` (ellipsis indexing keeps vmapped
    results with leading seed/sweep axes working).
    """
    est = result.metrics.node_est        # (..., S, N, R)
    usage = result.metrics.node_usage
    if est.size == 0 or usage.size == 0:
        raise ValueError(
            "estimator_error " + _NEEDS_NODE_SERIES.format(field="node_est"))
    err = est[..., :-1, :, :] - usage[..., 1:, :, :]
    out = {}
    for r, name in ((0, "cpu"), (1, "mem")):
        e = err[..., r]
        out.update({f"est_abs_err_{name}_{k}": v
                    for k, v in cdf(jnp.abs(e)).items()})
        out[f"est_bias_{name}"] = float(jnp.mean(e))       # >0: over-estimates
        out[f"est_under_frac_{name}"] = float(jnp.mean(e < 0.0))
    return out


def overprovisioning(result: SimResult) -> Dict[str, float]:
    """Usage–allocation gap per (node, slot): requested minus realized usage.

    The paper's Fig. 1-3 story at node granularity — the stranded
    capacity a reclamation pass can recover.
    """
    req = result.metrics.node_requested  # (..., S, N, R)
    usage = result.metrics.node_usage
    if req.size == 0 or usage.size == 0:
        raise ValueError(
            "overprovisioning "
            + _NEEDS_NODE_SERIES.format(field="node_requested"))
    gap = req - usage
    out = {}
    for r, name in ((0, "cpu"), (1, "mem")):
        out.update({f"overprov_{name}_{k}": v
                    for k, v in cdf(gap[..., r]).items()})
        out[f"mean_overprov_{name}"] = float(jnp.mean(gap[..., r]))
    return out


def zombie_nodes(result: SimResult, req_floor: float = 0.05,
                 usage_eps: float = 0.01) -> Dict[str, float]:
    """Nodes holding allocation while nearly idle (Beloglazov-style waste).

    A (node, slot) sample is a zombie when its committed requests exceed
    ``req_floor`` of capacity but realized usage sits under ``usage_eps``
    — capacity a consolidation/reclamation pass should target.
    """
    req = result.metrics.node_requested
    usage = result.metrics.node_usage
    if req.size == 0 or usage.size == 0:
        raise ValueError(
            "zombie_nodes " + _NEEDS_NODE_SERIES.format(field="node_requested"))
    out = {}
    for r, name in ((0, "cpu"), (1, "mem")):
        zombie = (req[..., r] > req_floor) & (usage[..., r] < usage_eps)
        out[f"zombie_frac_{name}"] = float(jnp.mean(zombie))
    return out


def fault_recovery(result: SimResult, qos_target: float,
                   consecutive: int = 3) -> Dict[str, float]:
    """Fault-tolerance summary: time-to-recover and evictions by cause.

    ``recovery_slots`` is the paper-style robustness headline — slots from
    the first QoS dip below target until the trend holds at/above target
    for ``consecutive`` slots (0 when QoS never dips).  The eviction
    split separates crashes (``n_fault_evicted``, involuntary) from the
    degradation controller's shedding (``n_degrade_evicted``, voluntary),
    and ``degraded_frac`` is the fraction of slots spent in brownout —
    together they say whether the controller recovered *by* degrading
    gracefully or never needed to.  ``retained_task_slots`` (total
    running task-slots) is the admitted-work retention metric the
    fault-recovery bench compares across degradation strategies.
    ``n_migrated`` / ``n_migration_failed`` split the live-migration pass
    (``SimConfig(migration=...)``): tasks re-placed with progress kept vs
    candidates that fell back to the evict-to-retry path (both 0 when
    migration is off).
    """
    m = result.metrics
    return {
        "recovery_slots": int(recovery_slots(
            m.qos, qos_target, consecutive=consecutive)),
        "n_fault_evicted": int(m.n_fault_evicted[-1]),
        "n_degrade_evicted": int(m.n_degrade_evicted[-1]),
        "degraded_frac": float(jnp.mean(m.degraded.astype(jnp.float32))),
        "retained_task_slots": int(jnp.sum(m.n_running)),
        "qos_min": float(jnp.min(m.qos)),
        "n_migrated": int(m.n_migrated[-1]),
        "n_migration_failed": int(m.n_migration_failed[-1]),
    }


def guard_report(result: SimResult) -> Dict[str, float]:
    """Drift-watchdog summary (``SimConfig(guard=GuardConfig(...))``).

    ``guard_trips`` counts breaker transitions into OPEN, ``open_frac`` /
    ``half_open_frac`` the fraction of slots spent in each non-closed
    state, ``n_guard_deferred`` the reclaim candidates the breaker held
    back (suspension + trickle clipping), and ``err_q_max`` / ``err_q_mean``
    the windowed drift quantile the trip condition acted on.  Raises
    :class:`ValueError` when the run was unguarded — the guard leaves of
    :class:`SlotMetrics` are empty then, exactly like the per-node series
    of :func:`estimator_error`.
    """
    m = result.metrics
    if m.guard_tripped.size == 0:
        raise ValueError(
            "guard_report needs the drift-watchdog series "
            "(SlotMetrics.guard_tripped is empty); run the simulation "
            "with SimConfig(guard=GuardConfig(...))")
    state = m.guard_tripped
    opened = state == 1
    prev = jnp.concatenate(
        [jnp.zeros_like(opened[..., :1]), opened[..., :-1]], axis=-1)
    return {
        "guard_trips": int(jnp.sum(opened & ~prev)),
        "open_frac": float(jnp.mean(opened.astype(jnp.float32))),
        "half_open_frac": float(jnp.mean((state == 2).astype(jnp.float32))),
        "n_guard_deferred": int(m.n_guard_deferred[..., -1].max()),
        "err_q_max": float(jnp.max(m.guard_err_q)),
        "err_q_mean": float(jnp.mean(m.guard_err_q)),
    }


def summarize(ts: TaskSet, result: SimResult, qos_target: float) -> Dict[str, float]:
    """One-stop summary used by benchmarks (utilization, QoS, admission).

    Machine-level keys (``machine_level``, ``estimator_error``,
    ``overprovisioning``, ``zombie_nodes``) are included when the run
    recorded per-node series and SKIPPED WITH A WARNING otherwise —
    callers need not know about ``SimConfig(record_node_usage=True)`` to
    get the cluster-level summary.  Guard keys (``guard_report``) are
    included only when the run was guarded; the guard is off by default,
    so an unguarded run omits them without a warning.
    """
    m = result.metrics
    admitted = result.placement >= 0
    out = {
        **cluster_level(result),
        **load_balance(result),
        "qos_mean": float(jnp.mean(m.qos)),
        "qos_violation_frac": float(jnp.mean((m.qos < qos_target))),
        "admitted_frac": float(jnp.mean(admitted)),
        "n_admitted": int(jnp.sum(admitted)),
        "n_rejected": int(m.n_rejected[-1]),
        "n_reclaimed": int(m.n_reclaimed[-1]),
        "final_penalty": float(m.penalty[-1]),
        **fault_recovery(result, qos_target),
    }
    if m.node_usage.size:
        out.update(machine_level(result))
        out.update(estimator_error(result))
        out.update(overprovisioning(result))
        out.update(zombie_nodes(result))
    else:
        warnings.warn(
            "summarize: skipping machine-level keys (machine_level, "
            "estimator_error, overprovisioning, zombie_nodes) — per-node "
            "series were not recorded; pass "
            "SimConfig(record_node_usage=True) to include them",
            stacklevel=2)
    if m.guard_tripped.size:
        out.update(guard_report(result))
    return out
