"""Estimator-drift watchdog + circuit breaker (``repro.guard``, ISSUE 10).

The contract under test, in order of importance:

1. **Opt-in parity** — ``guard=None`` AND an inert ``GuardConfig`` (huge
   trip threshold, ``guard_scale=0``) make bit-identical decisions, at
   the simulator, ``Experiment`` and serving-engine level, in sequential
   and wavefront admission modes (the PR 8/9 parity pattern).
2. **Watchdog math** — the ring-buffer/windowed-quantile monitor matches
   a numpy sliding-window oracle, and the breaker NEVER trips under the
   exact ``current`` estimator on a churn-free workload.
3. **Breaker semantics** — trip -> cooldown -> half-open probe -> close
   (and half-open re-trip), with the reclaim trickle bounded while
   half-open and suspended while open.
4. **Fail-fast config validation** — degenerate
   ``FaultConfig``/``MigrationConfig``/``GuardConfig`` values raise
   ``ValueError`` at construction (satellite of ISSUE 10).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Experiment
from repro.core import SimConfig, run
from repro.core.types import CLASS_PRODUCTION, TaskSet
from repro.faults import FaultConfig, usage_surge
from repro.guard import (
    CLOSED,
    GuardConfig,
    HALF_OPEN,
    OPEN,
    breaker_step,
    push_errors,
    reclaim_width,
    trip_statistic,
)
from repro.guard import watchdog as wd
from repro.migration import MigrationConfig
from repro.serving.engine import EngineConfig, ServeEngine
from repro.serving.stream import RequestStream, StreamConfig
from repro.traces import analysis, generate_calibrated

# Inert guard: the compiled guard path with zero-effect values — never
# trips (threshold far above any normalized error) and never tightens the
# reclaim cap (guard_scale=0), so decisions must match guard=None exactly.
INERT = GuardConfig(trip_threshold=1e9, guard_scale=0.0)


def _taskset(arrival, request, duration=50, mean_frac=0.5, priority=None):
    T = len(arrival)
    request = jnp.asarray(request, jnp.float32)
    if request.ndim == 1:
        request = jnp.stack([request, request], axis=1)
    mean = request * mean_frac
    return TaskSet(
        arrival=jnp.asarray(arrival, jnp.int32),
        duration=jnp.full((T,), duration, jnp.int32),
        request=request,
        mean_usage=mean,
        std_usage=jnp.zeros((T, 2), jnp.float32),
        peak_usage=mean,
        ar_rho=jnp.zeros((T,), jnp.float32),
        priority=(jnp.asarray(priority, jnp.int32) if priority is not None
                  else jnp.zeros((T,), jnp.int32)),
        src=jnp.zeros((T,), jnp.int32),
    )


def _assert_results_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.placement),
                                  np.asarray(b.placement))
    np.testing.assert_array_equal(np.asarray(a.admit_slot),
                                  np.asarray(b.admit_slot))
    np.testing.assert_array_equal(np.asarray(a.metrics.qos),
                                  np.asarray(b.metrics.qos))
    np.testing.assert_array_equal(np.asarray(a.metrics.n_rejected),
                                  np.asarray(b.metrics.n_rejected))
    np.testing.assert_array_equal(np.asarray(a.metrics.penalty),
                                  np.asarray(b.metrics.penalty))


# ------------------------------------------------------------------ parity

@pytest.mark.parametrize("mode", ["sequential", "wavefront"])
def test_sim_inert_guard_bit_identical(mode):
    ts = generate_calibrated(0, 8, 24, offered_load=1.4)
    base = SimConfig(n_nodes=8, n_slots=24, arrivals_per_slot=64,
                     retry_capacity=32, admission_mode=mode,
                     reclamation=True, reclaim_pool=64, estimator="ewma")
    res0 = run(ts, base, "flex-f")
    res1 = run(ts, base._replace(guard=INERT), "flex-f")
    _assert_results_equal(res0, res1)


def test_sim_inert_guard_bit_identical_with_faults_and_migration():
    # The guard threads through the migrate pass's penalty too: the inert
    # config must leave the full faults+migration+reclamation stack
    # untouched.
    ts = generate_calibrated(1, 8, 24, offered_load=1.4)
    base = SimConfig(n_nodes=8, n_slots=24, arrivals_per_slot=64,
                     retry_capacity=32, reclamation=True, reclaim_pool=64,
                     estimator="ewma",
                     faults=FaultConfig(crash_rate=0.01, warn_slots=2),
                     migration=MigrationConfig(bandwidth=8, pool_size=32))
    res0 = run(ts, base, "flex-f")
    res1 = run(ts, base._replace(guard=INERT), "flex-f")
    _assert_results_equal(res0, res1)


def test_experiment_inert_guard_bit_identical():
    ts = generate_calibrated(2, 8, 24, offered_load=1.4)
    base = SimConfig(n_nodes=8, n_slots=24, arrivals_per_slot=64,
                     retry_capacity=32, reclamation=True, reclaim_pool=64,
                     estimator="ewma")
    res0 = Experiment(ts, base, policy="flex-f").run(seeds=[0, 1])
    res1 = Experiment(ts, base._replace(guard=INERT),
                      policy="flex-f").run(seeds=[0, 1])
    _assert_results_equal(res0, res1)


def test_engine_inert_guard_bit_identical():
    def drive(guard):
        eng = ServeEngine(EngineConfig(n_replicas=4, estimator="ewma",
                                       guard=guard), seed=3)
        stream = RequestStream(StreamConfig(mean_rate=12.0, seed=3),
                               horizon=48)
        stats = stream.drive(eng)
        return eng, stats

    e0, s0 = drive(None)
    e1, s1 = drive(INERT)
    for f in ("decisions", "admitted", "finished", "evicted_events",
              "tokens_generated", "guard_trips", "guard_open_steps",
              "guard_deferred"):
        assert getattr(s0, f) == getattr(s1, f), f
    assert s0.qos_series == s1.qos_series
    assert s0.penalty_series == s1.penalty_series


def test_guard_metrics_empty_when_off():
    ts = _taskset(arrival=[0, 1], request=[0.3, 0.3])
    cfg = SimConfig(n_nodes=2, n_slots=8, arrivals_per_slot=4,
                    retry_capacity=4)
    res = run(ts, cfg, "flex-f")
    assert res.metrics.guard_tripped.shape == (8, 0)
    assert res.metrics.n_guard_deferred.shape == (8, 0)
    assert res.metrics.guard_err_q.shape == (8, 0)


# --------------------------------------------------------- watchdog math

def test_drift_window_matches_numpy_oracle():
    # Ring-push + windowed quantile vs a numpy sliding-window oracle over
    # a random error stream (the cold window is zero-padded on both
    # sides, so early slots compare too).
    rng = np.random.default_rng(0)
    W, R, steps, q = 7, 2, 25, 0.9
    errs = rng.uniform(0.0, 0.5, size=(steps, R)).astype(np.float32)
    win = wd.init_window(W, R)
    for t in range(steps):
        win = push_errors(win, jnp.asarray(errs[t]))
        stat = float(trip_statistic(win, q))
        hist = np.zeros((W, R), np.float32)
        take = errs[max(0, t - W + 1):t + 1][::-1]
        hist[:len(take)] = take
        oracle = float(np.max(np.quantile(hist, q, axis=0)))
        assert stat == pytest.approx(oracle, abs=1e-6), t
        # newest sample sits at row 0 (the degrade push_window idiom)
        np.testing.assert_allclose(np.asarray(win[0]), errs[t])


def test_breaker_never_trips_under_exact_estimator():
    # The 'current' estimator reproduces last slot's usage exactly; on a
    # churn-free workload (zero noise, everything admitted at slot 0 and
    # resident past the horizon) the drift is the admission transient
    # only, far under the default threshold — the breaker must stay
    # CLOSED for the whole run and defer nothing.
    ts = _taskset(arrival=[0, 0, 0, 0], request=[0.3] * 4, duration=100,
                  mean_frac=0.2)
    cfg = SimConfig(n_nodes=4, n_slots=32, arrivals_per_slot=8,
                    retry_capacity=8, reclamation=True, reclaim_pool=16,
                    estimator="current", guard=GuardConfig())
    res = run(ts, cfg, "flex-f")
    assert (np.asarray(res.metrics.guard_tripped) == CLOSED).all()
    assert int(res.metrics.n_guard_deferred[-1]) == 0


# ------------------------------------------------------ breaker semantics

def _step_seq(cfg, errs, state=CLOSED, timer=0):
    states = []
    for e in errs:
        state, timer, _ = breaker_step(jnp.int32(state), jnp.int32(timer),
                                       jnp.float32(e), cfg)
        state, timer = int(state), int(timer)
        states.append(state)
    return states, state, timer


def test_breaker_trajectory_trip_cooldown_halfopen_close():
    cfg = GuardConfig(trip_threshold=0.1, cooldown=3, probe_slots=2)
    hi, lo = 0.5, 0.01
    # one drifting slot trips it immediately (the new state governs the
    # slot), then cooldown slots of OPEN, a clean 2-slot probe, CLOSED.
    states, *_ = _step_seq(cfg, [lo, hi, lo, lo, lo, lo, lo, lo, lo])
    assert states == [CLOSED, OPEN, OPEN, OPEN, HALF_OPEN, HALF_OPEN,
                      CLOSED, CLOSED, CLOSED]


def test_breaker_halfopen_retrips_on_renewed_drift():
    cfg = GuardConfig(trip_threshold=0.1, cooldown=3, probe_slots=4)
    hi, lo = 0.5, 0.01
    states, state, timer = _step_seq(cfg, [hi, lo, lo, lo, hi])
    assert states == [OPEN, OPEN, OPEN, HALF_OPEN, OPEN]
    assert timer == cfg.cooldown           # re-trip re-arms the cooldown


def test_breaker_open_expiry_under_drift_reopens():
    # Sustained drift across the whole cooldown: the breaker must re-open
    # rather than leak a half-open slot at expiry.
    cfg = GuardConfig(trip_threshold=0.1, cooldown=2, probe_slots=2)
    states, *_ = _step_seq(cfg, [0.5] * 6)
    assert states == [OPEN] * 6


def test_reclaim_width_by_state():
    cfg = GuardConfig(probe_reclaim=3)
    assert int(reclaim_width(jnp.int32(CLOSED), 16, cfg)) == 16
    assert int(reclaim_width(jnp.int32(OPEN), 16, cfg)) == 0
    assert int(reclaim_width(jnp.int32(HALF_OPEN), 16, cfg)) == 3
    # trickle never exceeds the pool
    assert int(reclaim_width(jnp.int32(HALF_OPEN), 2,
                             GuardConfig(probe_reclaim=8))) == 2


def test_sim_surge_trips_breaker_and_suspends_reclaim():
    # A demand ramp (usage_surge) drives the windowed estimator's drift
    # over the threshold: the breaker must trip, suspend the reclaim pass
    # (deferred counter grows while open), and report the quantile.
    ts = generate_calibrated(3, 8, 48, offered_load=1.6)
    cfg = SimConfig(n_nodes=8, n_slots=48, arrivals_per_slot=64,
                    retry_capacity=32, reclamation=True, reclaim_pool=64,
                    estimator="ewma",
                    faults=FaultConfig(),
                    guard=GuardConfig(window=6, trip_threshold=0.05,
                                      cooldown=8, probe_slots=4))
    sched = usage_surge(48, 8, start=12, ramp=8, hold=8, peak_mult=3.0)
    res = run(ts, cfg, "flex-f", fault_schedule=sched)
    states = np.asarray(res.metrics.guard_tripped)
    assert (states == OPEN).any()
    assert states[0] == CLOSED          # zero-initialized window never trips
                                        # before the first observation
    rep = analysis.guard_report(res)
    assert rep["guard_trips"] >= 1
    assert rep["open_frac"] > 0
    assert rep["err_q_max"] > 0.05
    assert int(res.metrics.n_guard_deferred[-1]) > 0


def test_blend_estimate_open_uses_requested():
    est = jnp.asarray([[0.2, 0.1], [0.4, 0.3]], jnp.float32)
    req = jnp.asarray([[0.6, 0.05], [0.5, 0.9]], jnp.float32)
    cfg = GuardConfig(open_blend=1.0)
    closed = wd.blend_estimate(est, req, jnp.asarray(False), cfg)
    np.testing.assert_allclose(np.asarray(closed), np.asarray(est))
    opened = wd.blend_estimate(est, req, jnp.asarray(True), cfg)
    # one-sided: max(est, requested) at blend weight 1
    np.testing.assert_allclose(np.asarray(opened),
                               np.maximum(np.asarray(est), np.asarray(req)))


# -------------------------------------------------------- serving engine

def test_engine_guard_defers_batch_keeps_production():
    # A usage shock drifts the windowed estimator; the engine breaker must
    # trip and defer sub-production admissions brownout-style while open.
    cfg = EngineConfig(
        n_replicas=4, estimator="ewma",
        guard=GuardConfig(window=6, trip_threshold=0.02, cooldown=6,
                          probe_slots=3, probe_reclaim=2))
    eng = ServeEngine(cfg, seed=3)
    stream = RequestStream(
        StreamConfig(mean_rate=12.0, seed=3, shock_start=16, shock_len=12,
                     shock_mult=3.0), horizon=48)
    stats = stream.drive(eng)
    assert stats.guard_trips >= 1
    assert stats.guard_open_steps > 0
    assert stats.guard_deferred > 0


def test_engine_guard_halfopen_trickle_bounded():
    # Force HALF_OPEN and check one admission pass: batch traffic beyond
    # the probe_reclaim FIFO head must stay queued; production passes.
    from repro.serving.engine import Request

    cfg = EngineConfig(
        n_replicas=2, estimator="current",
        guard=GuardConfig(probe_reclaim=2))
    eng = ServeEngine(cfg, seed=0)
    eng.refresh_snapshots()
    eng._g_state = HALF_OPEN
    eng._g_timer = 3
    for i in range(6):
        eng.submit(Request(rid=i, prompt_len=16, max_tokens=16,
                           true_tokens=8))
    eng.submit(Request(rid=99, prompt_len=16, max_tokens=16,
                       true_tokens=8, priority=CLASS_PRODUCTION))
    eng.admit_pending()
    admitted = {r.rid for rs in eng.active.values() for r in rs}
    assert 99 in admitted                      # production always lands
    assert admitted >= {0, 1, 99}              # FIFO-head trickle admitted
    assert len(admitted) == 3                  # nothing beyond the trickle
    assert eng.stats.guard_deferred == 4


# ----------------------------------------------------- analysis plumbing

def test_guard_report_raises_without_guard():
    ts = _taskset(arrival=[0], request=[0.3])
    res = run(ts, SimConfig(n_nodes=2, n_slots=8, arrivals_per_slot=4,
                            retry_capacity=4), "flex-f")
    with pytest.raises(ValueError, match="guard"):
        analysis.guard_report(res)


def test_summarize_warns_but_survives_without_guard():
    ts = generate_calibrated(4, 4, 16, offered_load=1.2)
    cfg = SimConfig(n_nodes=4, n_slots=16, arrivals_per_slot=32,
                    retry_capacity=16)
    res = run(ts, cfg, "flex-f")
    # the guard is off by default: its keys are simply absent, and no
    # guard warning is raised (only the machine-level one, whose series
    # this run did not record)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = analysis.summarize(ts, res, qos_target=0.99)
    assert not [w for w in caught if "guard" in str(w.message)]
    for k in ("guard_trips", "open_frac", "half_open_frac",
              "n_guard_deferred", "err_q_max", "err_q_mean"):
        assert k not in out, k
    assert "qos_mean" in out


def test_summarize_includes_guard_keys_when_on():
    ts = generate_calibrated(4, 4, 16, offered_load=1.2)
    cfg = SimConfig(n_nodes=4, n_slots=16, arrivals_per_slot=32,
                    retry_capacity=16, guard=GuardConfig())
    res = run(ts, cfg, "flex-f")
    out = analysis.summarize(ts, res, qos_target=0.99)
    for k in ("guard_trips", "open_frac", "half_open_frac",
              "n_guard_deferred", "err_q_max", "err_q_mean"):
        assert k in out, k


# -------------------------------------------- fail-fast config validation

@pytest.mark.parametrize("kwargs", [
    dict(window=0), dict(window=-3), dict(err_quantile=1.5),
    dict(err_quantile=-0.1), dict(trip_threshold=0.0),
    dict(trip_threshold=-1.0), dict(cooldown=0), dict(probe_slots=-1),
    dict(probe_reclaim=-1), dict(open_blend=2.0), dict(guard_scale=-0.5),
])
def test_guardconfig_rejects_degenerate(kwargs):
    with pytest.raises(ValueError):
        GuardConfig(**kwargs)


@pytest.mark.parametrize("kwargs", [
    dict(crash_rate=-0.1), dict(crash_rate=1.5), dict(crash_duration=0),
    dict(flap_rate=-1.0), dict(flap_capacity=-0.5), dict(surge_frac=2.0),
    dict(surge_mult=0.0), dict(surge_duration=-4), dict(storm_rate=-0.2),
    dict(storm_slowdown=-1.0), dict(warn_slots=-1), dict(qos_window=0),
    dict(degrade_evict=-1), dict(burst_slot=-2), dict(burst_frac=1.1),
])
def test_faultconfig_rejects_degenerate(kwargs):
    with pytest.raises(ValueError):
        FaultConfig(**kwargs)


@pytest.mark.parametrize("kwargs", [
    dict(bandwidth=-1), dict(migrate_cost=-1), dict(pool_size=0),
    dict(overload_threshold=-0.1), dict(margin_scale=-1.0),
])
def test_migrationconfig_rejects_degenerate(kwargs):
    with pytest.raises(ValueError):
        MigrationConfig(**kwargs)


def test_config_validation_covers_replace():
    with pytest.raises(ValueError):
        GuardConfig()._replace(window=-1)
    with pytest.raises(ValueError):
        FaultConfig()._replace(crash_rate=2.0)
    with pytest.raises(ValueError):
        MigrationConfig()._replace(pool_size=-5)


def test_config_defaults_still_construct():
    GuardConfig()
    FaultConfig()
    MigrationConfig()
    assert SimConfig().guard is None
    assert EngineConfig().guard is None
