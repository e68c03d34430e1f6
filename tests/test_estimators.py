"""Estimator subsystem: registry, built-ins, legacy shims, reclamation.

The contract under test (docs/api.md "Estimators"):
  * built-ins behave (ewma(decay=0) is `current`; noise never goes
    negative; `quantile` matches a numpy sliding-window oracle);
  * the legacy knobs (`est_noise_std`, stateless estimator objects) and
    `SimConfig.estimator` resolve BIT-IDENTICALLY to the registry path;
  * the headroom-reclamation pass admits materially more tasks than the
    `current`-estimator baseline at equal-or-lower QoS violation, and
    reuses the wavefront admission path (no second code path);
  * `analysis.summarize` degrades gracefully without per-node series.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EwmaEstimator as LegacyEwmaEstimator
from repro.core import SimConfig, run
from repro.estimators import (
    CurrentEstimator,
    EwmaEstimator,
    LearnedUsageEstimator,
    QuantileWindowEstimator,
    as_stateful,
    get_estimator,
    list_estimators,
    resolve_estimator,
    train_usage_predictor,
)
from repro.traces import analysis, generate_calibrated

CFG = SimConfig(n_nodes=60, n_slots=32, arrivals_per_slot=256,
                retry_capacity=64)
QOS_TARGET = 0.99


@pytest.fixture(scope="module")
def ts():
    return generate_calibrated(0, CFG.n_nodes, CFG.n_slots, 1.5)


def _usage_seq(n_steps, n_nodes=5, n_res=2, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(0, 1, (n_steps, n_nodes, n_res)),
                       jnp.float32)


def _drive(est, seq, key=None):
    """Run a measurement sequence through an estimator; return est series."""
    key = key if key is not None else jax.random.PRNGKey(0)
    state = est.init_state(seq.shape[1], seq.shape[2])
    out = []
    for t in range(seq.shape[0]):
        state = est.refresh(state, seq[t], jax.random.fold_in(key, t))
        out.append(state.est)
    return jnp.stack(out)


# ---------------------------------------------------------------- registry

def test_builtins_registered():
    assert {"current", "ewma", "quantile", "learned"} <= set(
        list_estimators())


def test_get_estimator_roundtrip():
    est = get_estimator("quantile")
    assert hasattr(est, "init_state") and hasattr(est, "refresh")
    hash(est)  # must stay a static-jit argument


def test_unknown_name_raises():
    with pytest.raises(KeyError, match="unknown estimator"):
        get_estimator("no-such-estimator")


def test_noise_only_for_current():
    assert isinstance(resolve_estimator("current", 0.3), CurrentEstimator)
    with pytest.raises(ValueError, match="est_noise_std"):
        resolve_estimator("ewma", 0.3)


# ---------------------------------------------------------------- built-ins

def test_ewma_zero_decay_is_current():
    seq = _usage_seq(6)
    a = _drive(EwmaEstimator(decay=0.0), seq)
    b = _drive(CurrentEstimator(), seq)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_current_noise_never_negative():
    seq = _usage_seq(32)
    est = _drive(CurrentEstimator(noise_std=3.0), seq)
    assert float(jnp.min(est)) >= 0.0
    assert float(jnp.std(est - seq)) > 0.0  # noise actually applied


def test_quantile_matches_numpy_oracle():
    window, q = 4, 0.9
    seq = _usage_seq(9)
    got = np.asarray(_drive(QuantileWindowEstimator(window=window, q=q),
                            seq))
    us = np.asarray(seq)
    for t in range(len(us)):
        # ring semantics: history shorter than the window is padded with
        # the FIRST measurement (the t==0 broadcast fill)
        hist = [us[0]] * max(window - 1 - t, 0) + list(
            us[max(t - window + 1, 0):t + 1])
        want = np.quantile(np.stack(hist), q, axis=0).astype(np.float32)
        np.testing.assert_allclose(got[t], want, atol=1e-6)


def test_untrained_learned_is_current():
    seq = _usage_seq(8)
    a = _drive(LearnedUsageEstimator.untrained(window=4), seq)
    b = _drive(CurrentEstimator(), seq)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stateless_adapter_matches_builtin():
    seq = _usage_seq(7)
    a = _drive(as_stateful(LegacyEwmaEstimator(decay=0.7)), seq)
    b = _drive(EwmaEstimator(decay=0.7), seq)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- legacy shims

def _fingerprint(res):
    return (np.asarray(res.placement), np.asarray(res.metrics.usage),
            np.asarray(res.metrics.qos))


def test_estimator_resolution_bit_identical(ts):
    """"ewma" registry name == legacy stateless object ==
    SimConfig.estimator; an explicit estimator wins over the config's."""
    via_kind = run(ts, CFG._replace(estimator="current"), "flex-f",
                   estimator="ewma")
    via_name = run(ts, CFG, "flex-f", estimator="ewma")
    via_obj = run(ts, CFG, "flex-f",
                  estimator=LegacyEwmaEstimator(decay=0.7))
    via_cfg = run(ts, CFG._replace(estimator="ewma"), "flex-f")
    for a, b in zip(_fingerprint(via_kind), _fingerprint(via_name)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_fingerprint(via_kind), _fingerprint(via_obj)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_fingerprint(via_kind), _fingerprint(via_cfg)):
        np.testing.assert_array_equal(a, b)


def test_noise_shim_bit_identical(ts):
    via_kind = run(ts, CFG, "flex-f", estimator="current",
                   est_noise_std=0.2)
    via_obj = run(ts, CFG, "flex-f",
                  estimator=CurrentEstimator(noise_std=0.2))
    for a, b in zip(_fingerprint(via_kind), _fingerprint(via_obj)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------- reclamation

@pytest.fixture(scope="module")
def reclaim_runs(ts):
    base = run(ts, CFG._replace(estimator="current"), "least-fit")
    recl = run(ts, CFG._replace(estimator="quantile", reclamation=True,
                                reclaim_pool=256), "least-fit")
    return base, recl


def test_reclamation_admits_more_at_equal_qos(ts, reclaim_runs):
    """The acceptance bar: predictive estimator + reclamation >= 1.2x
    admitted vs `current`, QoS-violation fraction no worse."""
    base, recl = reclaim_runs
    n_base = int((np.asarray(base.placement) >= 0).sum())
    n_recl = int((np.asarray(recl.placement) >= 0).sum())
    assert int(recl.metrics.n_reclaimed[-1]) > 0
    assert n_recl >= 1.2 * n_base
    viol_base = float(np.mean(np.asarray(base.metrics.qos) < QOS_TARGET))
    viol_recl = float(np.mean(np.asarray(recl.metrics.qos) < QOS_TARGET))
    assert viol_recl <= viol_base


def test_reclamation_respects_capacity(reclaim_runs):
    _, recl = reclaim_runs
    assert np.isfinite(np.asarray(recl.metrics.usage)).all()
    pl = np.asarray(recl.placement)
    assert ((pl >= -1) & (pl < CFG.n_nodes)).all()


def test_reclamation_off_keeps_counter_zero(reclaim_runs):
    base, _ = reclaim_runs
    assert int(base.metrics.n_reclaimed[-1]) == 0


def test_no_second_admission_path():
    """Reclamation must route through admit_queue's wavefront batch path,
    not a parallel implementation: the reclaim policy exposes the
    kernel_inputs hook admit_queue dispatches on, and the simulator has
    exactly one admission entry point for the pass."""
    import inspect

    from repro.api import ReclaimPolicy, policy_supports_kernel
    from repro.core import simulator

    assert policy_supports_kernel(ReclaimPolicy())
    src = inspect.getsource(simulator)
    # no direct wavefront calls: both the regular and the reclaim pass go
    # through the shared admission.admit_queue front-end
    assert "admit_queue_wavefront(" not in src
    assert src.count("admission.admit_queue(") >= 2


# ------------------------------------------------------------- observability

def test_summarize_degrades_gracefully(ts, reclaim_runs):
    base, _ = reclaim_runs  # no record_node_usage
    with pytest.warns(UserWarning, match="record_node_usage"):
        s = analysis.summarize(ts, base, QOS_TARGET)
    assert "admitted_frac" in s and "n_reclaimed" in s
    assert not any(k.startswith("est_abs_err") for k in s)


def test_summarize_includes_estimator_keys_when_recorded(ts):
    res = run(ts, CFG._replace(estimator="ewma", record_node_usage=True),
              "flex-f")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no graceful-degradation warning
        s = analysis.summarize(ts, res, QOS_TARGET)
    for key in ("est_abs_err_cpu_p50", "est_bias_mem", "mean_overprov_cpu",
                "zombie_frac_cpu", "usage_to_cap_cpu_p50"):
        assert key in s, key


def test_machine_level_error_names_the_knob(ts, reclaim_runs):
    base, _ = reclaim_runs
    with pytest.raises(ValueError, match="record_node_usage=True"):
        analysis.machine_level(base)
    with pytest.raises(ValueError, match="record_node_usage=True"):
        analysis.estimator_error(base)


# ------------------------------------------------------------ learned (slow)

@pytest.mark.slow
def test_learned_trains_checkpoints_reloads(ts, tmp_path):
    params, losses = train_usage_predictor(
        ts, window=6, hidden=4, n_slots=CFG.n_slots, steps=40,
        batch_size=256, seed=0, ckpt_dir=str(tmp_path))
    assert losses[-1] < losses[0]  # training actually reduced the loss

    est = LearnedUsageEstimator.from_checkpoint(str(tmp_path))
    assert est.window == 6 and est.hidden == 4

    # the reloaded estimator predicts like the in-memory one ...
    seq = _usage_seq(8)
    a = _drive(est, seq)
    b = _drive(LearnedUsageEstimator.from_params(params, window=6,
                                                 hidden=4), seq)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    # ... and runs end-to-end through the simulator + reclamation pass
    res = run(ts, CFG._replace(reclamation=True, reclaim_pool=128),
              "least-fit", estimator=est)
    assert np.isfinite(np.asarray(res.metrics.usage)).all()
    assert int((np.asarray(res.placement) >= 0).sum()) > 0
