"""End-to-end system behaviour: the paper's headline claims, reduced scale.

(The numeric claims are scale-dependent; these assertions check the
ORDERING the paper establishes, with generous margins.)"""
import jax.numpy as jnp
import pytest

from repro.core import FlexParams, SimConfig, run
from repro.traces import analysis, generate_calibrated

CFG = SimConfig(n_nodes=150, n_slots=64, arrivals_per_slot=512,
                retry_capacity=128)


@pytest.fixture(scope="module")
def world():
    ts = generate_calibrated(1, CFG.n_nodes, CFG.n_slots, 1.6)
    out = {}
    for kind in ("least-fit", "oversub", "flex-f", "flex-l"):
        params = FlexParams.default(
            theta=2.0 if kind == "oversub" else 1.0)
        out[kind] = analysis.summarize(ts, run(ts, CFG, kind, params), 0.99)
    return out


def test_flex_utilization_gain(world):
    """Paper Fig. 6: Flex reaches ~1.6x LeastFit utilization."""
    gain = (world["flex-f"]["avg_usage_cpu"]
            / world["least-fit"]["avg_usage_cpu"])
    assert gain > 1.35, gain


def test_flex_admits_more_requests(world):
    """Paper Fig. 6: Flex admits up to 1.74x more requests."""
    gain = (world["flex-f"]["avg_request_cpu"]
            / world["least-fit"]["avg_request_cpu"])
    assert gain > 1.35, gain


def test_flex_matches_oversub_utilization(world):
    ratio = (world["flex-f"]["avg_usage_cpu"]
             / world["oversub"]["avg_usage_cpu"])
    assert ratio > 0.8, ratio


def test_flex_qos_beats_oversub(world):
    """Paper Fig. 7: Flex maintains the QoS target, Oversub violates."""
    assert (world["flex-f"]["qos_violation_frac"]
            <= world["oversub"]["qos_violation_frac"])
    assert world["flex-f"]["qos_mean"] >= 0.985


def test_flex_load_balance_beats_oversub(world):
    """Paper Fig. 9: Flex spreads load at least as well as Oversub."""
    assert (world["flex-l"]["mean_norm_std_mem"]
            <= world["oversub"]["mean_norm_std_mem"] * 1.15)
