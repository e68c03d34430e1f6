"""Engine ≡ simulator admission parity (ISSUE 7 property harness).

The serving engine no longer has admission logic of its own: it maps
replicas onto the simulator's NodeState and calls the same
``admission.admit_queue`` core the scheduler scan uses.  These tests
PROVE that, two ways:

* **engine ≡ admit_queue** — for randomized engine states (replica
  budgets, resident requests, declared/true footprints, penalty states,
  straggler EMAs), the placements the engine applies are bit-identical
  to calling ``admit_queue`` directly on the engine's own
  ``node_state()`` / ``_task_arrays()`` view — for the eager
  per-request loop, the jitted sequential scan, AND the wavefront
  batched path (which also proves the engine's power-of-two padding is
  decision-invariant);
* **mode ≡ mode over whole trajectories** — engines differing only in
  ``admission_mode`` produce identical admission/eviction event streams
  under open-loop arrivals, so the batched modes inherit the eager
  baseline's semantics through evictions, re-queues and penalty
  feedback, not just on a single pass.

The randomized suite is seeded numpy (>= 200 generated cases, always
run); a hypothesis-driven variant runs when hypothesis is installed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import admission
from repro.api.protocols import policy_queue_order
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.serving.stream import RequestStream, StreamConfig

PARITY_POLICIES = ["flex-f", "least-fit", "flex-priority"]

# width every reference call pads to: one compiled scan shape per policy
# for the whole module instead of one per random queue length (XLA's CPU
# backend has segfaulted compiling dozens of fresh shapes late in a long
# suite run)
REF_PAD_WIDTH = 16


@pytest.fixture(scope="module", autouse=True)
def _fresh_jax_caches():
    # shed executables accumulated by earlier test modules before this
    # compile-heavy module adds its own
    jax.clear_caches()
    yield


# ---------------------------------------------------------------------------
# randomized engine states
# ---------------------------------------------------------------------------

def _random_engine(rng: np.random.Generator, policy: str,
                   mode: str) -> ServeEngine:
    cfg = EngineConfig(
        n_replicas=4,
        kv_budget_tokens=int(rng.integers(200, 2000)),
        policy=policy,
        max_active_per_replica=int(rng.integers(4, 16)),
        straggler_weight=float(rng.uniform(0.0, 1.0)),
        admission_mode=mode,
        admit_batch=16,
    )
    eng = ServeEngine(cfg, seed=0)
    rid = 0
    # resident requests with partially-generated footprints
    for i in range(cfg.n_replicas):
        for _ in range(int(rng.integers(0, cfg.max_active_per_replica // 2))):
            true = int(rng.integers(4, 80))
            req = Request(rid=rid, prompt_len=int(rng.integers(4, 60)),
                          max_tokens=int(true * rng.uniform(1.0, 3.0)),
                          true_tokens=true, src=int(rng.integers(0, 8)),
                          priority=int(rng.integers(0, 2)),
                          generated=int(rng.integers(0, true)), replica=i)
            eng.active[i].append(req)
            rid += 1
    # pending queue, mixed feasible/oversized
    for _ in range(int(rng.integers(1, 14))):
        true = int(rng.integers(4, 120))
        eng.submit(Request(
            rid=rid, prompt_len=int(rng.integers(4, 80)),
            max_tokens=int(true * rng.uniform(1.0, 4.0)),
            true_tokens=true, src=int(rng.integers(0, 8)),
            priority=int(rng.integers(0, 2))))
        rid += 1
    # straggler EMAs + a random controller penalty state
    eng.step_time_ema = rng.uniform(0.5, 2.5, cfg.n_replicas)
    eng.ctrl = eng.ctrl._replace(
        penalty=jnp.asarray(float(rng.uniform(1.0, 4.0)), jnp.float32))
    eng.refresh_snapshots()
    return eng


def _reference_placements(eng: ServeEngine) -> np.ndarray:
    """Placements from admit_queue called directly on the engine's view —
    a single sequential scan: the simulator-side ground truth.

    Padded to the fixed REF_PAD_WIDTH with invalid entries, which is a
    *different* width than the engine's power-of-two padding for queues
    shorter than 8 — so agreement between the two sides still proves
    the decisions are padding-invariant.
    """
    reqs = list(eng.queue)
    q = len(reqs)
    assert q <= REF_PAD_WIDTH
    r, srcs, prios = eng._task_arrays(reqs)
    order = np.arange(q)
    hook = policy_queue_order(eng.policy)
    if hook is not None:
        order = np.asarray(hook(jnp.asarray(r), jnp.asarray(prios),
                                jnp.ones(q, bool)))
    rp = np.zeros((REF_PAD_WIDTH, r.shape[1]), np.float32)
    sp = np.zeros(REF_PAD_WIDTH, np.int32)
    pp = np.zeros(REF_PAD_WIDTH, np.int32)
    vp = np.zeros(REF_PAD_WIDTH, bool)
    rp[:q], sp[:q], pp[:q], vp[:q] = r[order], srcs[order], prios[order], True
    _, pl = admission.admit_queue(
        eng.policy, eng.node_state(), jnp.asarray(rp), jnp.asarray(sp),
        jnp.asarray(pp), jnp.asarray(vp),
        jnp.asarray(float(eng.ctrl.penalty), jnp.float32), eng.params)
    out = np.full(q, -1, np.int32)
    out[order] = np.asarray(pl)[:q]
    return out


def _engine_placements(eng: ServeEngine) -> np.ndarray:
    reqs = list(eng.queue)
    eng.admit_pending()
    return np.array([req.replica for req in reqs], np.int32)


@pytest.mark.parametrize("policy", PARITY_POLICIES)
def test_engine_matches_admit_queue_randomized(policy):
    """>= 70 cases per policy (210 total): every admission mode's decisions
    are bit-identical to direct admit_queue on the equivalent NodeState."""
    rng = np.random.default_rng(hash(policy) % 2**32)
    for case in range(70):
        seed_state = rng.integers(0, 2**31)
        for mode in ("eager", "sequential", "wavefront"):
            eng = _random_engine(np.random.default_rng(seed_state),
                                 policy, mode)
            expected = _reference_placements(eng)
            got = _engine_placements(eng)
            np.testing.assert_array_equal(
                got, expected,
                err_msg=f"policy={policy} mode={mode} case={case}")


@pytest.mark.parametrize("policy", ["flex-f", "least-fit"])
def test_trajectory_parity_across_modes(policy):
    """Whole open-loop trajectories (admission + eviction event streams,
    final stats) are identical across eager/sequential/wavefront."""
    def events(mode):
        cfg = EngineConfig(n_replicas=3, kv_budget_tokens=600,
                           policy=policy, max_active_per_replica=8,
                           admission_mode=mode, admit_batch=16)
        eng = ServeEngine(cfg, seed=0)
        log = []
        eng.on_admit = lambda r: log.append(("admit", r.rid, r.replica,
                                             eng.stats.steps))
        eng.on_evict = lambda r: log.append(("evict", r.rid, r.replica,
                                             eng.stats.steps))
        stream = RequestStream(StreamConfig(pattern="burst", mean_rate=3.0,
                                            prompt_mean=16,
                                            max_tokens_mean=48, seed=11),
                               horizon=40)
        stats = stream.drive(eng, steps=50)
        return log, (stats.admitted, stats.finished, stats.evicted_events,
                     tuple(stats.qos_series), tuple(stats.penalty_series))

    ref_log, ref_stats = events("eager")
    assert any(e[0] == "admit" for e in ref_log)
    for mode in ("sequential", "wavefront"):
        log, stats = events(mode)
        assert log == ref_log, f"event stream diverged in mode={mode}"
        assert stats == ref_stats, f"stats diverged in mode={mode}"


# ---------------------------------------------------------------------------
# hypothesis variant (skipped when hypothesis is unavailable)
# ---------------------------------------------------------------------------

def test_engine_matches_admit_queue_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           policy=st.sampled_from(PARITY_POLICIES),
           mode=st.sampled_from(("sequential", "wavefront")))
    def prop(seed, policy, mode):
        eng = _random_engine(np.random.default_rng(seed), policy, mode)
        expected = _reference_placements(eng)
        np.testing.assert_array_equal(_engine_placements(eng), expected)

    prop()
