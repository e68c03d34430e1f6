"""Serving engine: Flex admission vs LeastFit, eviction, stragglers,
eviction/re-queue invariants, and registry policy resolution."""
import numpy as np
import pytest

from repro.api import resolve_policy
from repro.serving.engine import EngineConfig, Request, ServeEngine


def _reqs(n, over=3.0, true=20, prompt=20, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt_len=prompt,
                    max_tokens=int(true * over), true_tokens=true)
            for i in range(n)]


def _engine(policy, **kw):
    cfg = EngineConfig(n_replicas=2, kv_budget_tokens=400, policy=policy,
                       max_active_per_replica=32, **kw)
    return ServeEngine(cfg)


def test_flex_admits_more_than_reserve():
    # Round 1 is identical (no usage signal yet); once usage is measured,
    # flex packs by the real footprints instead of the declared ones and
    # carries far more concurrent work.
    concurrent = {}
    for pol in ("least-fit", "flex-f"):
        eng = _engine(pol)
        for r in _reqs(64, true=30):
            eng.submit(r)
        peak = 0
        for _ in range(8):
            eng.step()
            peak = max(peak, sum(len(v) for v in eng.active.values()))
        concurrent[pol] = peak
    assert concurrent["flex-f"] > concurrent["least-fit"]


def test_reserve_never_evicts():
    eng = _engine("least-fit")
    for r in _reqs(64):
        eng.submit(r)
    stats = eng.run(200)
    assert stats.evicted_events == 0
    assert stats.finished == 64


def test_flex_eviction_and_recovery():
    # adversarial: declared == true (no over-estimation), so usage-based
    # over-admission must overflow, evict, and the penalty must rise
    eng = _engine("flex-f")
    for r in _reqs(64, over=1.0, true=60, prompt=40):
        eng.submit(r)
    stats = eng.run(900)
    assert stats.evicted_events > 0
    assert max(stats.penalty_series) > 1.0
    assert stats.finished == 64          # evicted requests eventually finish


def test_straggler_avoidance():
    eng = _engine("flex-f")
    eng.step_time_ema = np.asarray([1.0, 10.0])   # replica 1 is slow
    for r in _reqs(8):
        eng.submit(r)
    eng.step()
    assert len(eng.active[0]) > len(eng.active[1])


# ---------------------------------------------------------------------------
# eviction / re-queue invariants (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

def _overflow_engine():
    """One replica, honest clients (declared == true): flex over-admits by
    usage and MUST overflow once generation catches up."""
    eng = ServeEngine(EngineConfig(
        n_replicas=1, kv_budget_tokens=300, policy="flex-f",
        max_active_per_replica=16))
    for r in _reqs(12, over=1.0, true=60, prompt=40):
        eng.submit(r)
    return eng


def test_eviction_order_newest_admission_first():
    """Victims are the most recently admitted residents, evicted in
    reverse admission order (LIFO), until the replica fits again."""
    admit_order, evict_log = [], []
    eng = _overflow_engine()
    eng.on_admit = lambda r: admit_order.append(r.rid)
    eng.on_evict = lambda r: evict_log.append(
        (r.rid, [q.rid for q in eng.active[0]]))
    eng.run(60)
    assert evict_log, "overflow scenario produced no evictions"
    seniority = {rid: k for k, rid in enumerate(admit_order)}
    for rid, residents_after in evict_log:
        # every request still resident when rid was evicted was admitted
        # no later than rid (ties: re-admissions refresh seniority)
        assert all(seniority[q] <= seniority[rid] for q in residents_after)


def test_evicted_requests_requeue_fifo_stable():
    """Evicted requests re-enter the queue ahead of fresh arrivals, in
    their original admission order, with progress reset."""
    eng = _overflow_engine()
    evicted_this_step = []
    eng.on_evict = lambda r: evicted_this_step.append(r.rid)
    for _ in range(60):
        evicted_this_step.clear()
        head_before = [r.rid for r in eng.queue]
        eng.step()
        if evicted_this_step:
            victims = [r for r in eng.queue
                       if r.rid in set(evicted_this_step)]
            # progress reset, detached from the replica
            assert all(r.generated == 0 and r.replica == -1 and not r.done
                       for r in victims)
            # FIFO-stable: victims sit at the head in admission (= rid
            # submission) order, ahead of everything previously queued
            rids = [r.rid for r in eng.queue]
            n = len(evicted_this_step)
            assert rids[:n] == sorted(evicted_this_step)
            assert rids[n:] == head_before


def test_eviction_counters_monotone():
    eng = _overflow_engine()
    per_req_max = {}
    last_events = 0
    for _ in range(60):
        eng.step()
        assert eng.stats.evicted_events >= last_events
        last_events = eng.stats.evicted_events
        for reqs in list(eng.active.values()) + [list(eng.queue)]:
            for r in reqs:
                assert r.evictions >= per_req_max.get(r.rid, 0)
                per_req_max[r.rid] = r.evictions
    assert last_events > 0
    assert last_events == sum(per_req_max.values())


def test_no_request_both_done_and_resident():
    """A finished request leaves its replica the same step it completes;
    a resident (or queued) request is never marked done."""
    eng = _overflow_engine()
    done_rids = set()
    for _ in range(60):
        eng.step()
        for i, reqs in eng.active.items():
            for r in reqs:
                assert not r.done, f"done request {r.rid} resident on {i}"
                assert r.replica == i
        for r in eng.queue:
            assert not r.done and r.replica == -1
        done_rids = {r.rid for i in eng.active for r in eng.active[i]
                     if r.done} | done_rids
    assert not done_rids


# ---------------------------------------------------------------------------
# registry policy resolution (ISSUE 7 satellite fix)
# ---------------------------------------------------------------------------

def test_policy_resolves_through_registry():
    # the engine resolves its policy with the simulator's resolve_policy:
    # a registry name or an object, "flex-f" by default
    assert ServeEngine(EngineConfig(n_replicas=2)).policy.name == "flex-f"
    for name in ("flex-f", "least-fit", "flex-priority", "best-fit-usage"):
        eng = ServeEngine(EngineConfig(n_replicas=2, policy=name))
        assert eng.policy == resolve_policy(name)
        assert eng.policy.name == name
    pol = resolve_policy("flex-l")
    assert ServeEngine(EngineConfig(n_replicas=2, policy=pol)).policy is pol


def test_unknown_policy_name_errors():
    """Unknown names must raise (listing what IS registered), not fall
    through to FLEX semantics as the pre-registry engine did."""
    with pytest.raises(KeyError, match="registered"):
        ServeEngine(EngineConfig(n_replicas=2, policy="flex-typo"))


def test_registry_policy_runs_end_to_end():
    eng = ServeEngine(EngineConfig(
        n_replicas=2, kv_budget_tokens=400, policy="flex-priority",
        max_active_per_replica=8, admit_batch=16))
    for r in _reqs(12, true=20):
        r.priority = r.rid % 2
        eng.submit(r)
    stats = eng.run(60)
    assert stats.finished == 12


def test_unknown_admission_mode_errors():
    with pytest.raises(ValueError, match="admission_mode"):
        ServeEngine(EngineConfig(n_replicas=2, admission_mode="batchy"))
