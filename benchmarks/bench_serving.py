"""Serving-engine admission at production rate (ISSUE 7 tentpole bench).

Two question this bench answers, both recorded into
``BENCH_serving.json`` (guarded by ``scripts/check_bench.py``):

1. **Hot-loop speedup** — ``serve_depth*`` rows: at queue depth >= 256,
   how many admission decisions/sec does each execution mode sustain?
   ``eager`` is the pre-batching per-request loop (the baseline the
   ISSUE's >=3x bar is measured against), ``sequential`` the jitted
   lax.scan, ``wavefront`` the batched top-K kernel path.  All three
   make bit-identical decisions (tests/test_serving_parity.py), so this
   is a pure execution-shape comparison.

2. **Steady state under live arrivals** — ``serve_<pattern>`` rows: the
   engine driven OPEN-LOOP by ``serving.stream.RequestStream`` under
   Poisson / diurnal / burst arrivals, reporting admission-latency
   percentiles (p50/p95/p99 ms per admission pass), eviction rate, QoS
   and utilization at steady state.

``us_per_call`` is the mean wall time of one admission pass;
``decisions_per_s`` (the check_bench regression metric) counts every
admission decision evaluated (admitted OR blocked) against the wall
time spent inside admission.
"""
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.serving.stream import RequestStream, StreamConfig
from repro.traces.generator import ARRIVAL_PATTERNS


def _pad_widths(admit_batch: int):
    w, out = 8, []
    while w < admit_batch:
        out.append(w)
        w *= 2
    out.append(admit_batch)
    return out


def _warm_admitter(eng: ServeEngine):
    """Pre-compile the jitted admission entry at every pad width.

    The engine pads queues to power-of-two widths, so the first pass at
    each width pays XLA compilation; warming keeps compile time out of
    the reported latency percentiles (engine stats are untouched —
    ``_admit_fn`` is pure)."""
    if eng.cfg.admission_mode == "eager":
        return
    node = eng.node_state()
    pen = jnp.asarray(1.0, jnp.float32)
    for w in _pad_widths(eng.cfg.admit_batch):
        eng._admit_fn(node, jnp.zeros((w, 2), jnp.float32),
                      jnp.zeros(w, jnp.int32), jnp.zeros(w, jnp.int32),
                      jnp.zeros(w, bool), pen)


def _admission_metrics(stats):
    lat = np.asarray(stats.admit_latency_s, float)
    wall = float(lat.sum())
    return {
        "decisions_per_s": stats.decisions / max(wall, 1e-9),
        "adm_p50_ms": float(np.percentile(lat, 50) * 1e3),
        "adm_p95_ms": float(np.percentile(lat, 95) * 1e3),
        "adm_p99_ms": float(np.percentile(lat, 99) * 1e3),
    }, float(lat.mean() * 1e6)


def _depth_workload(n_req: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt_len=int(rng.integers(16, 64)),
                    max_tokens=int(rng.integers(128, 512)),
                    true_tokens=int(rng.integers(96, 256)),
                    src=int(rng.integers(0, 16)))
            for i in range(n_req)]


def run(full: bool):
    rows = []

    # --- hot-loop: decisions/sec per execution mode at depth >= 256 ---
    n_req = 4096 if full else 1024
    steps = 8 if full else 4
    base = None
    for mode in ("eager", "sequential", "wavefront"):
        cfg = EngineConfig(n_replicas=4, kv_budget_tokens=65536,
                           policy="flex-f", max_active_per_replica=64,
                           admission_mode=mode, admit_batch=256)
        eng = ServeEngine(cfg)
        _warm_admitter(eng)
        for r in _depth_workload(n_req):
            eng.submit(r)
        eng.run(steps)
        depth = len(eng.queue)
        metrics, us = _admission_metrics(eng.stats)
        dps = metrics["decisions_per_s"]
        if mode == "eager":
            base = dps
        rows.append(Row(f"serve_depth256_{mode}", us, {
            "decisions_per_s": dps,
            "speedup_vs_eager": dps / max(base, 1e-9),
            "min_queue_depth": depth,
        }))

    # --- steady state under open-loop arrivals, per pattern ---
    horizon = 600 if full else 160
    rate = 64.0 if full else 24.0
    for pattern in ARRIVAL_PATTERNS:
        cfg = EngineConfig(n_replicas=8, kv_budget_tokens=8192,
                           policy="flex-f", max_active_per_replica=64,
                           admission_mode="wavefront", admit_batch=256)
        eng = ServeEngine(cfg)
        _warm_admitter(eng)
        stream = RequestStream(
            StreamConfig(pattern=pattern, mean_rate=rate, seed=7),
            horizon=horizon)
        t0 = time.time()
        stats = stream.drive(eng, steps=horizon + horizon // 4)
        wall = time.time() - t0
        metrics, us = _admission_metrics(stats)
        rows.append(Row(f"serve_{pattern}", us, {
            **metrics,
            "evict_rate": stats.evicted_events / max(stats.admitted, 1),
            "qos_final": stats.qos_series[-1],
            "mean_util": float(np.mean(stats.util_series)),
            "finished": stats.finished,
            "wall_s": wall,
        }))
    return rows
