#!/usr/bin/env python3
"""Chip smoke: Flex admission through both front ends on one TPU chip.

Everything runs in this one process (a chip belongs to one process), at
the paper's cluster size, with every admission shape on its real Pallas
kernel:

  (a) simulator — ``repro.api.Experiment`` on ``sim_setup(full=True)``
      (4000 nodes, 288 five-minute slots, ~700k calibrated tasks,
      queue width 1024 retries + 4096 arrivals), policy ``flex-f``, run
      three ways: the sequential reference scan, the per-task kernel
      (``use_kernel=True``) and wavefront admission over the batched
      top-K kernel.  Their ``analysis.summarize`` dicts must be equal.
  (b) reclamation — the same cluster with ``estimator="ewma",
      reclamation=True``: the reclaim pass admits its 256-wide pool
      through the batched kernel.  Its summary must be finite.
  (c) serving — ``repro.serving.ServeEngine`` with 8 replicas, policy
      ``flex`` and its default wavefront admission, driven open-loop by a
      burst ``RequestStream``; the same stream under sequential admission
      must give equal admitted, finished and decision counts.

Each phase prints its sizes, its compile and run seconds (results ended
with ``block_until_ready``) and the number of ``tpu_custom_call`` ops in
its compiled program, which proves the kernel ran and not the reference.
The last line is one JSON object naming the device.  Without a TPU the
script exits non-zero before any phase runs.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import math
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Queue-width-preserving horizon cut for the wavefront comparison: its
# conflict rounds cost about 3 s per slot at N = 4000, Q = 5120 on a
# v5e, so all 288 slots would not fit the smoke's time.  The wavefront
# run and its own reference run cover the first WAVEFRONT_SLOTS slots of
# the same trace; N and the queue widths stay at paper size.  (A
# one-slot simulator program trips an XLA TPU compiler check, so keep
# this at 2 or more.)
WAVEFRONT_SLOTS = 48
QOS_TARGET = 0.99


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def kernel_calls(compiled) -> int:
    """Pallas TPU kernels in a compiled program (0 = reference path)."""
    return compiled.as_text().count("tpu_custom_call")


def differing_keys(a: dict, b: dict) -> list:
    """Keys whose values differ (NaN equals NaN)."""
    keys = sorted(set(a) | set(b))
    return [k for k in keys
            if not (a.get(k) == b.get(k)
                    or (isinstance(a.get(k), float)
                        and isinstance(b.get(k), float)
                        and math.isnan(a[k]) and math.isnan(b[k])))]


def run_sim(label, ts, cfg, policy="flex-f"):
    """One Experiment run: AOT compile (timed, inspected), then exp.run()."""
    import jax

    from repro.api import Experiment
    from repro.core import simulator
    from repro.traces import analysis

    exp = Experiment(ts, cfg, policy=policy)
    t0 = time.perf_counter()
    compiled = simulator.simulate_core.lower(
        exp.trace, exp.arrival_table, exp.cluster, exp.policy, exp.params,
        jax.random.PRNGKey(0), exp.estimator, exp.controller).compile()
    compile_s = time.perf_counter() - t0
    n_kernel = kernel_calls(compiled)
    t0 = time.perf_counter()
    res = jax.block_until_ready(exp.run(seeds=0))
    run_s = time.perf_counter() - t0
    summary = analysis.summarize(ts, res, QOS_TARGET)
    print(f"  {label}: slots={cfg.n_slots} compile_s={compile_s:.3f} "
          f"run_s={run_s:.3f} tpu_custom_call={n_kernel} "
          f"n_admitted={summary['n_admitted']} "
          f"n_rejected={summary['n_rejected']} "
          f"n_reclaimed={summary['n_reclaimed']} "
          f"qos_mean={summary['qos_mean']!r} "
          f"final_penalty={summary['final_penalty']!r}", flush=True)
    return summary, n_kernel


def phase_simulator(ts, cfg, wavefront_slots, errors):
    print(f"phase a: simulator nodes={cfg.n_nodes} slots={cfg.n_slots} "
          f"tasks={ts.num_tasks} queue={cfg.retry_capacity}+"
          f"{cfg.arrivals_per_slot} policy=flex-f", flush=True)
    ref, n_ref = run_sim("reference", ts, cfg)
    ker, n_ker = run_sim("kernel", ts, cfg._replace(use_kernel=True))
    if n_ref != 0:
        errors.append(f"a: reference program holds {n_ref} kernels")
    if n_ker < 1:
        errors.append("a: per-task kernel program holds no tpu_custom_call")
    diff = differing_keys(ref, ker)
    print(f"  kernel == reference: {not diff} {diff}", flush=True)
    if diff:
        errors.append(f"a: kernel summary differs from reference on {diff}")

    cut = cfg._replace(n_slots=wavefront_slots)
    if wavefront_slots != cfg.n_slots:
        print(f"  wavefront horizon cut to {wavefront_slots} of "
              f"{cfg.n_slots} slots (nodes and queue width unchanged); "
              f"its reference run is cut the same way", flush=True)
        ref, _ = run_sim("reference_cut", ts, cut)
    wav, n_wav = run_sim("wavefront", ts,
                         cut._replace(admission_mode="wavefront"))
    if n_wav < 1:
        errors.append("a: wavefront program holds no tpu_custom_call")
    diff = differing_keys(ref, wav)
    print(f"  wavefront == reference: {not diff} {diff}", flush=True)
    if diff:
        errors.append(f"a: wavefront summary differs from reference on "
                      f"{diff}")


def phase_reclamation(ts, cfg, errors):
    print(f"phase b: reclamation nodes={cfg.n_nodes} slots={cfg.n_slots} "
          f"pool={cfg.reclaim_pool} estimator=ewma", flush=True)
    summary, n_ker = run_sim(
        "reclaim", ts, cfg._replace(estimator="ewma", reclamation=True))
    if n_ker < 1:
        errors.append("b: reclaim program holds no tpu_custom_call")
    bad = [k for k, v in summary.items() if not math.isfinite(v)]
    if bad:
        errors.append(f"b: non-finite summary keys {bad}")


def run_engine(mode, horizon, steps, rate):
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import EngineConfig, ServeEngine
    from repro.serving.stream import RequestStream, StreamConfig

    eng = ServeEngine(EngineConfig(n_replicas=8, policy="flex",
                                   admission_mode=mode))
    # Compile the admission entry at every pad width the engine uses
    # (powers of two from 8 up to admit_batch) before the stream starts.
    node = eng.node_state()
    pen = jnp.asarray(1.0, jnp.float32)
    widths, w = [], 8
    while w < eng.cfg.admit_batch:
        widths.append(w)
        w *= 2
    widths.append(eng.cfg.admit_batch)
    t0 = time.perf_counter()
    for w in widths:
        args = (node, jnp.zeros((w, 2), jnp.float32),
                jnp.zeros(w, jnp.int32), jnp.zeros(w, jnp.int32),
                jnp.zeros(w, bool), pen)
        jax.block_until_ready(eng._admit_fn(*args))
    compile_s = time.perf_counter() - t0
    n_kernel = kernel_calls(jax.jit(eng._admit_fn).lower(*args).compile())

    stream = RequestStream(StreamConfig(pattern="burst", mean_rate=rate,
                                        seed=7), horizon=horizon)
    t0 = time.perf_counter()
    stats = stream.drive(eng, steps=steps)
    run_s = time.perf_counter() - t0
    counts = {"admitted": stats.admitted, "finished": stats.finished,
              "decisions": stats.decisions,
              "evicted": stats.evicted_events}
    print(f"  {mode}: widths={widths} compile_s={compile_s:.3f} "
          f"run_s={run_s:.3f} tpu_custom_call={n_kernel} "
          f"submitted={stream.submitted} "
          + " ".join(f"{k}={v}" for k, v in counts.items()), flush=True)
    return counts, n_kernel


def phase_serving(errors, horizon=80, steps=100, rate=24.0):
    print(f"phase c: serving replicas=8 policy=flex pattern=burst "
          f"rate={rate} horizon={horizon} steps={steps}", flush=True)
    wav, n_wav = run_engine("wavefront", horizon, steps, rate)
    seq, n_seq = run_engine("sequential", horizon, steps, rate)
    if n_wav < 1:
        errors.append("c: wavefront admitter holds no tpu_custom_call")
    if n_seq != 0:
        errors.append(f"c: sequential admitter holds {n_seq} kernels")
    print(f"  wavefront == sequential: {wav == seq}", flush=True)
    if wav != seq:
        errors.append(f"c: serving counts differ: wavefront {wav}, "
                      f"sequential {seq}")


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's default device is {dev.platform!r} "
             f"({dev.device_kind}); this smoke runs only on a TPU")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repo's sources are not next to this script ({e})")
    cache_dir, n_entries = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    print(f"compile cache: {cache_dir} entries_at_start={n_entries}",
          flush=True)
    # Runs do not record per-node series; summarize says so on each call.
    warnings.filterwarnings("ignore", message="summarize: skipping machine")

    from benchmarks.common import sim_setup

    t0 = time.perf_counter()
    cfg, ts = sim_setup(full=True)
    print(f"trace: generate_calibrated(0, {cfg.n_nodes}, {cfg.n_slots}, "
          f"offered_load=1.6) tasks={ts.num_tasks} "
          f"gen_s={time.perf_counter() - t0:.3f}", flush=True)
    errors: list = []
    phase_simulator(ts, cfg, WAVEFRONT_SLOTS, errors)
    phase_reclamation(ts, cfg, errors)
    phase_serving(errors)
    if errors:
        fail("FAILED: " + "; ".join(errors))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
