"""Scheduler decision latency (paper §4.3: O(N/p), sub-second for thousands
of nodes).  Four sections:

  * ``schedule_one_*``: the jitted sequential ScheduleOne loop per decision,
    reference path vs the fused Pallas kernel path (``use_kernel=True``).
  * ``flex_pick_*``: the single fused filter+score+argmax primitive, kernel
    vs reference einsum, for N in {512, 2048, 8192} — each pair is parity-
    asserted (same node index) before it is timed.
  * ``admit_wavefront_*``: wavefront batched admission vs the sequential
    per-task kernel path for N in {512, 2048, 8192} x Q in {64, 512} —
    parity-asserted placement-for-placement, with the conflict-round count
    and node-sweep reduction (Q sweeps -> epoch sweeps) in the derived
    column.  The wavefront rows (``admit_wavefront_k8_*``) run the
    admission core's top-K candidate cache with its default tuning (K=8,
    score-bucket dedup), and — at N=2048, Q=512 — a duplicate-heavy queue
    (8 job shapes x 8 sources, ``admit_wavefront_k8_dup_*``) exercises
    the dedup fast path.
    ``python benchmarks/run.py --json bench_scheduler_throughput``
    merge-appends these rows into BENCH_scheduler_throughput.json so the
    perf trajectory across PRs is trackable.
  * On non-TPU backends the kernel rows run through the Pallas interpreter
    (``mode=interpret`` in the derived column) — correct but not
    representative of TPU latency; the reference rows are the honest CPU
    numbers.  Wavefront's win is sweep amortization (one HBM pass of the
    node table scores the whole queue), so the interpret/CPU wall-clock
    understates the TPU gain; the ``node_sweeps_ratio`` column is the
    backend-independent measure.

The queue goes through the open-policy admission core (``admit_queue``
with a registry policy object), so new policies inherit this bench."""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import Row
from repro.api import admission, get_policy
from repro.core import FlexParams, NodeState
from repro.kernels.flex_score.ops import flex_pick_node
from repro.kernels.flex_score.ref import pick_node_ref

KERNEL_SIZES = [512, 2048, 8192]
WAVEFRONT_GRID = [(n, q) for n in KERNEL_SIZES for q in (64, 512)]


def _time(fn, *args, iters=5, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def run(full: bool):
    rows = []
    params = FlexParams.default()
    policy = get_policy("flex-f")
    on_tpu = jax.default_backend() == "tpu"
    interp = 0.0 if on_tpu else 1.0

    # --- sequential ScheduleOne loop, reference vs kernel path ------------
    sizes = [1000, 4000, 16000] if not full else [4000, 16000, 64000]
    Q = 256
    key = jax.random.PRNGKey(0)
    for n in sizes:
        node = NodeState.zeros(n)
        node = node._replace(est_usage=jax.random.uniform(key, (n, 2)) * 0.5)
        reqs = jax.random.uniform(key, (Q, 2)) * 0.1
        srcs = prios = jnp.zeros((Q,), jnp.int32)
        valid = jnp.ones((Q,), bool)
        pen = jnp.asarray(1.2)

        f_ref = jax.jit(lambda nd: admission.admit_queue(
            policy, nd, reqs, srcs, prios, valid, pen, params))
        us = _time(lambda nd: f_ref(nd)[1], node, iters=5) / Q
        rows.append(Row(f"schedule_one_n{n}", us,
                        {"nodes": n, "decisions_per_s": 1e6 / us}))

        # kernel path only timed where it actually runs as a kernel (TPU)
        # or as its interpreter build (anywhere) — the dispatch in
        # flex_pick_node would silently fall back to the reference on
        # plain CPU and time the same program twice.
        f_ker = jax.jit(lambda nd: admission.admit_queue(
            policy, nd, reqs, srcs, prios, valid, pen, params,
            use_kernel=True, interpret=not on_tpu))
        us_k = _time(lambda nd: f_ker(nd)[1], node, iters=5) / Q
        rows.append(Row(f"schedule_one_kernel_n{n}", us_k,
                        {"nodes": n, "decisions_per_s": 1e6 / us_k,
                         "interpret": interp}))

    # --- fused filter+score primitive: kernel vs reference ---------------
    for n in KERNEL_SIZES:
        ks = jax.random.split(jax.random.PRNGKey(n), 3)
        est = jax.random.uniform(ks[0], (n, 2)) * 0.6
        res = jax.random.uniform(ks[1], (n, 2)) * 0.05
        src = jax.random.uniform(ks[2], (n,))
        r = jnp.asarray([0.08, 0.1])
        pen = jnp.asarray(1.2)

        g_ref = jax.jit(lambda e, rs, sf: pick_node_ref(
            e, rs, sf, r, pen, 1.0, 0.25))
        g_ker = jax.jit(lambda e, rs, sf: flex_pick_node(
            e, rs, sf, r, pen, interpret=not on_tpu))

        # parity gate: the two paths must agree before either is timed
        i_ref = int(g_ref(est, res, src)[0])
        i_ker = int(g_ker(est, res, src)[0])
        assert i_ref == i_ker, (
            f"kernel/reference disagree at N={n}: {i_ker} vs {i_ref}")

        us_ref = _time(lambda: g_ref(est, res, src)[0], iters=50)
        rows.append(Row(f"flex_pick_ref_n{n}", us_ref, {"nodes": n}))
        us_ker = _time(lambda: g_ker(est, res, src)[0], iters=50)
        rows.append(Row(f"flex_pick_kernel_n{n}", us_ker,
                        {"nodes": n, "interpret": interp,
                         "speedup_vs_ref": us_ref / us_ker}))

    # --- wavefront batched admission vs the per-task kernel scan ----------
    def _wavefront_rows(tag, n, q, node, reqs, srcs, prios):
        valid = jnp.ones((q,), bool)
        pen = jnp.asarray(1.2)

        f_seq = jax.jit(lambda nd: admission.admit_queue(
            policy, nd, reqs, srcs, prios, valid, pen, params,
            use_kernel=True, interpret=not on_tpu))
        f_topk = jax.jit(lambda nd: admission.admit_queue_wavefront(
            policy, nd, reqs, srcs, prios, valid, pen, params,
            interpret=not on_tpu, with_rounds=True))

        # parity gate: the wavefront must reproduce the sequential
        # decisions before anything is timed
        pl_seq = f_seq(node)[1]
        _, pl_topk, t_rounds, t_sweeps = f_topk(node)
        assert (pl_seq == pl_topk).all(), (
            f"topk-wavefront/sequential disagree at N={n} Q={q}")

        out = []
        us_seq = _time(lambda nd: f_seq(nd)[1], node, iters=3) / q
        out.append(Row(f"admit_seq_kernel_{tag}", us_seq,
                       {"nodes": n, "queue": q,
                        "decisions_per_s": 1e6 / us_seq,
                        "interpret": interp}))
        us_topk = _time(lambda nd: f_topk(nd)[1], node, iters=3) / q
        out.append(Row(f"admit_wavefront_k8_{tag}", us_topk,
                       {"nodes": n, "queue": q,
                        "decisions_per_s": 1e6 / us_topk,
                        "speedup_vs_seq": us_seq / us_topk,
                        "rounds": int(t_rounds),
                        "sweeps": int(t_sweeps),
                        "node_sweeps_ratio": q / max(int(t_sweeps), 1),
                        "interpret": interp}))
        return out

    for n, q in WAVEFRONT_GRID:
        ks = jax.random.split(jax.random.PRNGKey(n + q), 6)
        node = NodeState.zeros(n)._replace(
            est_usage=jax.random.uniform(ks[0], (n, 2)) * 0.6,
            reserved=jax.random.uniform(ks[1], (n, 2)) * 0.05,
            n_tasks=jax.random.randint(ks[2], (n,), 2, 8),
            src_count=jax.random.randint(ks[3], (n, 64), 0, 4))
        reqs = jax.random.uniform(ks[4], (q, 2)) * 0.15
        # a diverse queue: sources round-robin over every bucket (the
        # low-conflict regime wavefront is built for; grouped sources
        # degrade toward one commit per round — see docs/kernels.md)
        srcs = jnp.arange(q, dtype=jnp.int32) % 64
        prios = jax.random.randint(ks[5], (q,), 0, 2)
        rows.extend(_wavefront_rows(f"n{n}_q{q}", n, q, node, reqs, srcs,
                                    prios))

    # duplicate-heavy queue: 8 job shapes x 8 sources -> 64 distinct task
    # rows, the score-bucket-dedup regime (Q_eff = 64 << Q = 512)
    n, q = 2048, 512
    ks = jax.random.split(jax.random.PRNGKey(99), 5)
    node = NodeState.zeros(n)._replace(
        est_usage=jax.random.uniform(ks[0], (n, 2)) * 0.6,
        reserved=jax.random.uniform(ks[1], (n, 2)) * 0.05,
        n_tasks=jax.random.randint(ks[2], (n,), 2, 8),
        src_count=jax.random.randint(ks[3], (n, 64), 0, 4))
    shapes = jax.random.uniform(ks[4], (8, 2)) * 0.15
    reqs = shapes[jnp.arange(q) % 8]
    srcs = (jnp.arange(q, dtype=jnp.int32) // 8) % 8
    prios = jnp.zeros((q,), jnp.int32)
    rows.extend(_wavefront_rows(f"dup_n{n}_q{q}", n, q, node, reqs, srcs,
                                prios))
    return rows
