"""Plain reference of one cluster study, in jax.numpy.

Written from the paper's slot semantics (Flex, arXiv:2006.01354, sections
3-5), importing nothing of the program.  Per 5-minute slot:

1. the running set: tasks admitted before this slot and not yet past
   their duration; per-node task and same-source counts from it;
2. each task's demand: AR(1) noise around its mean usage (white noise
   from ``normal(fold_in(key, slot))``), clipped to [0, peak], at most
   one node;
3. weighted fair sharing of each node (capacity 1): first the
   request-guaranteed part min(demand, request), then the excess demand,
   each a water-filling of 4 progressive rounds weighted by the task's
   largest request; a task meets QoS when it gets min(demand, request)
   on every resource (1e-6 slack); cluster QoS is the share of running
   tasks that do;
4. the penalty P by Alg. 3 (AIMD on cluster QoS);
5. the load estimate L-hat is the node's realized usage;
6. the queue (retries, then this slot's arrivals, in task order) is
   admitted one task at a time by FlexF: feasible where
   ``P * L-hat + reserved + r <= 1`` on every resource, score
   ``-(w_load * max(P * L-hat + reserved) + w_src * same-source share)``,
   first best node; failures retry next slot up to 16 times, in a
   queue of fixed width whose overflow is dropped.

``dtype`` is the precision of the load, allocation and score
arithmetic: float32 is the configuration's, bfloat16 the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

N_SRC = 64
MAX_RETRIES = 16
WFS_ROUNDS = 4
EPS_WFS = 1e-9
EPS_QOS = 1e-6
ALPHA, BETA, P_INIT, P_MIN, P_MAX = 0.99, 1.0, 1.5, 1.0, 16.0
W_LOAD, W_SRC = 1.0, 0.25
NEG = -1e30


def arrival_table(arrival: np.ndarray, n_slots: int, width: int):
    """(n_slots, width) ids of the tasks arriving in each slot, in task
    order, padded with -1; arrivals past ``width`` in a slot are lost."""
    order = np.argsort(arrival, kind="stable")
    counts = np.bincount(arrival, minlength=n_slots)
    starts = np.concatenate([[0], np.cumsum(counts)])
    table = np.full((n_slots, width), -1, np.int32)
    for s in range(n_slots):
        ids = order[starts[s]:starts[s] + min(counts[s], width)]
        table[s, :len(ids)] = ids
    return table


def _fill(capacity, weight, cap, node, live, n_nodes, dt):
    """Weighted water-filling of per-node ``capacity`` over tasks' caps."""
    seg = lambda x: jax.ops.segment_sum(x, node, num_segments=n_nodes)
    cap = jnp.maximum(cap, 0.0).astype(dt) * live[:, None]
    w = (jnp.maximum(weight, EPS_WFS) * live).astype(dt)
    fits = (seg(cap) <= capacity + EPS_WFS)[node]
    got = jnp.where(fits, cap, 0.0).astype(dt)
    left = (capacity - seg(got)).astype(dt)
    for _ in range(WFS_ROUNDS):
        need = (cap - got).astype(dt)
        hungry = (need > EPS_WFS) & ~fits
        w_t = jnp.where(hungry, w[:, None], 0.0).astype(dt)
        w_n = seg(w_t)
        share = (left[node] * w_t / jnp.maximum(w_n[node], EPS_WFS)).astype(dt)
        give = (jnp.clip(share, 0.0, need) * hungry).astype(dt)
        got = (got + give).astype(dt)
        left = (left - seg(give)).astype(dt)
    return got


@functools.partial(jax.jit, static_argnames=("n_nodes", "retry_width",
                                             "dtype"))
def simulate(tasks: dict, table, key, *, n_nodes: int, retry_width: int,
             qos_target: float, dtype=jnp.float32):
    dt = dtype
    T = tasks["arrival"].shape[0]
    request = tasks["request"].astype(dt)
    mean, std = tasks["mean_usage"], tasks["std_usage"]
    peak, rho = tasks["peak_usage"], tasks["ar_rho"]
    dur, src = tasks["duration"], tasks["src"]
    weight = jnp.max(tasks["request"], axis=-1)
    f32 = jnp.float32

    def slot(c, xs):
        s, arrivals = xs
        running = ((c["place"] >= 0) & (c["admit"] < s)
                   & (s <= c["admit"] + dur))
        live = running.astype(dt)
        at = jnp.where(running, c["place"], 0)
        n_tasks = jnp.zeros(n_nodes, jnp.int32).at[at].add(
            running.astype(jnp.int32))
        same_src = jnp.zeros((n_nodes, N_SRC), jnp.int32).at[at, src].add(
            running.astype(jnp.int32))

        white = jax.random.normal(jax.random.fold_in(key, s), (T,), f32)
        noise = rho * c["noise"] + jnp.sqrt(
            jnp.maximum(1.0 - rho * rho, 0.0)) * white
        demand = jnp.minimum(jnp.clip(mean + std * noise[:, None], 0.0,
                                      peak), 1.0).astype(dt)

        # inactive tasks weigh nothing; they are parked on the last node
        park = jnp.clip(jnp.where(running, c["place"], n_nodes - 1), 0,
                        n_nodes - 1)
        full = jnp.ones((n_nodes, 2), dt)
        guaranteed = _fill(full, weight, jnp.minimum(demand, request), park,
                           live, n_nodes, dt)
        rest = (full - jax.ops.segment_sum(guaranteed, park,
                                           num_segments=n_nodes)).astype(dt)
        extra = _fill(rest, weight, (demand - guaranteed).astype(dt), park,
                      live, n_nodes, dt)
        alloc = ((guaranteed + extra) * live[:, None]).astype(dt)
        usage = jax.ops.segment_sum(alloc, park, num_segments=n_nodes)
        met = jnp.all(alloc + EPS_QOS >= jnp.minimum(demand, request),
                      axis=-1)
        n_run = jnp.sum(running.astype(jnp.int32))
        n_met = jnp.sum((met & running).astype(jnp.int32))
        q = jnp.where(n_run > 0, n_met.astype(f32)
                      / jnp.maximum(n_run, 1).astype(f32), 1.0).astype(f32)

        p = c["p"]
        target = f32(qos_target)
        p_new = jnp.where(q >= target, jnp.maximum(p * f32(ALPHA),
                                                   f32(P_MIN)),
                          jnp.where((q < target) & (q < c["prev_q"]),
                                    p + f32(BETA) * (p - 1.0), p))
        p_new = jnp.clip(p_new, f32(P_MIN), f32(P_MAX))

        queue = jnp.concatenate([c["retry"], arrivals])
        valid = queue >= 0
        qid = jnp.maximum(queue, 0)
        pen = p_new.astype(dt)
        est = usage.astype(dt)

        def one(state, x):
            reserved, n_t, s_c = state
            r, sr, ok = x
            load = (pen * est).astype(dt) + reserved
            feasible = jnp.all((load + r).astype(dt) <= 1.0, axis=-1)
            frac = (s_c[:, sr].astype(f32)
                    / jnp.maximum(n_t, 1).astype(f32)).astype(dt)
            score = -(dt(W_LOAD) * jnp.max(load, axis=-1)
                      + dt(W_SRC) * frac).astype(dt)
            score = jnp.where(feasible, score, NEG)
            take = ok & jnp.any(feasible)
            i = jnp.argmax(score)
            reserved = reserved.at[i].add(jnp.where(take, r, 0.0).astype(dt))
            n_t = n_t.at[i].add(take.astype(jnp.int32))
            s_c = s_c.at[i, sr].add(take.astype(jnp.int32))
            return (reserved, n_t, s_c), jnp.where(take, i, -1)

        _, picked = jax.lax.scan(
            one, (jnp.zeros((n_nodes, 2), dt), n_tasks, same_src),
            (request[qid], src[qid], valid))
        placed = valid & (picked >= 0)
        place = c["place"].at[qid].max(jnp.where(placed, picked, -1))
        admit = c["admit"].at[qid].max(jnp.where(placed, s, -1))
        failed = valid & (picked < 0)
        attempts = c["attempts"].at[qid].add(failed.astype(jnp.int32))
        again = failed & (attempts[qid] <= MAX_RETRIES)
        order = jnp.argsort(~again, stable=True)
        keep = jnp.arange(retry_width) < jnp.sum(again.astype(jnp.int32))
        retry = jnp.where(keep, queue[order][:retry_width], -1)
        c = dict(place=place, admit=admit, attempts=attempts, noise=noise,
                 retry=retry, p=p_new, prev_q=q)
        return c, (q, p_new)

    init = dict(place=jnp.full((T,), -1, jnp.int32),
                admit=jnp.full((T,), -1, jnp.int32),
                attempts=jnp.zeros((T,), jnp.int32),
                noise=jnp.zeros((T,), f32),
                retry=jnp.full((retry_width,), -1, jnp.int32),
                p=f32(P_INIT), prev_q=f32(1.0))
    n_slots = table.shape[0]
    final, (qos, penalty) = jax.lax.scan(
        slot, init, (jnp.arange(n_slots, dtype=jnp.int32), table))
    return final["place"], final["admit"], qos, penalty


def run_reference(config: dict, tasks: dict, demand_seed: int,
                  dtype=jnp.float32) -> dict:
    """One study of ``config`` on the host task arrays; host results."""
    n_slots = int(config["n_slots"])
    table = arrival_table(tasks["arrival"], n_slots,
                          int(config["arrivals_per_slot"]))
    dev = {k: jnp.asarray(v) for k, v in tasks.items()}
    out = simulate(dev, jnp.asarray(table), jax.random.PRNGKey(demand_seed),
                   n_nodes=int(config["n_nodes"]),
                   retry_width=int(config["retry_capacity"]),
                   qos_target=float(config["qos_target"]), dtype=dtype)
    place, admit, qos, penalty = (np.asarray(x) for x in out)
    return {"placement": place, "admit_slot": admit, "qos": qos,
            "penalty": penalty}
