"""Per-node resource allocation via weighted fair share (paper §3).

Given demands d_j, requests r_j and the placement, the allocator realizes
the paper's three cases per node (per resource dimension):

  1. sum(d) <= C                      -> a_j = d_j
  2. sum(d) >  C, sum(r) <= C         -> guarantee min(d_j, r_j), then WFS the
                                         remaining capacity over excess demand
  3. sum(d) >  C, sum(r) >  C         -> WFS twice: first over requests,
                                         then over remaining demand

All three reduce to two rounds of a *water-filling* primitive:
  round 1: caps = min(d, r)   (the request-guaranteed part)
  round 2: caps = d - a1      (excess demand shares what is left)
with WFS weights proportional to the request r_j (weighted fair share).

The water-filler is exact whenever the total cap on a node fits the node's
remaining capacity (cases 1-2) and converges geometrically in case 3; we run
a fixed number of progressive-filling iterations (``iters``) so the whole
allocator is one fused XLA program over every node at once.

Only running tasks carry weight: every other row is masked to zero and adds
exact ``+0.0`` terms to its parked node's sums.  ``_wfs_compacted`` therefore
runs the same arithmetic on the running rows alone, gathered in ascending
task order into the narrowest rung of a static ladder of row counts (T/64
... T/2, from the shape) that holds them, and scatters the result back; each
node's sums get the same live terms in the same order, so every rung gives
the full T-row answer.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

_EPS = 1e-9
_RUNG_QUANTUM = 1024   # rung widths are multiples of this many rows
_RUNG_DEPTH = 6        # the narrowest compact rung holds T / 2**6 rows
_COUNT_BLOCK = 1024    # block of the two-level running count


def _segment_sum(data: jnp.ndarray, seg: jnp.ndarray, num: int) -> jnp.ndarray:
    return jax.ops.segment_sum(data, seg, num_segments=num)


def waterfill(
    node_capacity: jnp.ndarray,  # (N, R) remaining capacity per node
    weights: jnp.ndarray,        # (T,)  WFS weights (>= 0)
    caps: jnp.ndarray,           # (T, R) per-task allocation ceiling (>= 0)
    seg: jnp.ndarray,            # (T,)  node id per task (already masked/clipped)
    mask: jnp.ndarray,           # (T,)  1.0 for live tasks, 0.0 otherwise
    num_nodes: int,
    iters: int = 4,
) -> jnp.ndarray:
    """Weighted progressive filling.  Returns per-task allocation (T, R)."""
    caps = jnp.maximum(caps, 0.0) * mask[:, None]
    w = jnp.maximum(weights, _EPS) * mask

    # Fast path: if everything fits, hand out the caps exactly.
    total_cap = _segment_sum(caps, seg, num_nodes)               # (N, R)
    fits = (total_cap <= node_capacity + _EPS)                   # (N, R)
    fits_t = fits[seg]                                           # (T, R)

    alloc = jnp.where(fits_t, caps, 0.0)
    remaining_node = node_capacity - _segment_sum(alloc, seg, num_nodes)

    def body(_, carry):
        alloc, remaining_node = carry
        need = caps - alloc                                       # (T, R)
        unsat = (need > _EPS) & (~fits_t)
        w_eff = jnp.where(unsat, w[:, None], 0.0)                 # (T, R)
        w_node = _segment_sum(w_eff, seg, num_nodes)              # (N, R)
        share = (remaining_node[seg] * w_eff
                 / jnp.maximum(w_node[seg], _EPS))
        give = jnp.clip(share, 0.0, need) * unsat
        alloc = alloc + give
        remaining_node = remaining_node - _segment_sum(give, seg, num_nodes)
        return alloc, remaining_node

    alloc, _ = jax.lax.fori_loop(0, iters, body, (alloc, remaining_node))
    return alloc


def wfs_allocate(
    demand: jnp.ndarray,      # (T, R)
    request: jnp.ndarray,     # (T, R)
    placement: jnp.ndarray,   # (T,) node idx, -1 when unplaced
    active: jnp.ndarray,      # (T,) bool
    num_nodes: int,
    capacity=1.0,             # scalar, (N,) or (N, R) — per-node capacity
    iters: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Allocate actual resources per task (paper §3 'Resource allocation').

    ``capacity`` broadcasts from a scalar (every node, every resource) up
    to a full (N, R) table — per-node values express transient capacity
    loss (fault-injection flaps, ``repro.faults``).

    Returns:
      alloc: (T, R) realized allocation a_j (0 for inactive tasks).
      node_usage: (N, R) summed usage L_i per node.
    """
    mask = active.astype(jnp.float32)
    seg = jnp.where(active, placement, num_nodes - 1)  # park inactive anywhere
    seg = jnp.clip(seg, 0, num_nodes - 1)
    r = demand.shape[-1]
    cap = jnp.asarray(capacity, jnp.float32)
    if cap.ndim == 0:
        cap_node = jnp.full((num_nodes, r), cap, jnp.float32)
    elif cap.ndim == 1:
        cap_node = jnp.broadcast_to(cap[:, None], (num_nodes, r))
    else:
        cap_node = jnp.broadcast_to(cap, (num_nodes, r))

    weights = jnp.maximum(jnp.max(request, axis=-1), _EPS)  # WFS weight ~ request

    # Round 1: the request-guaranteed portion min(d, r).
    a1 = waterfill(cap_node, weights, jnp.minimum(demand, request), seg, mask,
                   num_nodes, iters)
    # Round 2: excess demand d - a1 shares whatever capacity is left.
    rem = cap_node - _segment_sum(a1, seg, num_nodes)
    a2 = waterfill(rem, weights, demand - a1, seg, mask, num_nodes, iters)

    alloc = (a1 + a2) * mask[:, None]
    node_usage = _segment_sum(alloc, seg, num_nodes)
    return alloc, node_usage


def _ladder(num_tasks: int) -> Tuple[int, ...]:
    """Compact rung widths, ascending: T/64 ... T/2 rounded up to a
    multiple of ``_RUNG_QUANTUM``, each under T.  The full T-row path is
    the rung above them."""
    q = _RUNG_QUANTUM
    widths = {-(-num_tasks // (q << d)) * q for d in range(1, _RUNG_DEPTH + 1)}
    return tuple(sorted(w for w in widths if w < num_tasks))


def _running_count(active: jnp.ndarray) -> jnp.ndarray:
    """(T,) i32 inclusive running count of active tasks.

    Summed in two levels of at most ``_COUNT_BLOCK``: the TPU compiler
    takes tens of seconds over one cumsum of a few hundred thousand.
    """
    num_tasks = active.shape[0]
    n_blocks = -(-num_tasks // _COUNT_BLOCK)
    x = jnp.pad(active.astype(jnp.int32), (0, n_blocks * _COUNT_BLOCK
                                           - num_tasks))
    within = jnp.cumsum(x.reshape(n_blocks, _COUNT_BLOCK), axis=1)
    before = jnp.cumsum(within[:, -1]) - within[:, -1]
    return (within + before[:, None]).reshape(-1)[:num_tasks]


def _active_ids(active: jnp.ndarray, width: int) -> jnp.ndarray:
    """(width,) ids of the active tasks in ascending order, padded with T.

    Entry k is the first task whose running count of active tasks reaches
    k + 1: a running count and a binary search, gathers only.
    """
    count = _running_count(active)
    ranks = jnp.arange(1, width + 1, dtype=jnp.int32)
    return jnp.searchsorted(count, ranks, side="left").astype(jnp.int32)


def _compact_rung(width: int, num_nodes: int, iters: int):
    """``wfs_allocate`` on the first ``width`` active rows, scattered back
    to T rows; exact whenever at most ``width`` tasks are active."""

    def run(demand, request, placement, active, capacity):
        num_tasks = active.shape[0]
        ids = _active_ids(active, width)
        take = jnp.minimum(ids, num_tasks - 1)
        rows_alloc, node_usage = wfs_allocate(
            demand[take], request[take], placement[take], ids < num_tasks,
            num_nodes, capacity, iters)
        alloc = jnp.zeros_like(demand).at[ids].set(rows_alloc, mode="drop")
        return alloc, node_usage, jnp.int32(width)

    return run


def _full_rung(num_nodes: int, iters: int):
    """``wfs_allocate`` over all T rows: the rung above the ladder."""

    def run(demand, request, placement, active, capacity):
        alloc, node_usage = wfs_allocate(demand, request, placement, active,
                                         num_nodes, capacity, iters)
        return alloc, node_usage, jnp.int32(active.shape[0])

    return run


def _switch(branches):
    """``lax.switch`` over ``branches`` whose batching keeps one branch.

    A batched ``switch`` index would become a ``select_n`` that runs every
    branch; batched, this takes the widest rung any member needs (exact
    for all of them, rungs being ordered by width) and switches once on
    that unbatched index over the batched branches.
    """

    @custom_vmap
    def call(rung, *operands):
        return jax.lax.switch(rung, branches, *operands)

    @call.def_vmap
    def rule(axis_size, in_batched, rung, *operands):
        del axis_size
        rung_batched, *batched = in_batched
        if rung_batched:
            rung = jnp.max(rung)
        axes = tuple(0 if b else None for b in batched)
        out = _switch([jax.vmap(b, in_axes=axes) for b in branches])(
            rung, *operands)
        return out, (True,) * len(out)

    return call


def _wfs_compacted(
    demand: jnp.ndarray,      # (T, R)
    request: jnp.ndarray,     # (T, R)
    placement: jnp.ndarray,   # (T,) node idx, -1 when unplaced
    active: jnp.ndarray,      # (T,) bool
    num_nodes: int,
    capacity=1.0,             # scalar, (N,) or (N, R) — per-node capacity
    iters: int = 4,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``wfs_allocate`` on the narrowest ladder rung that holds the active
    set: the same (alloc, node_usage), bit for bit, and the rows it ran
    on (T on the full path)."""
    widths = _ladder(active.shape[0])
    branches = ([_compact_rung(w, num_nodes, iters) for w in widths]
                + [_full_rung(num_nodes, iters)])
    n_active = jnp.sum(active, dtype=jnp.int32)
    rung = jnp.sum(n_active > jnp.asarray(widths, jnp.int32), dtype=jnp.int32)
    return _switch(branches)(rung, demand, request, placement, active,
                             jnp.asarray(capacity, jnp.float32))
