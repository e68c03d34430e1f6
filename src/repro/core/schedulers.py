"""Phase-1 single-resource schedulers (paper §4.1, Algorithms 1-2).

``fifo_scheduler`` / ``lrf_scheduler`` (Theorems 4.1-4.2) are reference
semantics with precise load estimation.  Multi-resource admission
(Alg. 3) lives in ``repro.api.admission`` and the policies in the
``repro.api`` registry.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Phase-1 algorithms with precise load estimation (paper §4.1).
# Single-resource, standalone — used by the approximation-bound property
# tests (Theorems 4.1 and 4.2) and as reference semantics.
# ---------------------------------------------------------------------------

def fifo_scheduler(loads: jnp.ndarray, requests: jnp.ndarray,
                   capacity: float = jnp.inf) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Algorithm 1: visit tasks FIFO, put each on the least-loaded node.

    Args:
      loads: (N,) initial node loads.
      requests: (J,) task sizes (request == demand in the precise phase).
      capacity: per-node capacity C (inf for the theorem setting).

    Returns (final_loads (N,), assignment (J,) node idx or -1).
    """

    def step(l, r):
        i = jnp.argmin(l)
        fits = l[i] + r <= capacity
        l = jnp.where(fits, l.at[i].add(r), l)
        return l, jnp.where(fits, i, -1).astype(jnp.int32)

    return jax.lax.scan(step, loads, requests)


def lrf_scheduler(loads: jnp.ndarray, requests: jnp.ndarray,
                  capacity: float = jnp.inf) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Algorithm 2: sort by request descending, then FIFO placement.

    Returns (final_loads, assignment in the ORIGINAL task order).
    """
    order = jnp.argsort(-requests)
    loads, assign_sorted = fifo_scheduler(loads, requests[order], capacity)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return loads, assign_sorted[inv]
