"""int8 gradient all-reduce with error feedback (1-bit-Adam-family trick).

Ring all-reduce of f32 grads moves ~8 bytes/element/device; the compressed
exchange moves ~2 (int8 all-to-all of chunk shards + int8 all-gather of the
reduced chunks) — a 4x cut in DP-sync collective volume.  Quantization error
is carried in an ERROR-FEEDBACK buffer added to the next step's gradient, so
SGD/Adam convergence is preserved (Seide et al., Tang et al.).

Implemented with ``shard_map`` over the data axis so the int8 wire format is
explicit in the HLO (visible to the roofline collective parser).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_allreduce(grad: jnp.ndarray, mesh: Mesh,
                         axis: str = "data") -> jnp.ndarray:
    """Mean-all-reduce `grad` (replicated per device) over `axis` in int8.

    grad: (n, ) f32, n divisible by mesh.shape[axis]; returns the mean.
    """
    n_dev = mesh.shape[axis]

    def body(g):  # g: per-device local copy (n,)
        g = g.reshape(n_dev, -1)                       # chunk per peer
        q, scale = _quantize(g)
        # phase 1: all-to-all — each device collects everyone's copy of ITS
        # chunk (int8 on the wire)
        qs = jax.lax.all_to_all(q[None], axis, split_axis=1,
                                concat_axis=0, tiled=False)[:, 0]
        scales = jax.lax.all_gather(scale, axis)       # (n_dev,)
        chunk = jnp.sum(qs.astype(jnp.float32)
                        * scales[:, None], axis=0) / n_dev
        # phase 2: re-quantize the reduced chunk, all-gather (int8 wire)
        q2, s2 = _quantize(chunk)
        qall = jax.lax.all_gather(q2, axis)            # (n_dev, n/n_dev) i8
        sall = jax.lax.all_gather(s2, axis)
        return (qall.astype(jnp.float32) * sall[:, None]).reshape(-1)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=P(),      # replicated input
                       out_specs=P(),     # replicated output
                       check_vma=False)
    return fn(grad)


def ef_compress_step(grad: jnp.ndarray, error: jnp.ndarray, mesh: Mesh,
                     axis: str = "data") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One error-feedback compressed sync: returns (synced_grad, new_error)."""
    corrected = grad + error
    synced = compressed_allreduce(corrected, mesh, axis)
    # local quantization residual becomes next step's correction
    q, s = _quantize(corrected)
    new_error = corrected - _dequantize(q, s)
    return synced, new_error
