"""The flex_score Pallas kernels compile for a TPU v5e chip.

No accelerator is needed: the TPU compiler ships with JAX and compiles
for a chip that is described, not attached.  These compiles catch what
interpret mode cannot — block shapes off the (8, 128) tiling, scalar
stores to VMEM, layouts Mosaic refuses — at the shapes the chip smoke
runs: the paper-scale cluster (N = 4000 nodes, a queue of Q = 5120
tasks) and the serving engine (N = 8 replicas, a 256-wide queue).

The topology is described inside fixtures, never at import: only one
process at a time may load the TPU library, and under a multi-worker
pytest run every worker imports this file.  Keep these tests in this one
file, so that the one worker that runs them is the one that loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flex_score.flex_score import (flex_score_batch_tiles,
                                                 flex_score_batch_topk_tiles,
                                                 flex_score_tiles)

R = 2   # CPU, MEM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs off the disk
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _kernel_args(kernel, N, Q, sharding):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=sharding)
    if kernel == "tiles":    # one task: the per-decision scan body
        return flex_score_tiles, (f32(N, R), f32(N, R), f32(N, 1),
                                  f32(1, R + 4))
    fn = {"batch": flex_score_batch_tiles,
          "topk": flex_score_batch_topk_tiles}[kernel]
    return fn, (f32(N, R), f32(N, R), f32(Q, N), f32(Q, R + 4))


@pytest.mark.parametrize("N,Q", [(4000, 5120), (8, 256)],
                         ids=["paper", "engine"])
@pytest.mark.parametrize("kernel", ["tiles", "batch", "topk"])
def test_kernel_compiles_for_v5e(kernel, N, Q, one_chip,
                                 no_persistent_cache):
    fn, args = _kernel_args(kernel, N, Q, one_chip)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
