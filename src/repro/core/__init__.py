"""Flex core: usage-based load balancing with QoS feedback control."""
from repro.core.types import (  # noqa: F401
    CLASS_BATCH,
    CLASS_PRODUCTION,
    CLASS_SYSTEM,
    CPU,
    MEM,
    NUM_CLASSES,
    NUM_RESOURCES,
    NUM_SRC_BUCKETS,
    ControllerState,
    FlexParams,
    NodeState,
    SimConfig,
    SimResult,
    SlotMetrics,
    TaskSet,
)
from repro.core.penalty import update_penalty  # noqa: F401
from repro.core.schedulers import (  # noqa: F401
    fifo_scheduler,
    lrf_scheduler,
)
from repro.core.allocation import waterfill, wfs_allocate  # noqa: F401
from repro.core.qos import cluster_qos, task_qos, violation_fraction  # noqa: F401
from repro.core.simulator import build_arrival_table, run, simulate  # noqa: F401
