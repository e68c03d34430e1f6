"""``repro.api`` — the composable Policy/Experiment surface of Flex.

Three protocols (PlacementPolicy, Estimator, PenaltyController), a string
registry, one shared admission core used by both the discrete-time
simulator and the LLM serving engine, and an ``Experiment`` front-end that
vmaps whole studies into one XLA program.

    from repro.api import Experiment, register_policy

    @register_policy("my-policy")
    class MyPolicy: ...

    Experiment(trace, cluster, policy="my-policy").run(seeds=range(8))
"""
from repro.api.admission import (  # noqa: F401
    NEG_INF,
    KernelInputs,
    PolicyContext,
    TaskView,
    admit_one,
    admit_queue,
    admit_queue_wavefront,
    committed_load,
    dominant,
    fits,
    least_loaded_score,
    mask_infeasible,
    pick_node,
    usage_load,
)
from repro.api.protocols import (  # noqa: F401
    Estimator,
    PenaltyController,
    PlacementPolicy,
    policy_default_params,
    policy_prepare_params,
    policy_queue_order,
    policy_supports_kernel,
)
from repro.api.registry import (  # noqa: F401
    get_policy,
    list_policies,
    register_policy,
    resolve_policy,
)
from repro.api.policies import (  # noqa: F401
    AimdPenaltyController,
    BestFitUsagePolicy,
    CurrentUsageEstimator,
    EwmaEstimator,
    FlexFifoPolicy,
    FlexLrfPolicy,
    LeastFitPolicy,
    OversubPolicy,
    PriorityFlexPolicy,
    ReclaimPolicy,
    resolve_estimator,
)
from repro.estimators import (  # noqa: F401
    EstimatorState,
    get_estimator,
    list_estimators,
    register_estimator,
)
from repro.api.experiment import Experiment  # noqa: F401
