from repro.kernels.flex_score.flex_score import (  # noqa: F401
    NEG_INF,
    flex_score_batch_topk_tiles,
    flex_score_tiles,
)
from repro.kernels.flex_score.ops import (  # noqa: F401
    flex_pick_node,
    flex_pick_node_batch_topk,
)
from repro.kernels.flex_score.ref import (  # noqa: F401
    pick_node_batch_ref,
    pick_node_batch_topk_ref,
    pick_node_ref,
)
