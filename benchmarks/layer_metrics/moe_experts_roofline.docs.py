"""Per-layer metric `moe_experts_roofline.docs`: over the paired ticks whose `rlt.serve.account` event carries `expert_rows`: `shapes_mla_moe.moe_experts` (FLOPs expert_rows x 3 x hidden x width x 2, bytes the held experts' weights once a layer plus the rows), through `shapes.roofline_seconds`, over the self time of the ops under the scope `moe_experts` in those executions."""
from benchmarks.harness import shapes_mla_moe

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_mla_moe.moe_experts_roofline_pct(run)
