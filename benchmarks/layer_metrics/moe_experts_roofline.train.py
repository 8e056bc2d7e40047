"""Per-layer metric `moe_experts_roofline.train`: `shapes_swa_moe.moe_experts_fwd_bwd` (FLOPs expert_rows x 3 x hidden x width x 2 x 3: forward, the rows' cotangent, the weights'; bytes the held experts' weights read twice in the compute type, their gradient written once in float32, the rows in and out) with the program's own `expert_rows` (median over the traced fetches' `rlt.train.account`), through `shapes.roofline_seconds`, over the self time of the ops under the scope `moe_experts` a step."""
from benchmarks.harness import shapes_swa_moe

LAYER = "train kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_swa_moe.moe_experts_roofline_pct(run)
