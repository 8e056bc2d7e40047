"""Per-layer metric `moe_stream_roofline.docs`: over the paired ticks whose `rlt.serve.account` event carries `experts_hit`: `shapes_conv_moe.moe_stream` (FLOPs expert_rows x 3 x hidden x width x 2; bytes the weights of the (layer, expert) pairs that got a row in either lane, each once, plus the rows), through `shapes.roofline_seconds`, over the self time of the ops under the scope `moe_experts` in those executions."""
from benchmarks.harness import shapes_conv_moe

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_conv_moe.moe_stream_roofline_pct(run)
