"""Per-layer metric `moe_router_share.train`: self time of the ops under the scope `moe_router` (the float32 router product over the layer's input, the top-k, the softmax over the chosen, and their backward) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "moe_router")
