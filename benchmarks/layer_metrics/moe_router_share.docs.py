"""Per-layer metric `moe_router_share.docs`: self time of the ops under the scope `moe_router` (scores, group choice, top-k, weights; float32) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "moe_router")
