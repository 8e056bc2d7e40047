"""Per-layer metric `attn_full_share.train`: self time of the ops under the scope `attn_full` (a full layer's attention half: norm, projections, no rotation, the flash kernels over the triangle, the output product, forward and backward) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "attn_full")
