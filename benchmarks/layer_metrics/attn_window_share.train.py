"""Per-layer metric `attn_window_share.train`: self time of the ops under the scope `attn_window` (a sliding-window layer's attention half: norm, projections, rotation, the flash kernels over the band, the output product, forward and backward) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "attn_window")
