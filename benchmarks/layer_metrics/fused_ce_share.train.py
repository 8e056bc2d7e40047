"""Per-layer metric `fused_ce_share.train`: self time of the ops under the scope `fused_ce` (forward and backward of the chunked head and loss) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "train kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "fused_ce")
