"""Per-layer metric `moe_dispatch_share.train`: self time of the ops under the scope `moe_dispatch` (sorting the (token, expert) pairs by expert, gathering the held experts' rows by index, reading them back and the weighted combine, forward, recomputed and backward) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "moe_dispatch")
