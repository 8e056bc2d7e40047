"""Per-layer metric `optimizer_share.train`: self time of the ops under the scope `optimizer` (`tx.update` and `apply_updates`) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "optimizer")
