"""Per-layer metric `kv_pool_share.docs`: self time of the ops under the scope `kv_pool` (a layer's K/V taken out of the stacked pool and written back, all but the attention kernels) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "kv_pool")
