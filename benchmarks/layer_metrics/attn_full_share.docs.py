"""Per-layer metric `attn_full_share.docs`: self time of the ops under the scope `attn_full` (a full layer's attention half: projections, the paged kernel over the whole context, the output product; the pool's writes are `kv_pool`'s) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "attn_full")
