"""Per-layer metric `attn_window_share.docs`: self time of the ops under the scope `attn_window` (a sliding-window layer's attention half: projections, rotation, the paged kernel over the band, the output product; the pool's writes are `kv_pool`'s) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "attn_window")
