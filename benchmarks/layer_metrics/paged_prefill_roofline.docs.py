"""Per-layer metric `paged_prefill_roofline.docs`: over the paired ticks that carry a chunk: layers x `shapes.paged_prefill` of the dispatch's `prefill_rows` and `prefill_ctx`, through `shapes.roofline_seconds`, over the device time of the `rlt_paged_prefill` events."""
from benchmarks.harness import program_trace

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.paged_prefill_roofline_pct(run)
