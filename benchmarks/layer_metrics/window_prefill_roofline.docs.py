"""Per-layer metric `window_prefill_roofline.docs`: over the paired ticks that carry a chunk: the full layers' `shapes.paged_prefill` of the dispatch's `prefill_rows` and `prefill_ctx`, and the window layers' `shapes_window.window_prefill` (the band each row reads, from `prefill_rows`, `prefill_ctx`, `prefill_ctx_window` and the tables' window), each times its layers, through `shapes.roofline_seconds`, over the device time of the `rlt_paged_prefill` events."""
from benchmarks.harness import shapes_window

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_window.window_prefill_roofline_pct(run)
