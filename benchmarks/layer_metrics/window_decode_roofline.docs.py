"""Per-layer metric `window_decode_roofline.docs`: over the paired ticks with a decoding slot: `shapes.paged_decode` of the dispatch's `kv_tokens` on the full layers and of `kv_tokens_window` (the bands) on the window layers, each times its layers, through `shapes.roofline_seconds`, over the device time of the `rlt_paged_decode` events."""
from benchmarks.harness import shapes_window

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_window.window_decode_roofline_pct(run)
