"""Per-layer metric `delta_chunk_roofline.docs`: over the paired ticks whose chunk holds a real row: `shapes_delta.gated_delta` of the dispatch's `delta_rows` (the recurrence's 7 d_k d_v FLOPs a (row, head) plus the norms and the gate; the rows' q, k, v, gate and output, alpha and beta, the state in and out) times the linear layers, through `shapes.roofline_seconds`, over the device time of the `rlt_delta_chunk` events. The work is the algorithm's: the chunked form's extra products are not counted, so the share is bound by the yardstick's bytes."""
from benchmarks.harness import shapes_delta

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_delta.delta_chunk_roofline_pct(run)
