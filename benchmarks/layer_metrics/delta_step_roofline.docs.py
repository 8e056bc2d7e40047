"""Per-layer metric `delta_step_roofline.docs`: over the paired ticks in which the decode lane moves a slot's state: `shapes_delta.gated_delta` of the dispatch's `state_slots` rows in as many sequences (one row a slot: the state in and out is all but all of the bytes) times the linear layers, through `shapes.roofline_seconds`, over the device time of the `rlt_delta_step` events."""
from benchmarks.harness import shapes_delta

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_delta.delta_step_roofline_pct(run)
