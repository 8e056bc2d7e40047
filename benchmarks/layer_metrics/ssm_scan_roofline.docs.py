"""Per-layer metric `ssm_scan_roofline.docs`: over the paired ticks whose chunk holds a real row: `shapes_ssm.selective_scan` of the dispatch's `scan_rows` (7 FLOPs a (row, channel, state) step and 12 a (row, channel) pair; the rows' x, z, step input and output, B and C, the state in and out) times the scanning layers, through `shapes.roofline_seconds`, over the device time of the `rlt_ssm_scan` events. Against the matrix unit's peak a vector-unit kernel reads low by construction."""
from benchmarks.harness import shapes_ssm

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_ssm.ssm_scan_roofline_pct(run)
