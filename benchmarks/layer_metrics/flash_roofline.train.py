"""Per-layer metric `flash_roofline.train`: layers x `shapes.flash_fwd_bwd` of a chip's share of the batch over the device time of the three `rlt_flash_*` kernels a step; recomputed forwards are in the time and not in the work."""
from benchmarks.harness import program_trace

LAYER = "train kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.flash_roofline_pct(run)
