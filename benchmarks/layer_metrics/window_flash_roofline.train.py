"""Per-layer metric `window_flash_roofline.train`: a step's attention work by kind of layer, forward and backward as `shapes.flash_fwd_bwd` reckons it (3.5 x the forward's two products; q, k, v, o, do bytes) with the pairs of a window layer `W (W + 1) / 2 + (S - W) W` and of a full layer `S (S + 1) / 2` (`tables.band_pairs`), through `shapes.roofline_seconds`, over the device time of the three `rlt_flash_*` kernels a step; recomputed forwards are in the time and not in the work."""
from benchmarks.harness import shapes_swa_moe

LAYER = "train kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_swa_moe.window_flash_roofline_pct(run)
