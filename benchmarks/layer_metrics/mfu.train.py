"""Per-layer metric `mfu.train`: tokens/s x model FLOPs/token (recompute not counted) over chips x the table's bf16 peak."""
from benchmarks.harness import readers

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def reduce(run):
    return readers.mfu_pct(run)
