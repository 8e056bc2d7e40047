"""Per-layer metric `peak_hbm_gib.train`: peak_bytes_in_use after the window on the fullest chip: the room left for a larger batch or less recompute."""
from benchmarks.harness import readers

LAYER = "device memory"
UNIT = "GiB"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def reduce(run):
    return readers.peak_hbm_gib(run)
