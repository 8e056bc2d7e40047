"""Per-layer metric `collective_exposed_share.train`: all-gather / reduce-scatter / all-reduce time during which no compute runs on that chip, over the traced window (cells on more than one chip)."""
from benchmarks.harness import readers

LAYER = "sharding"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return readers.exposed_collective_pct(run)
