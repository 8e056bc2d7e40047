"""Per-layer metric `host_gap_ms.train`: median gap on the device between consecutive executions of the train-step program."""
from benchmarks.harness import readers

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return readers.step_gap_ms(run)
