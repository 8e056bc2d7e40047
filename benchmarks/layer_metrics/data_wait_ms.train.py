"""Per-layer metric `data_wait_ms.train`: median `rlt.data_wait` a step on the thread that dispatches the steps: how long the loop waits for the prefetcher."""
from benchmarks.harness import program_trace

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def reduce(run):
    return program_trace.data_wait_ms(run)
