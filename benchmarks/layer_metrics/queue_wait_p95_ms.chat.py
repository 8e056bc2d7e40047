"""Per-layer metric `queue_wait_p95_ms.chat`: 95th percentile of the time from when a request was due to the start of the first tick in which it holds a slot."""
from benchmarks.harness import readers

LAYER = "serving host loop"
UNIT = "ms"
MOVES = "ttft_p95_ms"
SOURCE = "host_clock"


def reduce(run):
    return readers.queue_wait_p95_ms(run)
