"""Per-layer metric `tick_ms.chat`: median wall time of driver.tick() inside the window, from the benchmark's own span."""
from benchmarks.harness import readers

LAYER = "serving host loop"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def reduce(run):
    return readers.tick_ms(run)
