"""Per-layer metric `tick_ms.docs`: median wall time of driver.tick() inside the window, from the benchmark's own span."""
from benchmarks.harness import readers

LAYER = "serving host loop"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def reduce(run):
    return readers.tick_ms(run)
