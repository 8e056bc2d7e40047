"""Per-layer metric `step_device_ms.chat`: median device time of the engine's one jitted step a tick, from the trace's module line."""
from benchmarks.harness import readers

LAYER = "serving step"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def reduce(run):
    return readers.step_device_ms(run)
