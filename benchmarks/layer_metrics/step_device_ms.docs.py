"""Per-layer metric `step_device_ms.docs`: median device time of the engine's one jitted step a tick, from the trace's module line."""
from benchmarks.harness import readers

LAYER = "serving step"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return readers.step_device_ms(run)
