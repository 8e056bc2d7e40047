"""Per-layer metric `sampling_share.chat`: self time of the ops under the scope `sample` (temperature, top-k, the sort, the draw) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "sample")
