"""Per-layer metric `sched_host_ms.chat`: median over the traced ticks of `rlt.serve.tick` minus the engine's three spans inside it (`rlt.serve.put`, `.dispatch`, `.fetch`): the scheduler's own time."""
from benchmarks.harness import program_trace

LAYER = "serving host loop"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def reduce(run):
    return program_trace.sched_host_ms(run)
