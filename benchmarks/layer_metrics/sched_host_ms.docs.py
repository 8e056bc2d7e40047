"""Per-layer metric `sched_host_ms.docs`: median over the traced ticks of `rlt.serve.tick` minus the engine's three spans inside it (`rlt.serve.put`, `.dispatch`, `.fetch`): the scheduler's own time."""
from benchmarks.harness import program_trace

LAYER = "serving host loop"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def reduce(run):
    return program_trace.sched_host_ms(run)
