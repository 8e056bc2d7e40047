"""Per-layer metric `host_exposed_ms.docs`: device idle between two consecutive executions of the step program (outside any execution), median over the traced ticks: what the chip waits for the host (the mean and its split by `rlt.*` phase are on the `[program]` line)."""
from benchmarks.harness import program_trace

LAYER = "serving host loop"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.host_exposed_ms(run)
