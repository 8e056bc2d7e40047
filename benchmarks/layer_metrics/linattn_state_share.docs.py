"""Per-layer metric `linattn_state_share.docs`: self time of the ops under the scope `linattn_state` (only the reads and writes that move a slot's rows between the carried state leaves and a linear mixer's computation) over the step program's device time. Lower is better: a carried leaf copied whole would show here."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "linattn_state")
