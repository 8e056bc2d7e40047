"""Per-layer metric `shortconv_state_share.docs`: self time of the ops under the scope `shortconv_state` (only the reads and writes that move a slot's two rows between the carried tails and a convolution's computation) over the step program's device time. Lower is better: a carried leaf copied whole would show here."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "shortconv_state")
