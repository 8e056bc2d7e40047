"""Per-layer metric `ssm_share.docs`: self time of the ops under the scope `ssm` (a state-space mixer whole: the four projections, the convolution, the inner norms, the selective scan, the gate; the reads and writes of its carried state are `ssm_state`'s) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "ssm")
