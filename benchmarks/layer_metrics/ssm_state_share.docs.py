"""Per-layer metric `ssm_state_share.docs`: self time of the ops under the scope `ssm_state` (the reads and writes that move a slot's rows between the carried state leaves and a state-space mixer's computation: every slot's rows in the decode lane, one slot's in the prefill lane) over the step program's device time. A carried leaf copied whole would show here."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.scope_share_pct(run, "ssm_state")
