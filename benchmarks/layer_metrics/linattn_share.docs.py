"""Per-layer metric `linattn_share.docs`: self time of the ops under the scopes `linattn` (a linear-attention mixer whole: its projections, convolutions, norms, the gated delta rule in both lanes and the gate) and `linattn_state` inside it (the reads and writes of its carried state: innermost wins in the trace's table, so the two are summed here) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    whole = program_trace.scope_share_pct(run, "linattn")
    state = program_trace.scope_share_pct(run, "linattn_state")
    if whole is None or state is None:
        return None
    return whole + state
