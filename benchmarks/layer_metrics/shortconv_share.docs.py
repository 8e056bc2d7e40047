"""Per-layer metric `shortconv_share.docs`: self time of the ops under the scopes `shortconv` (a gated short convolution whole: its in and out projections, the gates and the taps, in both lanes) and `shortconv_state` inside it (the reads and writes of its carried tail: innermost wins in the trace's table, so the two are summed here) over the step program's device time."""
from benchmarks.harness import program_trace

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    whole = program_trace.scope_share_pct(run, "shortconv")
    state = program_trace.scope_share_pct(run, "shortconv_state")
    if whole is None or state is None:
        return None
    return whole + state
