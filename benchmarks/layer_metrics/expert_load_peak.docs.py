"""Per-layer metric `expert_load_peak.docs`: over the traced ticks' `rlt.serve.account` events, `expert_rows_max` (the fullest expert's rows of any layer) over the mean rows a (layer, expert) pair, `expert_rows` / (expert layers x experts held), in percent, median over the ticks: 100 is even routing."""
from benchmarks.harness import shapes_conv_moe

LAYER = "serving step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def reduce(run):
    return shapes_conv_moe.expert_load_peak_pct(run)
