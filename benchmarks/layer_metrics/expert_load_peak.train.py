"""Per-layer metric `expert_load_peak.train`: over the traced fetches' `rlt.train.account` events, `expert_rows_max` (the fullest held expert's rows, summed over the layers) x experts held over `expert_rows` (rows routed to held experts, summed over the layers), in percent, median: 100 is even routing."""
from benchmarks.harness import shapes_swa_moe

LAYER = "model"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def reduce(run):
    return shapes_swa_moe.expert_load_peak_pct(run)
