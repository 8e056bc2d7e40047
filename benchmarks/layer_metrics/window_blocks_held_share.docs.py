"""Per-layer metric `window_blocks_held_share.docs`: over the traced ticks' `rlt.serve.account` events, `window_blocks_live` (the blocks a window layer holds for the slotted requests, at most a ring a slot) over `full_blocks_live` (the blocks one table for all layers would hold a layer): the share of a one-table pool the window layers still hold."""
from benchmarks.harness import shapes_window

LAYER = "serving host loop"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def reduce(run):
    return shapes_window.window_blocks_held_share_pct(run)
