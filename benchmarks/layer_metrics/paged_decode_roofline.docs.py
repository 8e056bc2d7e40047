"""Per-layer metric `paged_decode_roofline.docs`: over the ticks paired with their `rlt.serve.dispatch`: layers x `shapes.paged_decode` of the dispatch's `kv_tokens` and `decode_slots` at the model's own heads (`tables.attention_dims`), through `shapes.roofline_seconds`, over the device time of the `rlt_paged_decode` events."""
from benchmarks.harness import program_trace

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return program_trace.paged_decode_roofline_pct(run)
