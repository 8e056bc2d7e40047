"""Per-layer metric `mla_decode_roofline.docs`: over the paired ticks with a decoding slot: layers x `shapes_mla_moe.mla_decode` of the dispatch's `kv_tokens` and `decode_slots` (FLOPs 2 x kv_tokens x heads x (576 + 512), bytes the cached rows once plus the queries and outputs), through `shapes.roofline_seconds`, over the device time of the `rlt_mla_decode` events."""
from benchmarks.harness import shapes_mla_moe

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_mla_moe.mla_decode_roofline_pct(run)
