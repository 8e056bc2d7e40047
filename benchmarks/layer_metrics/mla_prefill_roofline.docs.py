"""Per-layer metric `mla_prefill_roofline.docs`: over the paired ticks that carry a chunk: layers x `shapes_mla_moe.mla_prefill` of the dispatch's `prefill_rows` and `prefill_ctx` (FLOPs the fewer of the absorbed and the expanded form), through `shapes.roofline_seconds`, over the device time of the `rlt_mla_prefill` events plus the ops under the scope `mla_expand`."""
from benchmarks.harness import shapes_mla_moe

LAYER = "serve kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def reduce(run):
    return shapes_mla_moe.mla_prefill_roofline_pct(run)
