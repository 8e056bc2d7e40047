"""Per-layer metric `device_idle_share.chat`: share of a steady traced stretch in which no op ran on the chip (1 - union of device-op intervals over the window), averaged over the chips."""
from benchmarks.harness import readers

LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def reduce(run):
    return readers.device_idle_share_pct(run)
