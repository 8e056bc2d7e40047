"""Operations and bytes of an expert layer that HOLDS every expert and is
bound by streaming their weights, and the reductions of the per-layer
metrics that read them. Kept with the benchmark so that no PR that claims a
gain can change them. The roofline ends in `shapes.roofline_seconds`
(through `program_trace.roofline_pct`).

`shapes_mla_moe.moe_experts` counts every held expert's weights once a
layer, which is right where 16 held experts all get rows of a 1024-row
chunk. With 64 small experts and a decode lane of 128 rows, which experts a
tick reads is the router's to say, and a tick with a chunk may read an
expert once for both lanes or once a lane. `moe_stream` therefore counts
the weights of the (layer, expert) pairs a tick HIT, each once
(`experts_hit`, a device-side count on `rlt.serve.account`: the union over
the tick's two lanes): the same work whether a program streams the experts
a lane or together, and never the bytes of an expert nobody asked for, so
the share cannot pass 100% on their account.
"""
from __future__ import annotations

import statistics
from typing import Optional

from benchmarks.harness import program_trace as pt
from benchmarks.harness import shapes_mla_moe

ACCOUNT = shapes_mla_moe.ACCOUNT


def moe_stream(expert_rows: int, experts_hit: int, hidden: int, width: int,
               held: int = 0, itemsize: int = 2) -> dict:
    """A tick's expert products: `expert_rows` rows (summed over the expert
    layers and the lanes) through gate, up and down; the weights of the
    `experts_hit` (layer, expert) pairs that got a row read once, the rows
    read and written once. `held` is not read: what is held and not hit
    costs nothing."""
    return {"flops": expert_rows * 3.0 * hidden * width * 2,
            "bytes": experts_hit * 3 * hidden * width * itemsize
                     + expert_rows * 2 * hidden * itemsize}


def moe_stream_roofline_pct(run) -> Optional[float]:
    """The paired ticks' expert work over the self time of the ops under
    the scope `moe_experts` inside the same executions. None where the
    program counts no `experts_hit`."""
    tb = pt.tables(run)
    model = run.model_tables()
    if tb is None or not hasattr(model, "expert_dims"):
        return None
    paired = [(r, s) for r, s in shapes_mla_moe.paired_accounts(tb)
              if "experts_hit" in s and "expert_rows" in s]
    if not paired:
        return None
    inside = pt.within(tb.step_ops[0], [r for r, _ in paired])
    seconds = pt.scope_self_seconds(inside).get("moe_experts", 0.0)
    dims = model.expert_dims(run.hp)
    work = [moe_stream(int(s["expert_rows"]), int(s["experts_hit"]), **dims)
            for _, s in paired]
    return pt.roofline_pct(work, seconds, run.peaks)


def expert_load_peak_pct(run) -> Optional[float]:
    """The fullest expert's rows over the mean rows a (layer, expert), in
    percent, median over the traced ticks' `rlt.serve.account` events: 100
    is even routing, `held` x 100 one expert taking a layer's every row."""
    tb = pt.tables(run)
    model = run.model_tables()
    if tb is None or not hasattr(model, "expert_dims"):
        return None
    pairs = model.expert_layers(run.hp) * model.expert_dims(run.hp)["held"]
    ratios = [100.0 * int(e.stats["expert_rows_max"]) * pairs
              / int(e.stats["expert_rows"])
              for e in tb.trace.host_named(ACCOUNT)
              if int(e.stats.get("expert_rows", 0)) > 0
              and "expert_rows_max" in e.stats]
    return statistics.median(ratios) if ratios else None
