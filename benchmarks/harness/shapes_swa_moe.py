"""Work functions and reductions of the training rooflines of a decoder
with sliding-window layers and held experts (`tables/<model>.py` with
`band_pairs`, `attention_layers(hp, kind)`, `expert_dims`): a step's flash
attention counted over each kind of layer's own (query, key) pairs, and the
expert layers' grouped products forward and backward over the rows the
program itself counted.

The counts a traced step carries are on the host event `rlt.train.account`
(`core/trainer.py`: the integer scalars the module logged in the step whose
metrics were just fetched). A program without that event, or a model
without these tables, gives None and the line leaves the metric out.
"""
from __future__ import annotations

import statistics
from typing import Optional

from benchmarks.harness import program_trace as pt
from benchmarks.harness import shapes

ACCOUNT = "rlt.train.account"


def flash_fwd_bwd_pairs(pairs: float, seq: int, heads: int, kv_heads: int,
                        head_dim: int, itemsize: int = 2) -> dict:
    """Flash attention, forward and backward, of one sequence of `seq`
    tokens whose rows see `pairs` (query, key) pairs in all:
    `shapes.flash_fwd_bwd` with the pairs given and not taken as the
    causal triangle's `seq^2 / 2`. FLOPs: the forward's two products of
    `2 x pairs x head_dim` a head, 3.5 x with the backward's five. Bytes:
    q, k, v, o once forward; q, k, v, o, do read and dq, dk, dv written
    backward."""
    fwd = 2 * 2 * heads * pairs * head_dim
    q_bytes = seq * heads * head_dim * itemsize
    kv_bytes = seq * kv_heads * head_dim * itemsize
    return {"flops": 3.5 * fwd,
            "bytes": (2 * q_bytes + 2 * kv_bytes)
                     + (4 * q_bytes + 4 * kv_bytes)}


def moe_experts_fwd_bwd(expert_rows: int, layers: int, hidden: int,
                        width: int, held: int, itemsize: int = 2) -> dict:
    """The grouped products of `layers` expert layers over `expert_rows`
    rows in all, forward and backward. FLOPs: a row goes through gate, up
    and down (`3 x hidden x width` multiply-adds) forward, again for the
    rows' cotangent and again for the weights'. Bytes: the held experts'
    weights read in the compute type forward and for the rows' cotangent,
    their gradient written once in float32, and a row read and written at
    `hidden` wide in each of the three."""
    weights = layers * held * 3 * hidden * width
    return {"flops": 3 * 2 * expert_rows * 3 * hidden * width,
            "bytes": 2 * weights * itemsize + 4 * weights
                     + 3 * 2 * expert_rows * hidden * itemsize}


def _tables(run):
    tb = pt.tables(run)
    model = run.model_tables()
    if tb is None or not hasattr(model, "band_pairs"):
        return None, model
    return tb, model


def _accounts(tb):
    return [e.stats for e in tb.trace.host_named(ACCOUNT)
            if int(e.stats.get("expert_rows", 0)) > 0]


def window_flash_roofline_pct(run) -> Optional[float]:
    """A step's attention work, each layer at its own kind's pairs, over
    the device time of the three flash kernels a step; recomputed forwards
    are in the time and not in the work."""
    tb, model = _tables(run)
    if tb is None:
        return None
    pt.need_kernels(tb, pt.FLASH_KERNELS)
    seconds = sum(tb.kernels[k][0] for k in pt.FLASH_KERNELS)
    seq = run.stamps["seq"]
    rows = run.traffic["batch"] / run.chips
    dims = model.attention_dims(run.hp)
    step = {"flops": 0.0, "bytes": 0.0}
    for kind in dict.fromkeys(model.layer_kinds(run.hp)):
        one = flash_fwd_bwd_pairs(model.band_pairs(run.hp, kind, seq), seq,
                                  **dims)
        for k, v in one.items():
            step[k] += rows * model.attention_layers(run.hp, kind) * v
    return pt.roofline_pct([step] * len(tb.runs[0]), seconds, run.peaks)


def moe_experts_roofline_pct(run) -> Optional[float]:
    """The traced steps' expert work over the self time of the ops under the
    scope `moe_experts`. The rows are the program's own count: the median
    `expert_rows` over the traced fetches stands for each traced step, whose
    routing differs by a few rows."""
    tb, model = _tables(run)
    if tb is None:
        return None
    counts = _accounts(tb)
    if not counts:
        return None
    rows = int(statistics.median(int(s["expert_rows"]) for s in counts))
    seconds = tb.scopes.get("moe_experts", 0.0)
    work = moe_experts_fwd_bwd(rows, model.expert_layers(run.hp),
                               **model.expert_dims(run.hp))
    return pt.roofline_pct([work] * len(tb.runs[0]), seconds, run.peaks)


def expert_load_peak_pct(run) -> Optional[float]:
    """The fullest held expert's rows (summed over the layers) over the
    mean rows a held expert, in percent, median over the traced fetches: 100
    is even routing."""
    tb, model = _tables(run)
    if tb is None:
        return None
    held = model.expert_dims(run.hp)["held"]
    ratios = [100.0 * int(s["expert_rows_max"]) * held
              / int(s["expert_rows"])
              for s in _accounts(tb) if "expert_rows_max" in s]
    return statistics.median(ratios) if ratios else None
