"""Operations and bytes of a selective scan (the recurrence of a Mamba-1
state-space mixer) from its shapes: what the algorithm needs, whatever the
program does; and the reduction of the per-layer metric that reads them.
Kept with the benchmark so that no PR that claims a gain can change them. The
roofline ends in `shapes.roofline_seconds` (through
`program_trace.roofline_pct`).

    h_t = exp(delta_t A) * h_{t-1} + (delta_t B_t) x_t      h [E, N]
    y_t = h_t C_t + D x_t,  out_t = y_t * silu(z_t)

**What is counted.** One (row, channel, state) step is 7 FLOPs: `delta A`,
the exponential (counted as one), its product with `h`, `(delta x) B`, the
sum, and `h C` with its sum into `y`. A (row, channel) pair adds 12: the
step size's softplus (4), `delta x`, `D x` and its sum, and the gate `y z
sigmoid(z)` (5). Bytes: the rows `x`, `z`, the step's input and the output,
`[rows, E]` each, in the activations' 2 bytes; `B` and `C` `[rows, N]` in 4;
the state `[E, N]` float32 read once and written once a sequence. The dims
and the number of scanning layers come from the run's own tables
(`scan_dims`, `scan_layers`); the rows from `rlt.serve.dispatch`
(`scan_rows`: the REAL rows of the tick's chunk; `state_slots`: the slots the
decode lane moves by a row, carried on the event for a reader of that lane).

**What it is measured against.** The device time of the kernel's events
(`rlt_ssm_scan`) in the paired ticks whose chunk holds a real row. The scan
runs on the vector unit, where a step's exponential and multiplies are one
lane of one instruction each; `peaks.json` holds the matrix unit's bf16 peak
and the memory's. Against the first the FLOPs are nothing (3 us a 1,024-row
call), so the yardstick's BYTES set the least time (52 us a call) and the
share reads low by construction (an eighth, PR 35): it moves the right way
when the kernel gets faster, and it cannot pass 100% while the kernel reads
its rows at all. A vector-unit peak is a `benchmark` PR's.
"""
from __future__ import annotations

from typing import Optional

from benchmarks.harness import program_trace as pt

KERNEL = "rlt_ssm_scan"
STEP_FLOPS, PAIR_FLOPS = 7, 12


def selective_scan(rows: int, sequences: int, channels: int, states: int,
                   itemsize: int = 2) -> dict:
    """One layer's scan of `rows` rows in all, over `sequences` sequences
    (each reads its state in and writes it out once)."""
    return {"flops": rows * channels * (STEP_FLOPS * states + PAIR_FLOPS),
            "bytes": (4 * rows * channels * itemsize
                      + 2 * rows * states * 4
                      + 2 * sequences * channels * states * 4)}


def ssm_scan_roofline_pct(run) -> Optional[float]:
    """Over the paired ticks whose chunk holds a real row: `selective_scan`
    of the dispatch's `scan_rows` (one sequence: the prefill lane's slot)
    times the scanning layers, against the device time of every
    `rlt_ssm_scan` event in those ticks."""
    tb = pt.tables(run)
    model = run.model_tables()
    if tb is None or not hasattr(model, "scan_dims"):
        return None
    pt.need_kernels(tb, [KERNEL])
    seconds, stats = pt.paired_kernel_seconds(
        tb, KERNEL, lambda s: pt.counter(s, "scan_rows") > 0)
    dims, layers = model.scan_dims(run.hp), model.scan_layers(run.hp)
    work = []
    for s in stats:
        one = selective_scan(pt.counter(s, "scan_rows"), 1, **dims)
        work.append({k: layers * v for k, v in one.items()})
    return pt.roofline_pct(work, seconds, run.peaks)
