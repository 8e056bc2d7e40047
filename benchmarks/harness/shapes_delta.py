"""Operations and bytes of a gated delta rule (the recurrence of a Gated
DeltaNet linear-attention layer) from its shapes: what the algorithm needs,
whatever form computes it; and the reductions of the per-layer metrics that
read them. Kept with the benchmark so that no PR that claims a gain can
change them. The roofline ends in `shapes.roofline_seconds` (through
`program_trace.roofline_pct`).

    S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
    o_t = S_t^T q_t                                   S [d_k, d_v] a head

**What is counted.** One (row, head) step of the recurrence is 7 d_k d_v
FLOPs: the decay of `S` (1), `S^T k` (2), the rank-one correction and its
sum into `S` (2), `S^T q` (2). A (row, head) pair adds the norms and the
gate: q and k over their L2 norms (6 d_k), the output's RMSNorm (4 d_v) and
its product with the gate's silu (5 d_v). Bytes: the rows q, k [rows, H
d_k] and v, the gate and the output [rows, H d_v] in the activations' 2
bytes; alpha and beta [rows, H] in 4; the state [H, d_k, d_v] float32 read
once and written once a sequence. The chunked form a program may run does
more arithmetic than this (the triangular system, the products inside a
chunk) on the matrix unit; it is not counted. The dims and the number of
linear layers come from the run's own tables (`delta_dims`, `delta_layers`);
the rows from `rlt.serve.dispatch` (`delta_rows`: the REAL rows of the
tick's chunk; `state_slots`: the slots the decode lane moves by a row).

**What it is measured against.** The device time of the kernel's events in
the paired ticks that hold such rows. Against `peaks.json` the recurrence's
FLOPs at the matrix unit's peak are less than its bytes at the memory's, so
the yardstick's BYTES set the least time, and the share cannot pass 100%
while the kernel reads its rows at all.
"""
from __future__ import annotations

from typing import Optional

from benchmarks.harness import program_trace as pt

CHUNK_KERNEL = "rlt_delta_chunk"
STEP_KERNEL = "rlt_delta_step"
STEP_FLOPS = 7


def gated_delta(rows: int, sequences: int, heads: int, d_k: int, d_v: int,
                itemsize: int = 2) -> dict:
    """One layer's delta rule over `rows` rows in all, in `sequences`
    sequences (each reads its state in and writes it out once)."""
    pair = 6 * d_k + 9 * d_v
    return {"flops": rows * heads * (STEP_FLOPS * d_k * d_v + pair),
            "bytes": (rows * heads * (2 * d_k + 3 * d_v) * itemsize
                      + 2 * rows * heads * 4
                      + 2 * sequences * heads * d_k * d_v * 4)}


def _roofline(run, kernel: str, counter: str, sequences) -> Optional[float]:
    tb = pt.tables(run)
    model = run.model_tables()
    if tb is None or not hasattr(model, "delta_dims"):
        return None
    if kernel not in tb.kernels:
        return None
    seconds, stats = pt.paired_kernel_seconds(
        tb, kernel, lambda s: int(s.get(counter, 0)) > 0)
    dims, layers = model.delta_dims(run.hp), model.delta_layers(run.hp)
    work = []
    for s in stats:
        n = pt.counter(s, counter)
        one = gated_delta(n, sequences(n), **dims)
        work.append({k: layers * v for k, v in one.items()})
    return pt.roofline_pct(work, seconds, run.peaks)


def delta_chunk_roofline_pct(run) -> Optional[float]:
    """Over the paired ticks whose chunk holds a real row: `gated_delta` of
    the dispatch's `delta_rows` (one sequence: the prefill lane's slot)
    times the linear layers, against the device time of every
    `rlt_delta_chunk` event in those ticks. Nothing where the program has no
    such kernel or counter."""
    return _roofline(run, CHUNK_KERNEL, "delta_rows", lambda n: 1)


def delta_step_roofline_pct(run) -> Optional[float]:
    """The same over the decode lane's one-row update where it is a kernel:
    `state_slots` rows in as many sequences, against `rlt_delta_step`."""
    return _roofline(run, STEP_KERNEL, "state_slots", lambda n: n)
