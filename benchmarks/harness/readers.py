"""The reductions the per-layer metric readers share. A reader file in
`benchmarks/layer_metrics/` declares its layer, unit and arrow and calls
one of these; each returns None where the run holds nothing to read, and
`run.py` then leaves the metric out of the line."""
from __future__ import annotations

import statistics
from typing import Optional

from benchmarks.harness import trace


def device_idle_share_pct(run) -> Optional[float]:
    """100 x (1 - union of device-op intervals / traced window), averaged
    over the chips."""
    t = run.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def _step_runs(device):
    """Executions of the program that took most device time in the traced
    stretch: the train step, or the engine's step."""
    totals = {}
    for name, s, e in device.modules:
        totals[name] = totals.get(name, 0.0) + (e - s)
    if not totals:
        return []
    return trace.module_runs(device, max(totals, key=totals.get))


def step_gap_ms(run) -> Optional[float]:
    """Median gap on the device between consecutive executions of the step
    program."""
    t = run.trace
    if t is None:
        return None
    gaps = []
    for d in t.devices:
        runs = _step_runs(d)
        gaps += [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    return 1e3 * statistics.median(gaps) if gaps else None


def step_device_ms(run) -> Optional[float]:
    """Median device time of one execution of the step program."""
    t = run.trace
    if t is None:
        return None
    durs = [e - s for d in t.devices for s, e in _step_runs(d)]
    return 1e3 * statistics.median(durs) if durs else None


def tick_ms(run) -> Optional[float]:
    ticks = run.stamps.get("ticks_s")
    return 1e3 * statistics.median(ticks) if ticks else None


def queue_wait_p95_ms(run) -> Optional[float]:
    from benchmarks.harness.common import percentile

    waits = run.stamps.get("queue_wait_s")
    return 1e3 * percentile(waits, 95) if waits else None


def mfu_pct(run) -> Optional[float]:
    """Tokens/s x the model's own FLOPs/token (`tables/<model>.py`;
    recompute not counted) over chips x peak."""
    tps = run.end_to_end.get("train_tokens_per_s")
    if not tps:
        return None
    fpt = run.model_tables().train_flops_per_token(run.hp, run.stamps["seq"])
    return 100.0 * tps * fpt / (run.chips * run.peaks["bf16_flops_per_s"])


def peak_hbm_gib(run) -> Optional[float]:
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None


def exposed_collective_pct(run) -> Optional[float]:
    """Collective time with no compute running on that chip, over the
    traced window, averaged over the chips."""
    t = run.trace
    if t is None or len(t.devices) < 2 or t.window_s <= 0:
        return None
    shares = []
    for d in t.devices:
        leaves = [(n, s, e) for n, s, e in d.ops
                  if not n.startswith(("while", "conditional", "call"))]
        coll = [(s, e) for n, s, e in leaves if trace.is_collective(n)]
        comp = [(s, e) for n, s, e in leaves if not trace.is_collective(n)]
        if not coll:
            return None
        shares.append(trace.exposed_seconds(coll, comp) / t.window_s)
    return 100.0 * sum(shares) / len(shares)
