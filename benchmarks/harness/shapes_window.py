"""Operations and bytes of the dense paged kernels in a decoder whose layers
are of two kinds, sliding-window and full, from their shapes: what the
algorithm needs, whatever the program does; and the reductions of the
per-layer metrics that read them. Kept with the benchmark so that no PR
that claims a gain can change them. Every roofline here ends in
`shapes.roofline_seconds` (through `program_trace.roofline_pct`).

A full layer's work is `shapes.paged_prefill` / `shapes.paged_decode` over
the whole context. A window layer's query at cache position `p` reads the
band `(p - window, p]` and nothing behind it, so its work is counted over
the band: `shapes.paged_*` over every layer would count, on the window
layers, keys that are never read, and a share of that could pass 100%. The
dims, the layers of each kind and the window come from the run's own tables
(`attention_dims`, `attention_layers(hp, kind)`, `window`); the counters
from `rlt.serve.dispatch` (`prefill_rows`, `prefill_ctx`,
`prefill_ctx_window`, `decode_slots`, `kv_tokens`, `kv_tokens_window`) and
`rlt.serve.account` (`window_blocks_live`, `full_blocks_live`).
"""
from __future__ import annotations

from typing import List, Optional

from benchmarks.harness import program_trace as pt
from benchmarks.harness import shapes

ACCOUNT = "rlt.serve.account"
WINDOW, FULL = "window", "full"


def window_prefill(chunk: int, context_before: int, context_seen: int,
                   window: int, heads: int, kv_heads: int, head_dim: int,
                   itemsize: int = 2) -> dict:
    """One prefill chunk of `chunk` query rows on a window layer: row j, at
    cache position `context_before + j`, reads the `min(context_before + j
    + 1, window)` newest keys; the kernel reads the `context_seen` cached
    tokens the chunk's rows can see (the dispatch's `prefill_ctx_window`)
    and the chunk's own."""
    full_rows = max(0, min(chunk, context_before + chunk + 1 - window))
    ramp = chunk - full_rows             # rows that still see every token
    pairs = (ramp * context_before + ramp * (ramp + 1) / 2
             + full_rows * window)
    kv_tokens = context_seen + chunk
    return {"flops": 2 * 2 * pairs * heads * head_dim,
            "bytes": 2 * kv_tokens * kv_heads * head_dim * itemsize
                     + 2 * chunk * heads * head_dim * itemsize}


def _scaled(work: dict, layers: int) -> dict:
    return {k: layers * v for k, v in work.items()}


def _kinds(run):
    """(dims, window, layers of the window kind, layers of the full kind)
    from the run's tables, or None where they are not of two kinds."""
    model = run.model_tables()
    if not hasattr(model, "window"):
        return None
    return (model.attention_dims(run.hp), model.window(run.hp),
            model.attention_layers(run.hp, WINDOW),
            model.attention_layers(run.hp, FULL))


def window_prefill_roofline_pct(run) -> Optional[float]:
    """Over the paired ticks that carry a chunk: the full layers'
    `shapes.paged_prefill` over the context and the window layers'
    `window_prefill` over the band, against the device time of every
    `rlt_paged_prefill` event in those ticks."""
    tb = pt.tables(run)
    kinds = _kinds(run)
    if tb is None or kinds is None:
        return None
    dims, window, n_win, n_full = kinds
    pt.need_kernels(tb, ["rlt_paged_prefill"])
    seconds, stats = pt.paired_kernel_seconds(
        tb, "rlt_paged_prefill", lambda s: pt.counter(s, "prefill_rows") > 0)
    work: List[dict] = []
    for s in stats:
        rows, ctx = pt.counter(s, "prefill_rows"), pt.counter(s, "prefill_ctx")
        work.append(_scaled(shapes.paged_prefill(rows, ctx, **dims), n_full))
        work.append(_scaled(window_prefill(
            rows, ctx, pt.counter(s, "prefill_ctx_window"), window, **dims),
            n_win))
    return pt.roofline_pct(work, seconds, run.peaks)


def window_decode_roofline_pct(run) -> Optional[float]:
    """Over the paired ticks with a decoding slot: `shapes.paged_decode` of
    the contexts on the full layers and of the bands (`kv_tokens_window`)
    on the window layers, against the device time of every
    `rlt_paged_decode` event in those ticks."""
    tb = pt.tables(run)
    kinds = _kinds(run)
    if tb is None or kinds is None:
        return None
    dims, _window, n_win, n_full = kinds
    pt.need_kernels(tb, ["rlt_paged_decode"])
    seconds, stats = pt.paired_kernel_seconds(
        tb, "rlt_paged_decode", lambda s: pt.counter(s, "decode_slots") > 0)
    work: List[dict] = []
    for s in stats:
        idle = [0] * (pt.counter(s, "decode_slots") - 1)
        for name, layers in (("kv_tokens", n_full),
                             ("kv_tokens_window", n_win)):
            # `paged_decode` sums the contexts and counts the slots
            work.append(_scaled(shapes.paged_decode(
                [pt.counter(s, name)] + idle, **dims), layers))
    return pt.roofline_pct(work, seconds, run.peaks)


def window_blocks_held_share_pct(run) -> Optional[float]:
    """Over the traced ticks' `rlt.serve.account` events: the blocks the
    window layers hold for the slotted requests (`window_blocks_live`) over
    those one table for all layers would hold a layer, which is what the
    full group holds (`full_blocks_live`)."""
    tb = pt.tables(run)
    if tb is None:
        return None
    events = [e.stats for e in tb.trace.host_named(ACCOUNT)
              if "window_blocks_live" in e.stats]
    full = sum(int(s["full_blocks_live"]) for s in events)
    if not full:
        return None
    return 100.0 * sum(int(s["window_blocks_live"]) for s in events) / full
