"""Operations and bytes of the latent-attention kernels and of the expert
product, from their shapes: what the algorithm needs, whatever the program
does; and the reductions of the per-layer metrics that read them. Kept with
the benchmark so that no PR that claims a gain can change them. Every
roofline here ends in `shapes.roofline_seconds` (through
`program_trace.roofline_pct`).

Latent attention over the cache is multi-query with `heads` query heads a
token: a score contracts over `score_dim` columns of the cached row (latent
and rope), the value is the row's first `value_dim` columns. The dims come
from the model's tables (`mla_dims`, `expert_dims`).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmarks.harness import program_trace as pt

ACCOUNT = "rlt.serve.account"


def mla_decode(kv_tokens: int, slots: int, heads: int, score_dim: int,
               value_dim: int, head_out: int = 0, itemsize: int = 2) -> dict:
    """One decode step: `slots` queries of `heads` heads read `kv_tokens`
    cached rows in all, each row once; the queries come in at `score_dim`
    and the outputs leave at `value_dim` a head."""
    return {"flops": 2.0 * kv_tokens * heads * (score_dim + value_dim),
            "bytes": kv_tokens * score_dim * itemsize
                     + slots * heads * (score_dim + value_dim) * itemsize}


def mla_prefill(rows: int, context_before: int, heads: int, score_dim: int,
                value_dim: int, head_out: int, itemsize: int = 2) -> dict:
    """A chunk of `rows` query rows on `context_before` cached tokens plus
    its own causal triangle. FLOPs: the fewer of the absorbed form (scores
    and values over the latent row) and the expanded one (per-head keys and
    values of `head_out` columns made from the `context_before + rows`
    latent rows, then scores over nope + rope and values over v)."""
    pairs = rows * context_before + rows * (rows + 1) / 2
    tokens = context_before + rows
    rope = score_dim - value_dim
    absorbed = 2.0 * pairs * heads * (score_dim + value_dim)
    expanded = (2.0 * pairs * heads * (head_out + rope)
                + 2.0 * tokens * value_dim * heads * head_out)
    return {"flops": min(absorbed, expanded),
            "bytes": tokens * score_dim * itemsize
                     + rows * heads * (score_dim + value_dim) * itemsize}


def moe_experts(expert_rows: int, layers: int, hidden: int, width: int,
                held: int, itemsize: int = 2) -> dict:
    """A tick's expert products: `expert_rows` rows (summed over the expert
    layers) through gate, up and down; the held experts' weights read once
    a layer, the rows read and written once."""
    return {"flops": expert_rows * 3.0 * hidden * width * 2,
            "bytes": layers * held * 3 * hidden * width * itemsize
                     + expert_rows * 2 * hidden * itemsize}


# ---- reductions ---------------------------------------------------------------


def _layered(work: dict, layers: int) -> dict:
    return {k: layers * v for k, v in work.items()}


def mla_decode_roofline_pct(run) -> Optional[float]:
    tb = pt.tables(run)
    if tb is None:
        return None
    pt.need_kernels(tb, ["rlt_mla_decode"])
    seconds, stats = pt.paired_kernel_seconds(
        tb, "rlt_mla_decode", lambda s: pt.counter(s, "decode_slots") > 0)
    model = run.model_tables()
    dims, layers = model.mla_dims(run.hp), model.attention_layers(run.hp)
    work = [_layered(mla_decode(pt.counter(s, "kv_tokens"),
                                pt.counter(s, "decode_slots"), **dims),
                     layers) for s in stats]
    return pt.roofline_pct(work, seconds, run.peaks)


def mla_prefill_roofline_pct(run) -> Optional[float]:
    """The kernel's time plus the ops under the scope `mla_expand` (a
    program that expands cached rows outside the kernel opens it), so that
    moving work out of the kernel cannot raise the share."""
    tb = pt.tables(run)
    if tb is None:
        return None
    pt.need_kernels(tb, ["rlt_mla_prefill"])
    keep = lambda s: pt.counter(s, "prefill_rows") > 0
    seconds, stats = pt.paired_kernel_seconds(tb, "rlt_mla_prefill", keep)
    runs = [r for r, ev in tb.pairs if keep(ev.stats)]
    seconds += sum(op.end - op.start for op in pt.within(
        [op for op in tb.step_ops[0] if op.scope == "mla_expand"], runs))
    model = run.model_tables()
    dims, layers = model.mla_dims(run.hp), model.attention_layers(run.hp)
    work = [_layered(mla_prefill(pt.counter(s, "prefill_rows"),
                                 pt.counter(s, "prefill_ctx"), **dims),
                     layers) for s in stats]
    return pt.roofline_pct(work, seconds, run.peaks)


def paired_accounts(tb) -> List[Tuple[tuple, Dict[str, object]]]:
    """Each paired execution of the step with the stats of the first
    `rlt.serve.account` event that begins after its dispatch ended, on the
    dispatch's thread: the tick's device-side counts, fetched with its
    tokens."""
    accounts = sorted(tb.trace.host_named(ACCOUNT), key=lambda e: e.start)
    out, j = [], 0
    for run, ev in tb.pairs:
        while j < len(accounts) and (accounts[j].start < ev.end
                                     or accounts[j].thread != ev.thread):
            j += 1
        if j < len(accounts):
            out.append((run, accounts[j].stats))
            j += 1
    return out


def moe_experts_roofline_pct(run) -> Optional[float]:
    """The ticks' expert work over the self time of the ops under the scope
    `moe_experts` inside the same executions."""
    tb = pt.tables(run)
    if tb is None:
        return None
    paired = [(r, s) for r, s in paired_accounts(tb) if "expert_rows" in s]
    if not paired:
        return None
    inside = pt.within(tb.step_ops[0], [r for r, _ in paired])
    seconds = pt.scope_self_seconds(inside).get("moe_experts", 0.0)
    model = run.model_tables()
    dims, layers = model.expert_dims(run.hp), model.expert_layers(run.hp)
    work = [moe_experts(int(s["expert_rows"]), layers, **dims)
            for _, s in paired]
    return pt.roofline_pct(work, seconds, run.peaks)
