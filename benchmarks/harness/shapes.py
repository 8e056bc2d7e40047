"""Operations and bytes of one kernel call from its shapes: what the
algorithm needs, whatever the program does. Kept with the benchmark so that
no PR that claims a gain can change them.

What depends on an architecture (its matmul parameters and FLOPs a token,
the dims and the number of its attention layers) is in
`benchmarks/tables/<model>.py`. A new kernel's work function goes into a new
file (its reader's, or a new one beside this) and ends in
`roofline_seconds`.
"""
from __future__ import annotations


def flash_fwd_bwd(batch: int, seq: int, heads: int, kv_heads: int,
                  head_dim: int, itemsize: int = 2) -> dict:
    """Causal flash attention, forward and backward, of `batch` sequences.
    FLOPs: forward QK^T and PV (2 products of 2*S*S*hd, halved by the mask),
    backward the five products dV, dP, dS->dQ, dS->dK and the recomputed QK^T
    (2.5 x the forward). Bytes: q, k, v, o read or written once forward; q,
    k, v, o, do read and dq, dk, dv written backward."""
    fwd = 2 * 2 * batch * heads * seq * seq * head_dim / 2
    q_bytes = batch * seq * heads * head_dim * itemsize
    kv_bytes = batch * seq * kv_heads * head_dim * itemsize
    return {"flops": 3.5 * fwd,
            "bytes": (2 * q_bytes + 2 * kv_bytes)
                     + (4 * q_bytes + 4 * kv_bytes)}


def paged_decode(context_lens, heads: int, kv_heads: int, head_dim: int,
                 itemsize: int = 2) -> dict:
    """One decode step of paged attention: each slot's query reads its whole
    context's K and V once. `context_lens` is the tokens in each decoding
    slot's cache."""
    tokens = float(sum(context_lens))
    return {"flops": 2 * 2 * tokens * heads * head_dim,
            "bytes": 2 * tokens * kv_heads * head_dim * itemsize
                     + 2 * len(context_lens) * heads * head_dim * itemsize}


def paged_prefill(chunk: int, context_before: int, heads: int, kv_heads: int,
                  head_dim: int, itemsize: int = 2) -> dict:
    """One prefill chunk of `chunk` query rows against `context_before`
    cached tokens plus its own causal triangle."""
    pairs = chunk * context_before + chunk * (chunk + 1) / 2
    kv_tokens = context_before + chunk
    return {"flops": 2 * 2 * pairs * heads * head_dim,
            "bytes": 2 * kv_tokens * kv_heads * head_dim * itemsize
                     + 2 * chunk * heads * head_dim * itemsize}


def roofline_seconds(work: dict, peaks: dict) -> dict:
    """The least time the chip could take, and which bound sets it."""
    by_flops = work["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
