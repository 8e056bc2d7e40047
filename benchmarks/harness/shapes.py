"""Operations and bytes from shapes: what the algorithm needs, whatever the
program does. Kept with the benchmark so that no PR that claims a gain can
change them.

`train_flops_per_token` is copied from `bench.py:_flops_per_token`.
"""
from __future__ import annotations


def matmul_params(hp: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    projection of every layer and the output head (the embedding is a
    gather)."""
    d, hd = hp["hidden_size"], hp["head_dim"]
    h, kv, f = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["intermediate_size"])
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f
    return hp["num_hidden_layers"] * per_layer + d * hp["vocab_size"]


def train_flops_per_token(hp: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 x matmul parameters plus causal
    attention (QK^T and AV at an average context of S/2; forward x2,
    backward x4). Recomputed operations are not counted."""
    attn = (6 * hp["num_hidden_layers"] * hp["num_attention_heads"]
            * hp["head_dim"] * seq)
    return 6.0 * matmul_params(hp) + attn


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
