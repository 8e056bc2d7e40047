"""Ring attention: sequence/context parallelism over the `seq` mesh axis.

Long-context machinery the reference lacks entirely (SURVEY §5.7: no
attention code at all in the reference — this is net-new capability that
the TPU rebuild treats as first-class). Design is TPU-idiomatic:

  * the sequence axis is sharded over the `seq` mesh axis; each device
    holds [B, S/n, H, D] of Q, K, V;
  * attention runs in n ring steps: every device computes blockwise
    attention of its local Q against the KV block it currently holds
    (online-softmax accumulation, flash-attention style — the S×S score
    matrix never materializes), then rotates the KV block to its ring
    neighbor with `lax.ppermute` — nearest-neighbor traffic that maps
    onto the physical ICI torus;
  * causality uses global offsets from `lax.axis_index`, so blocks
    entirely in a query's future contribute exp(-inf)=0 and the math
    stays exact (results match full attention to float tolerance);
  * compute is fully overlappable with the permute by XLA's async
    collective scheduling (the next block's matmul does not depend on
    the in-flight send).

Two entry points:
  * `ring_attention(q, k, v, mesh=...)` — standalone: wraps `shard_map`
    over the mesh (the usual "manual island inside an auto-sharded jit"
    pattern).
  * `ring_attention_local(...)` — the per-shard body, for callers already
    inside a `shard_map` of their own.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_lightning_tpu.ops.attention import repeat_kv

_NEG_INF = float("-inf")


def ring_perm(axis_size: int) -> list[tuple[int, int]]:
    """The canonical ring schedule: one single-cycle rotation, every rank
    sends to its +1 neighbor. This is schedule METADATA as much as
    implementation — tracecheck (analysis/tracecheck.py RLT303) validates
    every traced ppermute against exactly the properties this shape
    guarantees (no duplicate src/dst, full permutations form ONE cycle),
    so the ring path and the auditor cannot drift apart."""
    return [(j, (j + 1) % axis_size) for j in range(axis_size)]


def _accum_block(q, k, v, o, m, l, *, q_off, kv_off, causal, scale):
    """One online-softmax update of (o, m, l) with a KV block.

    q: [B, Sq, H, D]; k, v: [B, Skv, Hkv, D] (GQA-repeated here so the
    ring only ever ships the small KV). o: [B, H, Sq, D] f32 accumulator;
    m, l: [B, H, Sq] running max / denominator, f32.
    """
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = repeat_kv(k, rep)
        v = repeat_kv(v, rep)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_pos = jnp.arange(q.shape[1])[:, None] + q_off
        kv_pos = jnp.arange(k.shape[1])[None, :] + kv_off
        s = jnp.where(q_pos >= kv_pos, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # rows with nothing visible yet keep m=-inf; exp against a 0 stand-in
    # still yields exactly 0 contributions.
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])                     # [B,H,Sq,Skv]
    alpha = jnp.where(
        jnp.isfinite(m), jnp.exp(m - safe_m), 0.0
    )                                                      # [B,H,Sq]
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return o_new, m_new, l_new


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = "seq",
    axis_size: int,
    causal: bool = True,
    scale: Optional[float] = None,
):
    """Per-shard ring attention body (call inside shard_map).

    q, k, v: local shards [B, S_local, H(,kv), D]. Returns [B, S_local,
    H, D] in q's dtype.
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    idx = jax.lax.axis_index(axis_name)
    q_off = idx * Sq

    perm = ring_perm(axis_size)

    def body(t, carry):
        o, m, l, kb, vb = carry
        src = (idx - t) % axis_size          # original owner of (kb, vb)
        o, m, l = _accum_block(
            q, kb, vb, o, m, l,
            q_off=q_off, kv_off=src * Skv, causal=causal, scale=scale,
        )
        # rotate AFTER consuming: block t+1 arrives from the ring neighbor
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (o, m, l, kb, vb)

    o0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    m0 = jnp.full((B, H, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    # n-1 rotations suffice: the last block is consumed without a send
    # (a final ppermute whose output nobody reads would still serialize
    # the loop on ICI traffic).
    o, m, l, kb, vb = jax.lax.fori_loop(
        0, axis_size - 1, body, (o0, m0, l0, k, v)
    )
    src_last = (idx - (axis_size - 1)) % axis_size
    o, _, l = _accum_block(
        q, kb, vb, o, m, l,
        q_off=q_off, kv_off=src_last * Skv, causal=causal, scale=scale,
    )
    out = jnp.where(l[..., None] > 0, o / jnp.maximum(l[..., None], 1e-30), 0.0)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)      # [B, Sq, H, D]


def seq_island(local_fn, mesh: Mesh, axis_name: str = "seq", **kwargs):
    """Shared shard_map wrapper for sequence-parallel attention islands
    ([B, S, H, D] tensors: batch over the data axes, sequence over
    `axis_name`, heads over `tensor`). Used by both the ring and the
    ulysses (ops/ulysses.py) modes so they cannot disagree on layout."""
    bspec = tuple(ax for ax in ("data", "fsdp", "expert")
                  if ax in mesh.shape)
    head_ax = "tensor" if "tensor" in mesh.shape else None
    spec = P(bspec if bspec else None, axis_name, head_ax, None)
    return jax.shard_map(
        partial(local_fn, axis_name=axis_name,
                axis_size=mesh.shape[axis_name], **kwargs),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,  # collective-permute varying-axes opt-out
    )


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    axis_name: str = "seq",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel attention over `mesh`'s `axis_name` axis.

    Global [B, S, H, D] in/out; batch rides the data-parallel axes, heads
    ride `tensor`, sequence is split over `axis_name`. With axis size 1
    this degrades to plain blockwise attention on every device.
    """
    fn = seq_island(ring_attention_local, mesh, axis_name,
                    causal=causal, scale=scale)
    return fn(q, k, v)
