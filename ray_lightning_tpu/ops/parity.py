"""Parity of every hand-written kernel against its XLA reference, IN THE
EXECUTION ENVIRONMENT: compiled by Mosaic on a TPU, interpreted
elsewhere. The interpret-mode unit tests prove the kernel logic; only
this proves what the chip computes. Shared by `chip_smoke.py` (where a
miss is fatal) and bench.py's `kernels_verified` field.

Small shapes: a correctness gate, not a timing. Every error is
scale-relative (max |got - want| over max(|want|, 1)) and checked
against one tolerance sized for two f32-accumulated MXU paths that
differ only in tiling and reduction order at bf16 operand precision —
the bound the bf16 interpret-mode tests use.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

#: scale-relative error bound for every check below
TOLERANCE = 2e-2


@dataclasses.dataclass(frozen=True)
class PagedGeometry:
    """The serving shapes the paged kernels are checked at: an engine's
    slot count, head layout, pool block geometry and prefill chunk."""

    capacity: int = 4
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 64
    block_size: int = 8
    blocks_per_slot: int = 4
    prefill_chunk: int = 16


def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    scale = max(float(jnp.abs(want).max()), 1.0)
    return float(jnp.abs(got - want).max()) / scale


def kernel_parity_errors(
        paged: PagedGeometry = PagedGeometry()) -> Dict[str, float]:
    """name -> scale-relative error of each kernel against its reference:
    flash forward and backward, the fused chunked CE (remat and inline
    backward; loss and grads), and the two paged serving kernels at
    ``paged`` with ragged lengths and a non-zero left pad."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops import dispatch
    from ray_lightning_tpu.ops.attention import (
        dot_product_attention,
        paged_attention_reference,
        paged_prefill_reference,
    )
    from ray_lightning_tpu.ops.fused_ce import fused_cross_entropy
    from ray_lightning_tpu.ops.pallas.flash import flash_attention_pallas
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas,
    )
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_pallas,
    )

    rng = np.random.default_rng(7)
    if dispatch.on_tpu():
        # on the real chip: the PRODUCTION tile path — flagship head_dim,
        # tuned default blocks, and the production S=2048 so there are
        # >= 2 KV tiles (the cross-tile online-softmax rescaling only
        # runs with multiple KV blocks — a single-tile shape would pass
        # the gate even with that path broken). Cheap on the MXU.
        B, S, H, Hk, D = 2, 2048, 4, 2, 128
        block_q, block_k = None, None  # tuned defaults (512/1024)
    else:
        # interpret mode: same kernel code, sized to stay fast
        B, S, H, Hk, D = 2, 256, 4, 2, 64
        block_q, block_k = 128, 128
    q = jnp.asarray(rng.standard_normal((B, S, H, D), dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, Hk, D), dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, Hk, D), dtype=np.float32))

    errors: Dict[str, float] = {}

    # flash forward (GQA shape, causal — the model's configuration)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention_pallas(q, k, v, causal=True,
                                 block_q=block_q, block_k=block_k)
    errors["flash_fwd"] = _rel_err(out, ref)

    # flash backward: grads of the same scalar through both paths
    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention_pallas(
            q, k, v, causal=True, block_q=block_q,
            block_k=block_k) ** 2).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    errors["flash_bwd"] = max(_rel_err(b, a) for a, b in zip(gr, gf))

    # fused chunked CE vs materialized logits (loss AND grads)
    Dm, V, T = 128, 1024, B * S
    hidden = jnp.asarray(
        rng.standard_normal((B, S, Dm), dtype=np.float32))
    w = jnp.asarray(
        (rng.standard_normal((Dm, V)) * Dm ** -0.5).astype(np.float32))
    targets = jnp.asarray(rng.integers(0, V, (B, S)).astype(np.int32))

    def ce_ref(hidden, w):
        x = hidden.reshape(T, Dm).astype(jnp.bfloat16)
        logits = jnp.dot(x, w.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, targets.reshape(T)[:, None], axis=-1)[:, 0]
        return (lse - tgt).mean()

    def ce_fused(hidden, w):
        return fused_cross_entropy(hidden, w, targets, chunk_tokens=128)

    def ce_inline(hidden, w):
        return fused_cross_entropy(hidden, w, targets, chunk_tokens=128,
                                   inline_backward=True)

    (l_ref, g_ref) = jax.value_and_grad(ce_ref, argnums=(0, 1))(hidden, w)
    (l_fus, g_fus) = jax.value_and_grad(ce_fused, argnums=(0, 1))(hidden, w)
    (l_inl, g_inl) = jax.value_and_grad(ce_inline, argnums=(0, 1))(hidden, w)
    errors["fused_ce_loss"] = abs(float(l_fus) - float(l_ref))
    errors["fused_ce_grad"] = max(
        _rel_err(b, a) for a, b in zip(g_ref, g_fus))
    errors["inline_ce_loss"] = abs(float(l_inl) - float(l_ref))
    errors["inline_ce_grad"] = max(
        _rel_err(b, a) for a, b in zip(g_ref, g_inl))

    # the paged serving pair, bf16 like the pool they serve from: random
    # tables over a shared pool (block 0 = scratch), ragged lengths, a
    # non-zero left pad on every other row
    g = paged
    C, M, P, CH = (g.capacity, g.blocks_per_slot, g.block_size,
                   g.prefill_chunk)
    n_blocks = 1 + C * M
    dt = jnp.bfloat16
    pool_k = jnp.asarray(rng.standard_normal(
        (n_blocks, P, g.n_kv_heads, g.head_dim), dtype=np.float32), dt)
    pool_v = jnp.asarray(rng.standard_normal(
        (n_blocks, P, g.n_kv_heads, g.head_dim), dtype=np.float32), dt)
    tables = jnp.asarray(
        rng.integers(1, n_blocks, (C, M)).astype(np.int32))
    pad = jnp.asarray((np.arange(C) % 2) * 3, jnp.int32)

    q1 = jnp.asarray(rng.standard_normal(
        (C, g.n_heads, g.head_dim), dtype=np.float32), dt)
    lengths = jnp.asarray(
        rng.integers(4, M * P + 1, (C,)).astype(np.int32))
    errors["paged_decode"] = _rel_err(
        paged_attention_pallas(q1, pool_k, pool_v, tables, lengths,
                               pad=pad),
        paged_attention_reference(q1, pool_k, pool_v, tables, lengths,
                                  pad=pad))

    rows = min(C, 2)
    qc = jnp.asarray(rng.standard_normal(
        (rows, CH, g.n_heads, g.head_dim), dtype=np.float32), dt)
    pos = M * P - CH  # the last chunk of a full slot: every tile live
    errors["paged_prefill"] = _rel_err(
        paged_prefill_pallas(qc, pool_k, pool_v, tables[:rows], pos,
                             pad=pad[:rows]),
        paged_prefill_reference(qc, pool_k, pool_v, tables[:rows], pos,
                                pad=pad[:rows]))
    return errors


@dataclasses.dataclass(frozen=True)
class LatentGeometry:
    """The serving shapes the latent-attention kernels and the expert
    product are checked at. The defaults are the published dims of the
    DeepSeek-V3 family (128 heads, a 576-value row stored at 640, value
    512; experts 7168 x 2048) at the benchmark cell's engine shape; off
    the TPU pass a small one."""

    capacity: int = 8
    n_heads: int = 128
    row_dim: int = 640
    value_dim: int = 512
    block_size: int = 128
    blocks_per_slot: int = 16
    prefill_chunk: int = 1024
    layers: int = 2
    experts: int = 16
    hidden: int = 7168
    width: int = 2048
    rows: int = 512


def latent_parity_errors(
        g: LatentGeometry = LatentGeometry()) -> Dict[str, float]:
    """name -> scale-relative error of `rlt_mla_decode`, `rlt_mla_prefill`
    (ragged lengths, a stacked pool read at a traced layer) and the
    grouped product (uneven groups, one empty, rows of no group) against
    their `jax.numpy` twins, in the execution environment."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.attention import (
        mla_decode_reference,
        mla_prefill_reference,
    )
    from ray_lightning_tpu.ops.grouped_matmul import grouped_matmul
    from ray_lightning_tpu.ops.pallas.mla_attention import (
        mla_decode_pallas,
        mla_prefill_pallas,
    )

    rng = np.random.default_rng(11)
    dt = jnp.bfloat16
    C, M, P = g.capacity, g.blocks_per_slot, g.block_size
    n_blocks = 1 + C * M
    pool = jnp.asarray(rng.standard_normal(
        (g.layers, n_blocks, P, g.row_dim), dtype=np.float32), dt)
    tables = jnp.asarray(rng.integers(1, n_blocks, (C, M)).astype(np.int32))
    scale = 0.1
    layer = jnp.int32(g.layers - 1)
    errors: Dict[str, float] = {}

    q1 = jnp.asarray(rng.standard_normal(
        (C, g.n_heads, g.row_dim), dtype=np.float32), dt)
    lengths = jnp.asarray(rng.integers(1, M * P + 1, (C,)).astype(np.int32))
    errors["mla_decode"] = _rel_err(
        jax.jit(lambda *a: mla_decode_pallas(
            *a[:4], g.value_dim, scale, layer=a[4]))(
                q1, pool, tables, lengths, layer),
        jax.jit(lambda *a: mla_decode_reference(
            *a[:4], g.value_dim, scale, layer=a[4]))(
                q1, pool, tables, lengths, layer))

    qc = jnp.asarray(rng.standard_normal(
        (1, g.prefill_chunk, g.n_heads, g.row_dim), dtype=np.float32), dt)
    for name, pos in (("mla_prefill_first", 0),
                      ("mla_prefill_last", M * P - g.prefill_chunk)):
        args = (qc, pool, tables[:1], jnp.int32(pos), layer)
        errors[name] = _rel_err(
            jax.jit(lambda *a: mla_prefill_pallas(
                *a[:4], g.value_dim, scale, layer=a[4]))(*args),
            jax.jit(lambda *a: mla_prefill_reference(
                *a[:4], g.value_dim, scale, layer=a[4]))(*args))

    lhs = jnp.asarray(rng.standard_normal(
        (g.rows, g.hidden), dtype=np.float32), dt)
    rhs = jnp.asarray(rng.standard_normal(
        (g.experts, g.hidden, g.width), dtype=np.float32)
        * g.hidden ** -0.5, dt)
    sizes = rng.multinomial(g.rows * 3 // 4,
                            np.ones(g.experts - 1) / (g.experts - 1))
    sizes = jnp.asarray(np.concatenate([[0], sizes]).astype(np.int32))
    errors["grouped_matmul"] = _rel_err(
        jax.jit(lambda *a: grouped_matmul(*a, use_pallas=True))(
            lhs, rhs, sizes),
        jax.jit(lambda *a: grouped_matmul(*a, use_pallas=False))(
            lhs, rhs, sizes))
    return errors


if __name__ == "__main__":
    import json

    errs = latent_parity_errors()
    print(json.dumps({"latent_parity": errs, "tolerance": TOLERANCE,
                      "ok": all(e <= TOLERANCE for e in errs.values())}))
