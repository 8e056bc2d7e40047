"""Fused paged-attention decode kernel (pallas TPU).

The serving engine's reference decode lane feeds the model's cache path
from a dense per-slot gathered view of the block-paged KV pool —
`[L, C, gathered_len, Hkv, hd]` of HBM and a full pool read+write of
traffic every tick, charged honestly by `serve/audit.py`. This kernel
retires that copy: decode attention consumes the pool **directly
through the per-slot block tables**.

Schedule (one layer's pool, all slots):

    q       [C, H, hd]           one query token per slot
    pool_k  [n_blocks, P, Hkv, hd]  the shared block pool (k; v alike),
                                 or the stack [L, n_blocks, P, Hkv, hd]
                                 with a layer index (`stack_as_pool`)
    tables  [C, M] int32         slot -> pool block ids (0 = scratch)
    lengths [C] int32            valid cache positions per slot
    pad     [C] int32            left-pad columns to mask (ragged
                                 batched prefill; 0 = none)

grid = (C, M): for slot c the kernel streams that slot's M table-named
KV tiles through VMEM — the BlockSpec index_map reads the
scalar-prefetched table (`pltpu.PrefetchScalarGridSpec`), so the DMA
engine fetches pool block `tables[c, m]` while compute runs, and no
gathered copy ever exists in HBM. Per tile: one [H, P] score panel,
online-softmax statistics (running max / sum / accumulator in f32 VMEM
scratch, exactly the flash-attention discipline of
`ops/pallas/flash.py`), masked by `pad <= kv_pos < length` BEFORE the
max so scratch-block garbage (block 0, and table tails past a slot's
length) contributes exactly zero. Tiles entirely past `length` are
skipped (predicated body). GQA reads KV heads in place via the
`h // (H // Hkv)` head map — no repeat, no extra traffic.

Inference-only: decode has no backward, so there is no VJP — the
XLA reference path with identical semantics lives in
`ops.attention.paged_attention_reference`, and dispatch follows the
flash discipline (`ops.dispatch.use_pallas`, interpret mode off-TPU,
`ops.attention.paged_attention_uses_pallas` as the single predicate).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.dispatch import interpret_mode as _interpret

_NEG_INF = -1e30  # never true -inf: exp(-inf - -inf) = nan on empty rows


def paged_shapes_supported(q_shape, pool_shape) -> bool:
    """Would the kernel accept these shapes on a real TPU?

    q [C, H, hd], pool [n_blocks, P, Hkv, hd] (or the stack, with a
    leading layer axis: the last four dims are judged): the head dim
    must be lane-aligned (128, or 64 which still tiles acceptably — same rule
    as flash), the pool block must be sublane-aligned (P % 8), and the
    GQA ratio must be whole. Callers that must know the dispatch
    outcome use `ops.attention.paged_attention_uses_pallas`, never this
    directly — one predicate, no drift."""
    if len(q_shape) != 3 or len(pool_shape) not in (4, 5):
        return False
    _, h, hd = q_shape
    _, p, hkv, hd2 = pool_shape[-4:]
    if hd != hd2:
        return False
    if hd % 128 != 0 and hd not in (64,):
        return False
    if hkv < 1 or h % hkv != 0:
        return False
    if p % 8 != 0:
        return False
    return True


def stack_as_pool(pool_k, pool_v, tables, layer):
    """Serve one layer of the stacked pool ``[L, n_blocks, P, Hkv, hd]``
    WITHOUT taking it out of the stack: the stack is relabelled as one
    long pool ``[L * n_blocks, P, Hkv, hd]`` (the tiled dims are the
    last two, so the reshape moves nothing) and the layer is folded into
    the block ids, ``tables + layer * n_blocks`` (a [rows, M] int32
    add). The kernel then reads block ``tables[c, m]`` of ``layer``
    through the index_map it always had; a slot's scratch block 0 is
    the layer's own block 0. A 4-D pool passes through (``layer``
    must then be 0)."""
    if pool_k.ndim == 4:
        return pool_k, pool_v, tables
    n_layers, n_blocks = pool_k.shape[:2]
    flat = (n_layers * n_blocks, *pool_k.shape[2:])
    return (pool_k.reshape(flat), pool_v.reshape(flat),
            tables + jnp.asarray(layer, tables.dtype) * n_blocks)


def _decode_kernel(tbl_ref, len_ref, pad_ref, q_ref, k_ref, v_ref, o_ref,
                   acc, m_scr, l_scr, *, scale, block_p, num_kv_blocks,
                   n_rep):
    """One (slot, kv-tile) grid step. Scratch persists across the
    innermost tile axis (the flash forward's accumulation contract)."""
    c = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    length = len_ref[c]
    kv_start = m * block_p

    # tiles entirely past the slot's length (or entirely under its
    # left pad) hold nothing visible — skip the DMA'd tile's compute
    # (its garbage never reaches the stats)
    @pl.when((kv_start < length) & (kv_start + block_p > pad_ref[c]))
    def _body():
        q = q_ref[0].astype(jnp.float32)       # [H, hd]
        k = k_ref[0].astype(jnp.float32)       # [P, Hkv, hd]
        v = v_ref[0].astype(jnp.float32)
        h, hd = q.shape
        hkv = k.shape[1]
        # GQA head map: query head g*n_rep + r reads kv head g — group
        # the q heads and batch the contraction over kv heads, so KV
        # tiles are consumed in place (no repeat)
        qg = q.reshape(hkv, n_rep, hd)
        kg = k.transpose(1, 0, 2)              # [Hkv, P, hd]
        vg = v.transpose(1, 0, 2)
        s = (jax.lax.dot_general(
            qg, kg, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale).reshape(h, block_p)         # [Hkv, n_rep, P] -> [H, P]
        # the mask is built in the stats layout [H, P] directly: Mosaic
        # cannot reshape an i1 vector across the degenerate n_rep == 1
        # axis (MHA; LLO_CHECK `vmand ... ProducesVreg` on v5e)
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        visible = (kv_pos < length) & (kv_pos >= pad_ref[c])
        s = jnp.where(visible, s, _NEG_INF)
        m_prev = m_scr[:, 0]                   # [H]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # masked positions are zeroed EXPLICITLY, not only through the
        # exp: a fully-masked tile (every position below the slot's
        # pad) has s == m_new == _NEG_INF and exp(s - m_new) == 1 —
        # the sentinel-minus-sentinel trap would weight garbage at
        # full probability
        p = jnp.where(visible, jnp.exp(s - m_new[:, None]), 0.0)  # [H, P]
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = corr * l_scr[:, 0] + jnp.sum(p, axis=1)
        av = jax.lax.dot_general(
            p.reshape(hkv, n_rep, block_p), vg,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                      # [Hkv, n_rep, hd]
        acc[:] = corr[:, None] * acc[:] + av.reshape(h, hd)
        m_scr[:, 0] = m_new

    @pl.when(m == num_kv_blocks - 1)
    def _finish():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)   # fully-masked slot -> 0s
        o_ref[0] = (acc[:] / safe_l[:, None]).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    pad: jnp.ndarray | None = None,
    scale: float | None = None,
    layer=0,
) -> jnp.ndarray:
    """Decode attention over the paged pool: [C, H, hd] out.

    ``pool_k`` / ``pool_v`` are one layer's pool, or the stacked pool
    ``[L, n_blocks, P, Hkv, hd]`` read at ``layer`` (python int or
    traced scalar; `stack_as_pool`).
    ``tables`` names each slot's pool blocks (block 0 = reserved
    scratch — readable garbage, always masked by ``lengths``/``pad``);
    ``lengths[c]`` is the number of valid cache positions (including
    the just-written query token); ``pad[c]`` masks a left-padded
    slot's pad columns (positions < pad never attend)."""
    c, h, hd = q.shape
    pool_k, pool_v, tables = stack_as_pool(pool_k, pool_v, tables, layer)
    n_blocks, p, hkv, _ = pool_k.shape
    m = tables.shape[1]
    n_rep = h // hkv
    scale = scale if scale is not None else hd ** -0.5
    if pad is None:
        pad = jnp.zeros_like(lengths)
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_p=p, num_kv_blocks=m,
        n_rep=n_rep)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tables, lengths, pad
        grid=(c, m),
        in_specs=[
            pl.BlockSpec((1, h, hd),
                         lambda ci, mi, tbl, ln, pd: (ci, 0, 0)),
            # the paged trick: the KV tile for (slot, m) is whichever
            # pool block the scalar-prefetched table names — the tile
            # streams HBM -> VMEM with no intermediate gathered copy
            pl.BlockSpec((1, p, hkv, hd),
                         lambda ci, mi, tbl, ln, pd:
                         (tbl[ci, mi], 0, 0, 0)),
            pl.BlockSpec((1, p, hkv, hd),
                         lambda ci, mi, tbl, ln, pd:
                         (tbl[ci, mi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd),
                               lambda ci, mi, tbl, ln, pd: (ci, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, hd), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, h, hd), q.dtype),
        name="rlt_paged_decode",
        interpret=_interpret(),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      pad.astype(jnp.int32), q, pool_k, pool_v)
