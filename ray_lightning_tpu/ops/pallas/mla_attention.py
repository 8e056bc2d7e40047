"""Latent-attention (MLA) kernels over the latent paged pool (pallas TPU).

Multi-head latent attention caches ONE row a token a layer,
``[c_kv (normalised) | k_r (rotated)]``, and in its absorbed form every
query head attends to that same row: scores are over the whole row
(``score_dim`` = latent + rope columns), the value is the row's first
``value_dim`` columns. So attention over the cache is multi-query with
many heads a token: the K tile is shared by all of them and the matmul's
wide side is ``heads x query tokens``, its narrow side the tile's tokens.

    q       [B, R, H, Dk]        R query tokens a row of the batch
                                 (decode: R = 1 a slot; prefill: a chunk)
    pool    [n_blocks, P, Dk]    the latent pool, or the stack
                                 [L, n_blocks, P, Dk] with a layer index
    tables  [B, M] int32         row -> pool block ids (0 = scratch)
    pos     [B] int32            cache position of each row's first query
                                 (query j sees kv_pos <= pos + j)

grid = (B, R / bq, M / tile_blocks). One grid step reads a TILE of
``tile_blocks`` table-named pool blocks: the pool is handed to the call
``tile_blocks`` times, each copy with a BlockSpec whose index_map reads
another entry of the scalar-prefetched table, and the kernel joins the
blocks into one ``[tile_blocks * P, Dk]`` K tile in VMEM; a grid step is
a matmul of ``[bq * H, Dk] x [Dk, tile]``. Blocks past the last position a query tile
can see are clamped to that last block in the index_map, so the pipeline
fetches nothing new for them, and their compute is skipped.

Per tile: bfloat16 operands, float32 scores, online softmax (running max,
sum and accumulator in float32 VMEM scratch, the `ops/pallas/flash.py`
discipline), causal mask applied before the max with masked
probabilities zeroed explicitly, probabilities rounded to the pool's
dtype for the value product.

Inference only (no VJP). The `jax.numpy` twins with the same semantics
are `ops.attention.mla_decode_reference` / `mla_prefill_reference`;
dispatch is `ops.attention.mla_decode` / `mla_prefill`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.dispatch import interpret_mode as _interpret

_NEG_INF = -1e30  # never true -inf: exp(-inf - -inf) = nan on empty rows

#: tokens a K tile aims at: the matmul's narrow side
_TILE_TOKENS = 512
#: rows (query tokens x heads) a query tile aims at
_Q_ROWS = 1024
#: scoped VMEM the kernels ask for (v5e has 128 MiB; the default scope
#: of 16 MiB does not hold a [1024, 512] float32 score panel, its
#: probabilities and the accumulator beside the double-buffered tiles)
_VMEM_LIMIT = 64 * 1024 * 1024


def mla_shapes_supported(q_shape, pool_shape, value_dim: int) -> bool:
    """Would the kernels accept these shapes on a real TPU? q
    ``[..., H, Dk]``, pool ``[..., n_blocks, P, Dk]``: the value columns
    and the row must be whole 128-lane tiles, the block sublane-aligned for a 16-bit pool (P % 16) and the
    heads a whole number of sublane tiles (H % 8). Callers that must know
    the dispatch outcome use `ops.attention.mla_uses_pallas`."""
    if len(q_shape) not in (3, 4) or len(pool_shape) not in (3, 4):
        return False
    h, dk = q_shape[-2:]
    p, dk2 = pool_shape[-2:]
    if dk != dk2 or not 0 < value_dim < dk:
        return False
    if dk % 128 or value_dim % 128:
        # a row narrower than a whole number of 128-lane tiles is padded
        # in HBM anyway, and XLA then gives the pool a layout the kernel
        # cannot read without a copy (`MlaMoeConfig.pool_row_dim`)
        return False
    return p % 16 == 0 and h % 8 == 0


def stack_as_latent_pool(pool, tables, layer):
    """One layer of the stacked pool ``[L, n_blocks, P, Dk]`` without
    taking it out of the stack: relabel the stack as one long pool and
    fold the layer into the block ids (`paged_attention.stack_as_pool`,
    for one leaf)."""
    if pool.ndim == 3:
        return pool, tables
    n_layers, n_blocks = pool.shape[:2]
    return (pool.reshape(n_layers * n_blocks, *pool.shape[2:]),
            tables + jnp.asarray(layer, tables.dtype) * n_blocks)


def _fit(total: int, want: int) -> int:
    """The largest divisor of ``total`` that is <= ``want`` (>= 1)."""
    b = max(1, min(total, want))
    while total % b:
        b -= 1
    return b


def _mla_kernel(tbl_ref, pos_ref, q_ref, *rest, scale, block_p,
                tile_blocks, block_q, n_heads, value_dim):
    """One (row, query tile, kv tile) grid step; scratch persists across
    the innermost kv-tile axis."""
    k_refs = rest[:tile_blocks]
    o_ref, acc, m_scr, l_scr = rest[tile_blocks:]
    b = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    tile = tile_blocks * block_p
    q_start = pos_ref[b] + pl.program_id(1) * block_q
    kv_start = t * tile

    # a tile past the tile's last query position holds nothing any of its
    # queries may see: skip its compute (its blocks were not fetched)
    @pl.when(kv_start <= q_start + block_q - 1)
    def _body():
        q = q_ref[0]                                  # [bq * H, Dk]
        if tile_blocks == 1:
            k = k_refs[0][0]
        else:
            k = jnp.concatenate([r[0] for r in k_refs], axis=0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [rows, tile]
        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // n_heads
        visible = kv_pos <= q_pos
        s = jnp.where(visible, s, _NEG_INF)
        m_prev = m_scr[...]                           # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # zeroed explicitly: a fully masked panel has s == m_new and
        # exp(0) == 1 would weight garbage at full probability
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(k.dtype), k[:, :value_dim], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [rows, Dv]
        acc[...] = corr * acc[...] + pv
        m_scr[...] = m_new

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _mla_call(name, q, pool, tables, pos, value_dim, scale, layer,
              tile_blocks, block_q):
    """q [B, R, H, Dk] -> [B, R, H, value_dim] (see the module's text)."""
    b, r, h, dk = q.shape
    pool, tables = stack_as_latent_pool(pool, tables, layer)
    p = pool.shape[1]
    m = tables.shape[1]
    tb = _fit(m, tile_blocks if tile_blocks else max(1, _TILE_TOKENS // p))
    bq = _fit(r, block_q if block_q else max(1, _Q_ROWS // h))
    kernel = functools.partial(
        _mla_kernel, scale=scale, block_p=p, tile_blocks=tb, block_q=bq,
        n_heads=h, value_dim=value_dim)

    def k_spec(j):
        def index(bi, qi, ti, tbl, ps):
            # the last block any query of this tile can see; later ones
            # repeat it, which the pipeline does not fetch again
            last = jnp.minimum((ps[bi] + (qi + 1) * bq - 1) // p, m - 1)
            return tbl[bi, jnp.minimum(ti * tb + j, last)], 0, 0

        return pl.BlockSpec((1, p, dk), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # tables, pos
        grid=(b, r // bq, m // tb),
        in_specs=[pl.BlockSpec((1, bq * h, dk),
                               lambda bi, qi, ti, tbl, ps: (bi, qi, 0))]
        + [k_spec(j) for j in range(tb)],
        out_specs=pl.BlockSpec((1, bq * h, value_dim),
                               lambda bi, qi, ti, tbl, ps: (bi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq * h, value_dim), jnp.float32),
            pltpu.VMEM((bq * h, 1), jnp.float32),
            pltpu.VMEM((bq * h, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, r * h, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=name,
        interpret=_interpret(),
    )(tables.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(b, r * h, dk), *([pool] * tb))
    return out.reshape(b, r, h, value_dim)


def mla_decode_pallas(q, pool, tables, lengths, value_dim: int,
                      scale: float, layer=0, tile_blocks=None):
    """Decode over the latent pool: q [C, H, Dk] (one token a slot,
    already written at position ``lengths - 1``) -> [C, H, value_dim]."""
    return _mla_call("rlt_mla_decode", q[:, None], pool, tables,
                     lengths - 1, value_dim, scale, layer, tile_blocks,
                     1)[:, 0]


def mla_prefill_pallas(q, pool, tables, pos, value_dim: int, scale: float,
                       layer=0, tile_blocks=None, block_q=None):
    """Chunked causal prefill over the latent pool: q [B, CH, H, Dk],
    chunk token j at cache position ``pos + j`` (already written) ->
    [B, CH, H, value_dim]."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (q.shape[0],))
    return _mla_call("rlt_mla_prefill", q, pool, tables, pos, value_dim,
                     scale, layer, tile_blocks, block_q)
