"""Fused paged-attention PREFILL kernel (pallas TPU).

PR 11's decode kernel (`ops/pallas/paged_attention.py`) retired the
serving engine's capacity-wide dense KV view, but the prefill lane kept
gathering a `[L, prefill_batch, gathered_len, Hkv, hd]` per-group view
every chunk — at the flagship llama3-8b shape the remaining multi-GiB
HBM charge and the dominant per-chunk KV traffic in
`serve_memory_summary`. This kernel retires that last copy: the head
FIFO group's CH-token query chunk attends **causally** to the slot's
already-written pool blocks (plus the in-chunk K/V, which the model's
paged-prefill branch has already scattered into owned blocks through
the scratch-block-0 redirect) DIRECTLY through the per-row block
tables — the dense per-group gather never exists on the fused path.

Schedule (one layer's pool, the head group's chunk):

    q       [B, CH, H, hd]        the group's query chunk (B = group
                                  rows incl. vacant scratch rows)
    pool_k  [n_blocks, P, Hkv, hd]  the shared block pool (k; v alike),
                                  or the stack [L, n_blocks, P, Hkv, hd]
                                  with a layer index (`stack_as_pool`)
    tables  [B, M] int32          row -> pool block ids (0 = scratch)
    pos     [1] int32             the group's shared cache write offset
                                  (chunk token j sits at pos + j)
    pad     [B] int32             per-row left pad (ragged batched
                                  prefill; 0 = none)

grid = (B, CH/bq, M): for row b, query tile qi streams that row's M
table-named KV tiles through VMEM — the BlockSpec index_map reads the
scalar-prefetched table (`pltpu.PrefetchScalarGridSpec`, exactly the
decode kernel's discipline), so the DMA engine fetches pool block
`tables[b, m]` while compute runs and no gathered copy ever exists in
HBM. Per tile: one `[bq·H, P]` score panel, online-softmax statistics
(running max / sum / accumulator in f32 VMEM scratch — the
`ops/pallas/flash.py` discipline), per-row `pad <= kv_pos <= pos + j`
causal masking applied BEFORE the running max with masked
probabilities zeroed EXPLICITLY (a fully-masked tile's
`exp(-1e30 - (-1e30)) = 1` sentinel trap applies here exactly as it
did in decode — test-pinned), GQA KV heads read in place via the
grouped contraction (no repeat, no extra traffic). Tiles entirely past
the tile's last query position (or entirely under the row's pad) are
skipped (predicated body).

Inference-only: prefill under a serving engine has no backward, so
there is no VJP — the XLA reference twin with identical semantics is
`ops.attention.paged_prefill_reference`, and dispatch follows the
flash discipline (`ops.attention.paged_prefill_uses_pallas` as the
single predicate; interpret mode off-TPU).

Block sizes: the KV tile IS the pool block (`block_size`), the query
tile halves down from 128 until it divides CH and its tile-sized
buffers fit the scoped VMEM budget (`_fit_q_block`). The
on-TPU sweep over `block_size`/`blocks_per_slot` for BOTH paged
kernels lives in `serve/sweep.py` (docs/SERVING.md "block-size
autotune").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.dispatch import interpret_mode as _interpret
from ray_lightning_tpu.ops.pallas.paged_attention import stack_as_pool

_NEG_INF = -1e30  # never true -inf: exp(-inf - -inf) = nan on empty rows


#: VMEM the q-tile-sized buffers may claim, under Mosaic's 16 MiB scoped
#: default on v5e (the K/V tiles, stats columns and score panel take
#: the rest). tests/test_tpu_aot_compile.py checks that every shape the
#: predicate accepts compiles for "TPU v5 lite".
_Q_TILE_VMEM_BUDGET = 12 * 1024 * 1024

#: VMEM bytes per element of the [bq, H, hd] query tile: the f32
#: accumulator (4), the double-buffered q and o tiles (4 x itemsize,
#: priced at f32), and the f32 temporaries the body materializes at
#: tile size: the upcast q, its head-grouped transpose, the AV product,
#: its un-grouped transpose and the rescaled accumulator (measured:
#: 17.62M at bq=128 H=32 hd=128 bf16, ~35 B per element).
_Q_TILE_BYTES_PER_ELEM = 4 + 4 * 4 + 6 * 4


def _fit_q_block(ch: int, h: int, hd: int, cap: int = 128) -> int:
    """Largest query tile <= ``cap`` that divides the chunk width
    (halving search, the flash `_fit_block` discipline) and whose
    tile-sized buffers fit `_Q_TILE_VMEM_BUDGET`."""
    b = min(cap, ch)
    while b > 1 and (
            ch % b != 0
            or b * h * hd * _Q_TILE_BYTES_PER_ELEM > _Q_TILE_VMEM_BUDGET):
        b //= 2
    return b


def paged_prefill_shapes_supported(q_shape, pool_shape) -> bool:
    """Would the prefill kernel accept these shapes on a real TPU?

    q [B, CH, H, hd], pool [n_blocks, P, Hkv, hd] (or the stack, with
    a leading layer axis: the last four dims are judged): the head dim
    must be lane-aligned (128, or 64 which still tiles acceptably — the decode
    kernel's rule), the pool block must be sublane-aligned (P % 8), the
    GQA ratio must be whole, and the flattened score panel rows
    (q-tile x heads) must be sublane-aligned. Callers that must know
    the dispatch outcome use `ops.attention.paged_prefill_uses_pallas`,
    never this directly — one predicate, no drift."""
    if len(q_shape) != 4 or len(pool_shape) not in (4, 5):
        return False
    _, ch, h, hd = q_shape
    _, p, hkv, hd2 = pool_shape[-4:]
    if hd != hd2:
        return False
    if hd % 128 != 0 and hd not in (64,):
        return False
    if hkv < 1 or h % hkv != 0:
        return False
    if p % 8 != 0:
        return False
    if ch < 1 or (_fit_q_block(ch, h, hd) * h) % 8 != 0:
        return False
    return True


def _prefill_kernel(tbl_ref, pos_ref, pad_ref, q_ref, k_ref, v_ref,
                    o_ref, acc, m_scr, l_scr, *, scale, block_p,
                    block_q, num_kv_blocks, n_rep):
    """One (row, q-tile, kv-tile) grid step. Scratch persists across
    the innermost kv-tile axis (the flash forward's accumulation
    contract)."""
    b = pl.program_id(0)
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    pos = pos_ref[0]
    pad = pad_ref[b]
    # cache position of this q tile's first/last query row
    q_start = pos + pl.program_id(1) * block_q
    q_end = q_start + block_q - 1
    kv_start = m * block_p

    # tiles entirely past the tile's last query position (causal: no
    # query can see them) or entirely under the row's left pad hold
    # nothing visible — skip the DMA'd tile's compute (its garbage
    # never reaches the stats)
    @pl.when((kv_start <= q_end) & (kv_start + block_p > pad))
    def _body():
        q = q_ref[0].astype(jnp.float32)       # [bq, H, hd]
        k = k_ref[0].astype(jnp.float32)       # [P, Hkv, hd]
        v = v_ref[0].astype(jnp.float32)
        bq, h, hd = q.shape
        hkv = k.shape[1]
        # GQA head map: query head g*n_rep + r reads kv head g — group
        # the q heads and batch the contraction over kv heads, so KV
        # tiles are consumed in place (no repeat; the decode kernel's
        # grouped-contraction discipline, extended over the q tile)
        qg = (q.reshape(bq, hkv, n_rep, hd)
              .transpose(1, 0, 2, 3).reshape(hkv, bq * n_rep, hd))
        kg = k.transpose(1, 0, 2)              # [Hkv, P, hd]
        vg = v.transpose(1, 0, 2)
        s = jax.lax.dot_general(
            qg, kg, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                              # [Hkv, bq*n_rep, P]
        s4 = s.reshape(hkv, bq, n_rep, block_p)
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, s4.shape, 3)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, s4.shape, 1)
        # causal + pad, BEFORE the running max: scratch-block garbage,
        # table tails, pad columns and future in-chunk positions all
        # read _NEG_INF
        visible = (kv_pos <= q_pos) & (kv_pos >= pad)
        s4 = jnp.where(visible, s4, _NEG_INF)
        # flatten to the stats layout [bq*H, P] (row-major q x heads)
        sf = s4.transpose(1, 0, 2, 3).reshape(bq * h, block_p)
        vf = visible.transpose(1, 0, 2, 3).reshape(bq * h, block_p)
        m_prev = m_scr[:, 0]                   # [bq*H]
        m_new = jnp.maximum(m_prev, jnp.max(sf, axis=1))
        # masked positions are zeroed EXPLICITLY, not only through the
        # exp: a fully-masked row (every position under the row's pad,
        # or a pad-column query) has s == m_new == _NEG_INF and
        # exp(s - m_new) == 1 — the sentinel-minus-sentinel trap would
        # weight garbage at full probability
        p = jnp.where(vf, jnp.exp(sf - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = corr * l_scr[:, 0] + jnp.sum(p, axis=1)
        pg = (p.reshape(bq, hkv, n_rep, block_p)
              .transpose(1, 0, 2, 3).reshape(hkv, bq * n_rep, block_p))
        av = jax.lax.dot_general(
            pg, vg, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                      # [Hkv, bq*n_rep, hd]
        avf = (av.reshape(hkv, bq, n_rep, hd)
               .transpose(1, 0, 2, 3).reshape(bq * h, hd))
        acc[:] = corr[:, None] * acc[:] + avf
        m_scr[:, 0] = m_new

    @pl.when(m == num_kv_blocks - 1)
    def _finish():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)   # fully-masked row -> 0s
        bq, h, hd = o_ref.shape[1:]
        o_ref[0] = (acc[:] / safe_l[:, None]).reshape(
            bq, h, hd).astype(o_ref.dtype)


def paged_prefill_pallas(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    tables: jnp.ndarray,
    pos,
    pad: jnp.ndarray | None = None,
    scale: float | None = None,
    layer=0,
) -> jnp.ndarray:
    """Chunked causal prefill attention over the paged pool:
    [B, CH, H, hd] out.

    ``pool_k`` / ``pool_v`` are one layer's pool, or the stacked pool
    ``[L, n_blocks, P, Hkv, hd]`` read at ``layer`` (python int or
    traced scalar; `paged_attention.stack_as_pool`).
    ``tables`` names each group row's pool blocks (block 0 = reserved
    scratch — readable garbage, always masked); chunk token ``j`` sits
    at cache position ``pos + j`` and attends to
    ``pad[b] <= kv_pos <= pos + j`` — the already-written blocks plus
    the in-chunk prefix, which the caller has scattered into the pool
    BEFORE this call (write-then-attend, the decode lane's ordering).
    ``pad[b]`` masks a left-padded row's pad columns; a query that is
    itself a pad column sees nothing and emits zeros (discarded by the
    engine's active-row scatter)."""
    b, ch, h, hd = q.shape
    pool_k, pool_v, tables = stack_as_pool(pool_k, pool_v, tables, layer)
    n_blocks, p, hkv, _ = pool_k.shape
    m = tables.shape[1]
    n_rep = h // hkv
    scale = scale if scale is not None else hd ** -0.5
    if pad is None:
        pad = jnp.zeros((b,), jnp.int32)
    bq = _fit_q_block(ch, h, hd)
    nq = ch // bq
    kernel = functools.partial(
        _prefill_kernel, scale=scale, block_p=p, block_q=bq,
        num_kv_blocks=m, n_rep=n_rep)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tables, pos, pad
        grid=(b, nq, m),
        in_specs=[
            pl.BlockSpec((1, bq, h, hd),
                         lambda bi, qi, mi, tbl, ps, pd:
                         (bi, qi, 0, 0)),
            # the paged trick: the KV tile for (row, m) is whichever
            # pool block the scalar-prefetched table names — the tile
            # streams HBM -> VMEM with no intermediate gathered copy
            pl.BlockSpec((1, p, hkv, hd),
                         lambda bi, qi, mi, tbl, ps, pd:
                         (tbl[bi, mi], 0, 0, 0)),
            pl.BlockSpec((1, p, hkv, hd),
                         lambda bi, qi, mi, tbl, ps, pd:
                         (tbl[bi, mi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, h, hd),
                               lambda bi, qi, mi, tbl, ps, pd:
                               (bi, qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq * h, hd), jnp.float32),
            pltpu.VMEM((bq * h, 1), jnp.float32),
            pltpu.VMEM((bq * h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, ch, h, hd), q.dtype),
        name="rlt_paged_prefill",
        interpret=_interpret(),
    )(tables.astype(jnp.int32),
      jnp.asarray(pos, jnp.int32).reshape(1),
      pad.astype(jnp.int32), q, pool_k, pool_v)
