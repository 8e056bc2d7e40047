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

The kernel's unit of work is a KV TILE: ``tile_blocks`` table-named pool
blocks joined in VMEM (`prefill_tile_shape`: 512 tokens, 32 blocks of 16,
where the VMEM the query tile leaves allows it), and its time is that of
the tiles a query tile can see, not of the table. grid = (B, CH/bq): one
(row, query tile) a grid step, and inside it a loop over the LIVE tiles,
those with a position in ``[pad[b], pos + (qi + 1) * bq)``; the trip
count is a traced scalar (the table, ``pos`` and ``pad`` are scalar-
prefetched, `pltpu.PrefetchScalarGridSpec`), never a shape, so one
program serves every chunk. A tile past the query tile's last position,
or under the row's left pad, costs neither a fetch nor a step. The pool
stays in HBM (`memory_space=ANY`): the kernel copies in each live block
of a tile (`pltpu.make_async_copy`, the block id read from the prefetched
table; `paged_attention._fetch_tile`, the decode kernel's discipline)
into one half of a double buffer while it computes the other half, so no
gathered copy ever exists in HBM.

Once a grid step the query tile is cast and laid out by kv head,
``[Hkv, bq * n_rep, hd]`` in the pool's dtype (GQA: query head
g*n_rep + r reads kv head g, so KV heads are read in place — no repeat,
no extra traffic); the accumulator and the statistics stay in that
layout through the loop and are un-grouped once, at the end. Per tile:
K and V turned to ``[Hkv, tile, hd]``, one score product and one value
product batched over the kv heads with operands in the pool's dtype and
float32 out (for a bf16 pool the MXU's native form: the products are
exact in float32 either way, and the probabilities are cast to the
pool's dtype for the value product as the XLA twin
`ops.attention.dot_product_attention` casts them; a float32 pool
computes in float32 throughout), online-softmax statistics in float32
(running max / sum / accumulator in VMEM scratch — the
`ops/pallas/flash.py` discipline). Only a tile that straddles the query
tile's diagonal or the row's pad builds the
``pad <= kv_pos <= pos + j`` mask; there it is applied BEFORE the
running max with masked probabilities zeroed EXPLICITLY (a fully-masked
row's `exp(-1e30 - (-1e30)) = 1` sentinel trap applies here exactly as
it did in decode — test-pinned), and the V rows of a block that was not
fetched are zeroed, so scratch-block garbage (block 0, and whatever a
table names past the chunk, NaN included) contributes exactly zero.

A sliding ``window`` (static; None for a full-attention layer, which then
lowers the program it always did) is a lower bound beside the causal
upper one: query ``j`` sees ``pos + j - window < kv_pos <= pos + j``. A
query tile's live tiles then start at its FIRST row's floor,
``q_start - window + 1`` (`paged_attention.window_floor`), so tiles wholly
behind the window cost neither a fetch nor a step and a block the table names 0
there is never fetched; inside the boundary tiles the rows mask
``kv_pos > q_pos - window`` beside the causal mask.

Where Mosaic cannot slice a pool block out of HBM itself (``hd`` 64: a
row is half a lane tile), the same tile body is fed by the pipeline
instead (`_prefill_kernel_blockspec`: the pool an operand
``tile_blocks`` times over, grid = (B, CH/bq, ceil(M / tile_blocks)),
index maps clamped into the live blocks). That form's time is still its
table's (PERF.md section 6, PR 28); no serving cell runs it.

Inference-only: prefill under a serving engine has no backward, so
there is no VJP — the XLA reference twin with identical semantics is
`ops.attention.paged_prefill_reference`, and dispatch follows the
flash discipline (`ops.attention.paged_prefill_uses_pallas` as the
single predicate; interpret mode off-TPU).

Tile sizes follow from the operands (`prefill_tile_shape`; no argument,
no environment variable): the query tile halves down from 128 until it
divides CH and its buffers fit the VMEM planned for them
(`_fit_q_block`: the whole 128-row chunk at 32 heads of 128), then the
KV tile halves down from 512 tokens until it fits what is left. The
on-TPU sweep over `block_size`/`blocks_per_slot` for BOTH paged kernels
lives in `serve/sweep.py` (docs/SERVING.md "block-size autotune"); the
kernel alone on fixed inputs is `scripts/paged_prefill_alone.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.dispatch import interpret_mode as _interpret
from ray_lightning_tpu.ops.pallas.paged_attention import (
    _by_kv_head,
    _copies_in_kernel,
    _fetch_tile,
    _live_extent,
    pool_dims,
    stack_as_pool,
    window_floor,
)

_NEG_INF = -1e30  # never true -inf: exp(-inf - -inf) = nan on empty rows

#: tokens a KV tile aims at. A tile is what one step of the kernel's loop
#: computes: ``tile_blocks`` table-named pool blocks joined in VMEM. The
#: statistics and the accumulator are rescaled once a tile whatever its
#: width, so a wide tile is the cheaper one deep in a prompt; behind few
#: tokens a partial tile is computed whole (PERF.md section 6, PR 30)
_TILE_TOKENS = 512

#: scoped VMEM the kernel asks for (a v5e core has 128 MiB; Mosaic's
#: default scope of 16 MiB does not hold a 128-row query tile of 32 heads
#: beside its float32 accumulator and a 512-token score panel)
_VMEM_LIMIT = 64 * 1024 * 1024

#: what of `_VMEM_LIMIT` the rule below plans: the buffers that grow with
#: the query tile, and those that grow with the KV tile. The rest is
#: Mosaic's own. tests/test_tpu_aot_compile.py checks that every shape
#: the predicate accepts compiles for "TPU v5 lite"
_VMEM_BUDGET = 51 * 1024 * 1024


def _q_tile_bytes(bq: int, h: int, hd: int, itemsize: int = 2) -> int:
    """VMEM the buffers of a ``[bq, H, hd]`` query tile take: the
    pipeline's two q and two o tiles and the grouped q (5 x itemsize an
    element), the float32 accumulator (4), the float32 temporaries of the
    prologue and the epilogue, which upcast and regroup the tile once
    each (8), and a row's two float32 statistics, each padded to 128
    lanes."""
    return bq * h * (hd * (5 * itemsize + 4 + 8) + 2 * 128 * 4)


def _kv_token_bytes(rows: int, hkv: int, hd: int, itemsize: int = 2) -> int:
    """VMEM one token of the KV tile's width takes: K and V in the double
    buffer and regrouped by head (6 x itemsize an element), one float32
    turn of each (8), and a column of the score panel over ``rows`` = bq
    x H query rows: scores, probabilities and their cast."""
    return hkv * hd * (6 * itemsize + 8) + rows * (4 + 4 + itemsize)


def _fit_q_block(ch: int, h: int, hd: int, cap: int = 128) -> int:
    """Largest query tile <= ``cap`` that divides the chunk width
    (halving search, the flash `_fit_block` discipline) and whose
    buffers fit `_VMEM_BUDGET` beside a 128-token score panel (a KV
    tile's share that does not shrink with the kv heads: hkv 0)."""
    b = min(cap, ch)
    while b > 1 and (
            ch % b != 0
            or _q_tile_bytes(b, h, hd) + 128 * _kv_token_bytes(b * h, 0, hd)
            > _VMEM_BUDGET):
        b //= 2
    return b


def prefill_tile_shape(q_shape, pool_shape, blocks_per_slot: int):
    """(query tile rows, KV tile tokens) for a chunk ``q_shape``
    [B, CH, H, hd] over ``pool_shape`` [.., P, Hkv, hd] with tables of
    ``blocks_per_slot`` blocks: the kernel's own rule, from what it sees
    in its operands. The query tile first (`_fit_q_block`), then as many
    whole blocks as `_TILE_TOKENS` holds, halved until the tile fits the
    VMEM the query tile leaves, at most a whole table. A table that is no
    whole number of tiles ends in a shorter LIVE extent, never in a
    smaller tile."""
    _, ch, h, hd = q_shape
    p, hkv = pool_shape[-3:-1]
    bq = _fit_q_block(ch, h, hd)
    left = _VMEM_BUDGET - _q_tile_bytes(bq, h, hd)
    tb = max(1, _TILE_TOKENS // p)
    while tb > 1 and tb * p * _kv_token_bytes(bq * h, hkv, hd) > left:
        tb //= 2
    return bq, min(tb, blocks_per_slot) * p


def prefill_live_tiles(pos: int, pads, chunk: int, block_q: int,
                       tile_tokens: int, table_tokens: int,
                       window: int | None = None) -> int:
    """KV tiles a layer the kernel computes for one chunk at cache offset
    ``pos`` over rows with left pads ``pads``: for each row and query
    tile, the tiles with a position in ``[pad, pos + (qi + 1) * bq)``
    (`_live_extent`, on the host), the floor raised to the query tile's
    ``q_start - window + 1`` under a window. `DecodeEngine._step_work`
    counts them as ``prefill_tiles``."""
    pads = np.asarray(pads, np.int64)[:, None]
    starts = pos + np.arange(chunk // block_q) * block_q
    if window is not None:
        pads = np.maximum(pads, starts[None, :] - window + 1)
    ends = np.minimum(starts + block_q, table_tokens)[None, :]
    hi = -(-ends // tile_tokens)
    lo = np.minimum(pads // tile_tokens, hi)
    return int(np.where(ends > pads, hi - lo, 0).sum())


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


def _prepare(q_ref, qg, acc, m_scr, l_scr, *, n_rep):
    """Once a (row, query tile): the query tile into the head-grouped
    layout ``[Hkv, bq * n_rep, hd]`` (query head g*n_rep + r reads kv
    head g, so KV tiles are consumed in place: no repeat), in the pool's
    dtype for the MXU; the statistics reset."""
    bq, h, hd = q_ref.shape[1:]
    hkv = h // n_rep
    q = q_ref[0].astype(jnp.float32)
    qg[...] = (q.reshape(bq, hkv, n_rep, hd).transpose(1, 0, 2, 3)
               .reshape(hkv, bq * n_rep, hd)).astype(qg.dtype)
    acc[...] = jnp.zeros_like(acc)
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)


def _tile_step(qg, acc, m_scr, l_scr, k, v, kv_start, q_start, pad, *,
               scale, n_rep, window=None):
    """Online-softmax update of a query tile's statistics by one KV tile
    ``k`` / ``v`` [tile, Hkv, hd] (the pool's dtype) whose first token
    sits at ``kv_start``: two products batched over the kv heads,
    ``[bq * n_rep, hd] x [hd, tile]`` and ``[bq * n_rep, tile] x
    [tile, hd]``, operands in the pool's dtype and float32 out. Max, sum,
    correction and accumulator are float32 and stay in the grouped
    layout. Only a tile that straddles the query tile's diagonal, the
    row's pad or (with a ``window``) the window's trailing edge builds the
    mask."""
    _, rows, _ = qg.shape
    tile = k.shape[0]
    # [tile, Hkv, hd] -> [Hkv, tile, hd]: a sublane shuffle in float32
    kg = _by_kv_head(k, False)
    vg = _by_kv_head(v, False)

    def update(visible):
        s = jax.lax.dot_general(
            qg[...], kg, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [Hkv, rows, tile]
        if visible is not None:
            # causal + pad, BEFORE the running max: scratch-block
            # garbage, table tails, pad columns and future in-chunk
            # positions all read _NEG_INF
            s = jnp.where(visible, s, _NEG_INF)
        m_prev = m_scr[...]                              # [Hkv, rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        if visible is not None:
            # masked positions are zeroed EXPLICITLY, not only through
            # the exp: a fully-masked row (every position under the
            # row's pad, or a pad-column query) has s == m_new ==
            # _NEG_INF and exp(s - m_new) == 1 — the sentinel-minus-
            # sentinel trap would weight garbage at full probability
            p = jnp.where(visible, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=2, keepdims=True)
        acc[...] = corr * acc[...] + jax.lax.dot_general(
            p.astype(vg.dtype), vg, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # [Hkv, rows, hd]
        m_scr[...] = m_new

    # every query of the tile sees every token of a KV tile that ends at
    # or before the first query's position and starts at or past the pad
    whole = (kv_start + tile - 1 <= q_start) & (kv_start >= pad)
    if window is not None:
        # ... and the tile's first token is inside the LAST query's window
        whole &= kv_start > q_start + rows // n_rep - 1 - window

    @pl.when(whole)
    def _unmasked():
        update(None)

    @pl.when(jnp.logical_not(whole))
    def _masked():
        # the mask in the grouped layout: row r is query r // n_rep
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows, tile), 2)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows, tile), 1) // n_rep
        visible = (kv_pos <= q_pos) & (kv_pos >= pad)
        if window is not None:
            visible &= kv_pos > q_pos - window
        update(visible)


def _finish(o_ref, acc, l_scr, *, n_rep):
    """Un-group once: ``[Hkv, bq * n_rep, hd]`` -> ``[bq, H, hd]``."""
    bq, h, hd = o_ref.shape[1:]
    hkv = h // n_rep
    l = l_scr[...]
    safe_l = jnp.where(l == 0.0, 1.0, l)       # fully-masked row -> 0s
    o_ref[0] = ((acc[...] / safe_l).reshape(hkv, bq, n_rep, hd)
                .transpose(1, 0, 2, 3).reshape(bq, h, hd)
                .astype(o_ref.dtype))


def _prefill_kernel(tbl_ref, pos_ref, pad_ref, q_ref, k_hbm, v_hbm, o_ref,
                    kbuf, vbuf, sems, qg, acc, m_scr, l_scr, *,
                    scale, block_p, tile_blocks, table_blocks, block_q,
                    n_rep, window):
    """One (row, query tile) a grid step: a loop over the KV tiles the
    query tile can see, its trip count a traced scalar. The pool stays in
    HBM (`memory_space=ANY`); a tile's live blocks come in by one async
    copy each into one half of a double buffer while the other half is
    computed (`paged_attention._fetch_tile`)."""
    b = pl.program_id(0)
    tile = tile_blocks * block_p
    pad = pad_ref[b]
    # cache position of this query tile's first row; its last row sees
    # positions below ``q_start + block_q``
    q_start = pos_ref[0] + pl.program_id(1) * block_q
    b_lo, b_hi, t_lo, t_hi = _live_extent(
        q_start + block_q, window_floor(q_start, pad, window), block_p,
        tile_blocks, table_blocks)
    fetch = functools.partial(
        _fetch_tile, tbl_ref, k_hbm, v_hbm, kbuf, vbuf, sems, b,
        b_lo=b_lo, b_hi=b_hi, block_p=block_p, tile_blocks=tile_blocks,
        table_blocks=table_blocks)

    @pl.when(t_lo < t_hi)
    def _first():
        fetch(t_lo, 0, wait=False)

    _prepare(q_ref, qg, acc, m_scr, l_scr, n_rep=n_rep)

    def _tile(t, _):
        half = (t - t_lo) % 2

        @pl.when(t + 1 < t_hi)
        def _next():
            fetch(t + 1, 1 - half, wait=False)

        fetch(t, half, wait=True)
        _tile_step(qg, acc, m_scr, l_scr, kbuf[half], vbuf[half], t * tile,
                   q_start, pad, scale=scale, n_rep=n_rep, window=window)

    jax.lax.fori_loop(t_lo, t_hi, _tile, None)
    _finish(o_ref, acc, l_scr, n_rep=n_rep)


def _prefill_kernel_blockspec(tbl_ref, pos_ref, pad_ref, q_ref, *rest,
                              scale, block_p, tile_blocks, table_blocks,
                              block_q, n_rep, window):
    """One (row, query tile, KV tile) a grid step, the tile's blocks
    brought in by the pipeline: the pool is an operand ``tile_blocks``
    times over, each copy's index_map another entry of the table
    (`block_spec`, below). For pools whose rows Mosaic cannot slice in
    HBM itself (``hd`` 64); scratch persists across the innermost axis."""
    k_refs, v_refs = rest[:tile_blocks], rest[tile_blocks:2 * tile_blocks]
    o_ref, qg, acc, m_scr, l_scr = rest[2 * tile_blocks:]
    b, t = pl.program_id(0), pl.program_id(2)
    pad = pad_ref[b]
    q_start = pos_ref[0] + pl.program_id(1) * block_q
    _, _, t_lo, t_hi = _live_extent(
        q_start + block_q, window_floor(q_start, pad, window), block_p,
        tile_blocks, table_blocks)

    @pl.when(t == 0)
    def _init():
        _prepare(q_ref, qg, acc, m_scr, l_scr, n_rep=n_rep)

    @pl.when((t >= t_lo) & (t < t_hi))
    def _body():
        join = lambda refs: refs[0][0] if tile_blocks == 1 else \
            jnp.concatenate([r[0] for r in refs], axis=0)
        _tile_step(qg, acc, m_scr, l_scr, join(k_refs), join(v_refs),
                   t * tile_blocks * block_p, q_start, pad, scale=scale,
                   n_rep=n_rep, window=window)

    @pl.when(t == pl.num_programs(2) - 1)
    def _done():
        _finish(o_ref, acc, l_scr, n_rep=n_rep)


def paged_prefill_pallas(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    tables: jnp.ndarray,
    pos,
    pad: jnp.ndarray | None = None,
    scale: float | None = None,
    layer=0,
    window: int | None = None,
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
    engine's active-row scatter). A static ``window`` adds the lower
    bound ``kv_pos > pos + j - window``."""
    b, ch, h, hd = q.shape
    pool_k, pool_v, tables = stack_as_pool(pool_k, pool_v, tables, layer)
    n_blocks, p, hkv, _ = pool_dims(pool_k)
    kv_row = pool_k.shape[2:]       # (Hkv, hd), or (hd,) of a headless pool
    m = tables.shape[1]
    n_rep = h // hkv
    scale = scale if scale is not None else hd ** -0.5
    if pad is None:
        pad = jnp.zeros((b,), jnp.int32)
    bq, tile = prefill_tile_shape(q.shape, pool_dims(pool_k), m)
    nq, tb = ch // bq, tile // p
    static = dict(scale=scale, block_p=p, tile_blocks=tb, table_blocks=m,
                  block_q=bq, n_rep=n_rep, window=window)
    q_spec = pl.BlockSpec((1, bq, h, hd), lambda bi, qi, *_: (bi, qi, 0, 0))
    rows = bq * n_rep
    scratch = [
        pltpu.VMEM((hkv, rows, hd), pool_k.dtype),     # grouped q
        pltpu.VMEM((hkv, rows, hd), jnp.float32),      # accumulator
        pltpu.VMEM((hkv, rows, 1), jnp.float32),       # running max
        pltpu.VMEM((hkv, rows, 1), jnp.float32),       # running sum
    ]
    if _copies_in_kernel(hd):
        kernel = functools.partial(_prefill_kernel, **static)
        grid = (b, nq)
        # the paged trick: the pool never leaves HBM as a whole; the
        # kernel copies in whichever blocks the scalar-prefetched table
        # names, and only those a query tile can see
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch = [
            pltpu.VMEM((2, tb * p, *kv_row), pool_k.dtype),
            pltpu.VMEM((2, tb * p, *kv_row), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),   # (k | v, buffer half)
            *scratch,
        ]
        pools = (pool_k, pool_v)
    else:
        kernel = functools.partial(_prefill_kernel_blockspec, **static)
        grid = (b, nq, pl.cdiv(m, tb))

        def block_spec(j):
            def index(bi, qi, ti, tbl, ps, pd):
                # clamped into the blocks the query tile can see: a
                # block outside them repeats a live one, which the
                # pipeline does not fetch again and the mask never shows
                q_start = ps[0] + qi * bq
                b_lo, b_hi, _, _ = _live_extent(
                    q_start + bq, window_floor(q_start, pd[bi], window),
                    p, tb, m)
                blk = jnp.clip(ti * tb + j, b_lo, jnp.maximum(b_hi - 1, 0))
                return tbl[bi, blk], 0, 0, 0

            return pl.BlockSpec((1, p, hkv, hd), index)

        kv_specs = [block_spec(j) for j in range(tb)] * 2
        pools = (pool_k,) * tb + (pool_v,) * tb
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # tables, pos, pad
            grid=grid,
            in_specs=[q_spec, *kv_specs],
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, ch, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="rlt_paged_prefill",
        interpret=_interpret(),
    )(tables.astype(jnp.int32),
      jnp.asarray(pos, jnp.int32).reshape(1),
      pad.astype(jnp.int32), q, *pools)
