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

The kernel's unit of work is a KV TILE: ``tile_blocks`` table-named pool
blocks joined in VMEM (`decode_tile_tokens`: 128 tokens, 8 blocks of 16),
and its time is that of the tiles that hold a cached token, not of the
table. grid = (C,): one slot a grid step, and inside it a loop over the
slot's LIVE tiles, those with a position in ``[pad, length)``; the trip
count is a scalar-prefetched value (`pltpu.PrefetchScalarGridSpec`), never
a shape, so one program serves every batch. A tile past a slot's length,
or under its left pad, costs neither a fetch nor a step; a slot of length
0 costs a grid step and reads zeros. The pool stays in HBM
(`memory_space=ANY`): the kernel copies in each live block of a tile
(`pltpu.make_async_copy`, the block id read from the prefetched table)
into one half of a double buffer while it computes the other half, and
carries the tile in flight across grid steps (the next live slot's first
tile), so no gathered copy ever exists in HBM and the copies never wait
for a grid step. Per tile: one [H, tile] score panel, online-softmax
statistics in float32 (running max / sum / accumulator carried through
the loop, the flash-attention discipline of `ops/pallas/flash.py`),
masked by `pad <= kv_pos < length` BEFORE the max, masked probabilities
zeroed explicitly, and the V rows of a block that was not fetched zeroed,
so scratch-block garbage (block 0, and whatever a table names past a
slot's length, NaN included) contributes exactly zero. GQA reads KV heads
in place via the `h // (H // Hkv)` head map — no repeat, no extra
traffic.

A sliding ``window`` (static; None for a full-attention layer, which then
lowers the program it always did) is a lower bound beside the length's
upper one: a slot sees ``max(pad, length - window) <= kv_pos < length``,
which is the live extent above with a raised ``pad`` (`window_floor`), so
tiles wholly behind the window cost neither a fetch nor a step, the
boundary tile is masked by row, and a block the table names 0 behind the
window is never fetched.

Where Mosaic cannot slice a pool block out of HBM itself (``hd`` 64: a
row is half a lane tile), the same tile body is fed by the pipeline
instead (`_decode_kernel_blockspec`: the pool an operand ``tile_blocks``
times over, grid = (C, ceil(M / tile_blocks)), index maps clamped into
the live blocks). There every (operand, grid step) pair costs its
bookkeeping whether or not it fetches, so that form's time is still its
table's; on the chip it is 3-9x the first form (PERF.md section 6, PR 28).

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
import numpy as np
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
    if pool_k.ndim in (3, 4):      # one pool; 3-D: `headless_stack_as_pool`
        return pool_k, pool_v, tables
    n_layers, n_blocks = pool_k.shape[:2]
    flat = (n_layers * n_blocks, *pool_k.shape[2:])
    return (pool_k.reshape(flat), pool_v.reshape(flat),
            tables + jnp.asarray(layer, tables.dtype) * n_blocks)


def headless_stack_as_pool(pool_k, pool_v, tables, layer):
    """`stack_as_pool` for a decoder with ONE KV head that keeps its stack
    without the head axis, ``[L, n_blocks, P, hd]``: a leaf ``[.., P, 1,
    hd]`` has a degenerate second-minor dimension, which the chip's tiled
    layout pads to a sublane tile (twice the bytes in bfloat16, and Mosaic
    cannot slice one head of the padded pair out of HBM). The result is the
    3-D pool ``[L * n_blocks, P, hd]`` both paged kernels take as "one KV
    head": they add the head axis to a tile in VMEM, where it costs
    nothing."""
    n_layers, n_blocks = pool_k.shape[:2]
    flat = (n_layers * n_blocks, *pool_k.shape[2:])
    return (pool_k.reshape(flat), pool_v.reshape(flat),
            tables + jnp.asarray(layer, tables.dtype) * n_blocks)


def pool_dims(pool):
    """(n_blocks, P, Hkv, hd) of one pool: ``[n_blocks, P, Hkv, hd]``, or
    the headless ``[n_blocks, P, hd]`` of one KV head."""
    if pool.ndim == 3:
        return (*pool.shape[:2], 1, pool.shape[2])
    return tuple(pool.shape)


def _by_kv_head(x, upcast: bool):
    """A KV tile ``[tile, Hkv, hd]`` as ``[Hkv, tile, hd]`` (a sublane
    shuffle, in float32); a headless tile ``[tile, hd]`` gains the axis in
    front, which moves nothing."""
    if x.ndim == 2:
        return (x.astype(jnp.float32) if upcast else x)[None]
    y = x.astype(jnp.float32).transpose(1, 0, 2)
    return y if upcast else y.astype(x.dtype)


#: tokens a KV tile aims at. A tile is what one step of the kernel's loop
#: computes: ``tile_blocks`` table-named pool blocks joined in VMEM
_TILE_TOKENS = 128


def decode_tile_blocks(block_size: int, blocks_per_slot: int) -> int:
    """Pool blocks a KV tile of the decode kernel joins: as many
    ``block_size``-token blocks as `_TILE_TOKENS` holds, at most a whole
    table. The kernel's own rule, from what it sees in its operands (the
    pool's ``P``, the table's ``M``); a table that is no whole number of
    tiles ends in a shorter LIVE extent, never in a smaller tile."""
    return max(1, min(_TILE_TOKENS // block_size, blocks_per_slot))


def decode_tile_tokens(block_size: int, blocks_per_slot: int) -> int:
    """Tokens of one KV tile (`decode_tile_blocks` x ``block_size``): the
    unit the kernel's time is counted in. A slot of ``length`` cached
    tokens costs ``ceil(length / tile)`` tiles; `DecodeEngine._step_work`
    counts them as ``decode_tiles``."""
    return decode_tile_blocks(block_size, blocks_per_slot) * block_size


def window_floor(q_pos, pad, window):
    """The first cache position a query at ``q_pos`` sees under a sliding
    window of ``window`` tokens, itself included: ``q_pos - window + 1``,
    never below the left pad. None passes ``pad`` through (no window).
    Shared with `paged_prefill.py` (its query tile's FIRST row)."""
    if window is None:
        return pad
    return jnp.maximum(pad, q_pos - window + 1)


def decode_live_tiles(lengths, tile_tokens: int, window=None) -> int:
    """KV tiles a layer the decode kernel computes for slots of
    ``lengths`` cached tokens (no left pad): those with a position in
    ``[max(0, length - window), length)`` (`_live_extent`, on the host).
    `DecodeEngine._step_work` counts them."""
    lengths = np.asarray(lengths, np.int64)
    lo = 0 if window is None else np.maximum(lengths - window, 0)
    return int((-(-lengths // tile_tokens) - lo // tile_tokens).sum())


def _copies_in_kernel(hd: int) -> bool:
    """Whether the kernel copies its tiles in itself (`_decode_kernel`)
    or leaves them to the pipeline (`_decode_kernel_blockspec`): Mosaic
    slices a pool block out of HBM only where its rows are whole
    128-lane tiles."""
    return hd % 128 == 0


def _live_extent(length, pad, block_p, tile_blocks, table_blocks):
    """Live blocks ``[b_lo, b_hi)`` and live tiles ``[t_lo, t_hi)`` of a
    slot: those that hold a position in ``[pad, length)``. Nothing
    visible (``length <= pad``, a slot that asks for nothing) is an
    empty extent of both."""
    length = jnp.minimum(length, table_blocks * block_p)
    b_hi = jnp.where(length > pad, pl.cdiv(length, block_p), 0)
    b_lo = jnp.minimum(pad // block_p, b_hi)
    return b_lo, b_hi, b_lo // tile_blocks, pl.cdiv(b_hi, tile_blocks)


def _fetch_tile(tbl_ref, k_hbm, v_hbm, kbuf, vbuf, sems, row, t, half, b_lo,
                b_hi, *, block_p, tile_blocks, table_blocks, wait):
    """Start (or wait for) the copies of KV tile ``t`` of table row
    ``row`` into ``half`` of the double buffer, one async copy a pool
    block, the block id read from the prefetched table. A block outside
    the live blocks ``[b_lo, b_hi)`` is not fetched; its V rows are
    zeroed instead (0 x garbage must stay 0; K's garbage is masked in
    the scores). Shared with `paged_prefill.py`."""
    for j in range(tile_blocks):
        b = t * tile_blocks + j
        live = (b >= b_lo) & (b < b_hi)
        rows = pl.ds(j * block_p, block_p)
        blk = 0 if wait else tbl_ref[
            row, jnp.minimum(b, table_blocks - 1)]
        ck = pltpu.make_async_copy(
            k_hbm.at[blk], kbuf.at[half, rows], sems.at[0, half])
        cv = pltpu.make_async_copy(
            v_hbm.at[blk], vbuf.at[half, rows], sems.at[1, half])

        @pl.when(live)
        def _copy():
            if wait:
                ck.wait()
                cv.wait()
            else:
                ck.start()
                cv.start()

        if not wait:
            @pl.when(jnp.logical_not(live))
            def _zero():
                vbuf[half, rows] = jnp.zeros(
                    (block_p, *vbuf.shape[2:]), vbuf.dtype)


def _tile_update(qg, k, v, kv_start, length, pad, carry, *, scale):
    """Online-softmax update of one slot's statistics by one KV tile:
    ``qg`` [Hkv, n_rep, hd] float32, ``k`` / ``v`` [tile, Hkv, hd] in the
    pool's dtype, ``carry`` = (running max [H, 1], sum [H, 1],
    accumulator [H, hd]), all float32."""
    m_prev, l_prev, acc = carry
    hkv, n_rep, hd = qg.shape
    h, tile = hkv * n_rep, k.shape[0]
    # GQA head map: query head g*n_rep + r reads kv head g — the
    # contraction is batched over kv heads, so KV tiles are consumed in
    # place (no repeat)
    kg = _by_kv_head(k, True)                          # [Hkv, tile, hd]
    vg = _by_kv_head(v, True)
    s = (jax.lax.dot_general(
        qg, kg, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale).reshape(h, tile)            # [Hkv, n_rep, tile] -> [H, tile]
    # the mask is built in the stats layout [H, tile] directly: Mosaic
    # cannot reshape an i1 vector across the degenerate n_rep == 1
    # axis (MHA; LLO_CHECK `vmand ... ProducesVreg` on v5e)
    kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    visible = (kv_pos < length) & (kv_pos >= pad)
    s = jnp.where(visible, s, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # masked positions are zeroed EXPLICITLY, not only through the exp: a
    # fully-masked panel has s == m_new == _NEG_INF and exp(s - m_new)
    # == 1 — the sentinel-minus-sentinel trap would weight garbage at
    # full probability
    p = jnp.where(visible, jnp.exp(s - m_new), 0.0)    # [H, tile]
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
    av = jax.lax.dot_general(
        p.reshape(hkv, n_rep, tile), vg,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                      # [Hkv, n_rep, hd]
    return m_new, l_new, corr * acc + av.reshape(h, hd)


def _init_carry(h, hd):
    return (jnp.full((h, 1), _NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, hd), jnp.float32))


def _emit(o_ref, l, acc):
    safe_l = jnp.where(l == 0.0, 1.0, l)   # nothing visible -> zeros
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)


def _decode_kernel(tbl_ref, len_ref, pad_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sems, state, *, scale, block_p, tile_blocks,
                   table_blocks, n_slots, n_rep):
    """One slot a grid step: a loop over the slot's LIVE KV tiles, its
    trip count a prefetched scalar. The pool stays in HBM
    (`memory_space=ANY`); a tile's live blocks come in by one async copy
    each into one half of a double buffer while the other half is
    computed, and the tile in flight when a slot ends is the next live
    slot's first, so the copies never wait for a grid step (``state``:
    the half the next tile lands in, and whether it is in flight)."""
    c = pl.program_id(0)
    tile = tile_blocks * block_p
    extent = lambda slot: _live_extent(
        len_ref[slot], pad_ref[slot], block_p, tile_blocks, table_blocks)

    def fetch(slot, t, half, wait):
        b_lo, b_hi, _, _ = extent(slot)
        _fetch_tile(tbl_ref, k_hbm, v_hbm, kbuf, vbuf, sems, slot, t, half,
                    b_lo, b_hi, block_p=block_p, tile_blocks=tile_blocks,
                    table_blocks=table_blocks, wait=wait)

    @pl.when(c == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    length = len_ref[c]
    pad = pad_ref[c]
    _, _, t_lo, t_hi = extent(c)
    half0 = state[0]
    h, hd = q_ref.shape[1:]
    qg = q_ref[0].astype(jnp.float32).reshape(h // n_rep, n_rep, hd)

    @pl.when((t_lo < t_hi) & (state[1] == 0))
    def _first():
        fetch(c, t_lo, half0, wait=False)

    def next_live(slot):
        # the first slot after ``slot`` with a live tile (n_slots: none)
        def dead(s):
            _, _, lo, hi = extent(jnp.minimum(s, n_slots - 1))
            return (s < n_slots) & (lo >= hi)

        return jax.lax.while_loop(dead, lambda s: s + 1, slot + 1)

    def _tile(t, carry):
        half = (half0 + t - t_lo) % 2

        @pl.when(t + 1 < t_hi)
        def _next_tile():
            fetch(c, t + 1, 1 - half, wait=False)

        @pl.when(t + 1 == t_hi)
        def _next_slot():
            nxt = next_live(c)
            state[1] = (nxt < n_slots).astype(jnp.int32)

            @pl.when(nxt < n_slots)
            def _():
                slot = jnp.minimum(nxt, n_slots - 1)
                fetch(slot, extent(slot)[2], 1 - half, wait=False)

        fetch(c, t, half, wait=True)
        return _tile_update(qg, kbuf[half], vbuf[half], t * tile, length,
                            pad, carry, scale=scale)

    _, l, acc = jax.lax.fori_loop(t_lo, t_hi, _tile, _init_carry(h, hd))

    @pl.when(t_lo < t_hi)
    def _advance():
        state[0] = (half0 + t_hi - t_lo) % 2

    _emit(o_ref, l, acc)


def _decode_kernel_blockspec(tbl_ref, len_ref, pad_ref, q_ref, *rest, scale,
                             block_p, tile_blocks, table_blocks, n_rep):
    """One (slot, KV tile) a grid step, the tile's blocks brought in by
    the pipeline: the pool is an operand ``tile_blocks`` times over, each
    copy's index_map another entry of the table (`block_spec`, below). For
    pools whose rows Mosaic cannot slice in HBM itself (``hd`` 64)."""
    k_refs, v_refs = rest[:tile_blocks], rest[tile_blocks:2 * tile_blocks]
    o_ref, acc, m_scr, l_scr = rest[2 * tile_blocks:]
    c, t = pl.program_id(0), pl.program_id(1)
    h, hd = q_ref.shape[1:]

    @pl.when(t == 0)
    def _init():
        m_scr[...], l_scr[...], acc[...] = _init_carry(h, hd)

    length, pad = len_ref[c], pad_ref[c]
    _, _, t_lo, t_hi = _live_extent(length, pad, block_p, tile_blocks,
                                    table_blocks)

    @pl.when((t >= t_lo) & (t < t_hi))
    def _body():
        qg = q_ref[0].astype(jnp.float32).reshape(h // n_rep, n_rep, hd)
        join = lambda refs: refs[0][0] if tile_blocks == 1 else \
            jnp.concatenate([r[0] for r in refs], axis=0)
        m_scr[...], l_scr[...], acc[...] = _tile_update(
            qg, join(k_refs), join(v_refs), t * tile_blocks * block_p,
            length, pad, (m_scr[...], l_scr[...], acc[...]), scale=scale)

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        _emit(o_ref, l_scr[...], acc[...])


def paged_attention_pallas(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    pad: jnp.ndarray | None = None,
    scale: float | None = None,
    layer=0,
    window: int | None = None,
) -> jnp.ndarray:
    """Decode attention over the paged pool: [C, H, hd] out.

    ``pool_k`` / ``pool_v`` are one layer's pool, or the stacked pool
    ``[L, n_blocks, P, Hkv, hd]`` read at ``layer`` (python int or
    traced scalar; `stack_as_pool`).
    ``tables`` names each slot's pool blocks (block 0 = reserved
    scratch — readable garbage, always masked by ``lengths``/``pad``);
    ``lengths[c]`` is the number of valid cache positions (including
    the just-written query token), 0 for a slot that asks for nothing
    (it costs no tile and reads zeros); ``pad[c]`` masks a left-padded
    slot's pad columns (positions < pad never attend); a static
    ``window`` raises it to ``length - window`` (`window_floor` of the
    query at ``length - 1``)."""
    c, h, hd = q.shape
    pool_k, pool_v, tables = stack_as_pool(pool_k, pool_v, tables, layer)
    n_blocks, p, hkv, _ = pool_dims(pool_k)
    kv_row = pool_k.shape[2:]       # (Hkv, hd), or (hd,) of a headless pool
    m = tables.shape[1]
    tb = decode_tile_blocks(p, m)
    scale = scale if scale is not None else hd ** -0.5
    if pad is None:
        pad = jnp.zeros_like(lengths)
    if window is not None:     # (None: not an equation more than before)
        pad = window_floor(lengths - 1, pad, window)
    static = dict(scale=scale, block_p=p, tile_blocks=tb, table_blocks=m,
                  n_rep=h // hkv)
    q_spec = pl.BlockSpec((1, h, hd), lambda ci, *_: (ci, 0, 0))
    if _copies_in_kernel(hd):
        kernel = functools.partial(_decode_kernel, n_slots=c, **static)
        grid = (c,)
        # the paged trick: the pool never leaves HBM as a whole; the
        # kernel copies in whichever blocks the scalar-prefetched table
        # names, and only those a slot's length reaches
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch = [
            pltpu.VMEM((2, tb * p, *kv_row), pool_k.dtype),
            pltpu.VMEM((2, tb * p, *kv_row), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),   # (k | v, buffer half)
            pltpu.SMEM((2,), jnp.int32),
        ]
        pools = (pool_k, pool_v)
    else:
        kernel = functools.partial(_decode_kernel_blockspec, **static)
        grid = (c, pl.cdiv(m, tb))

        def block_spec(j):
            def index(ci, ti, tbl, ln, pd):
                # clamped into the slot's live blocks: a block outside
                # them repeats a live one, which the pipeline does not
                # fetch again and the mask never shows
                b_lo, b_hi, _, _ = _live_extent(ln[ci], pd[ci], p, tb, m)
                b = jnp.clip(ti * tb + j, b_lo, jnp.maximum(b_hi - 1, 0))
                return tbl[ci, b], 0, 0, 0

            return pl.BlockSpec((1, p, hkv, hd), index)

        kv_specs = [block_spec(j) for j in range(tb)] * 2
        scratch = [
            pltpu.VMEM((h, hd), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ]
        pools = (pool_k,) * tb + (pool_v,) * tb
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # tables, lengths, pad
            grid=grid,
            in_specs=[q_spec, *kv_specs],
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((c, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        name="rlt_paged_decode",
        interpret=_interpret(),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      pad.astype(jnp.int32), q, *pools)
