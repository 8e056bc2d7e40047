"""Attention ops: masked SDPA reference + flash-attention dispatch +
paged decode attention for the serving engine.

The hot op of the flagship model. Three tiers:
  1. `dot_product_attention` — pure jnp reference (materializes the S×S
     score matrix); correct everywhere, used for tests and tiny shapes.
  2. `flash_attention` — tiled online-softmax kernel
     (ray_lightning_tpu.ops.pallas.flash) that never materializes scores;
     O(S) memory, MXU-shaped tiles. Falls back to (1) off-TPU or for
     shapes that don't tile.
  3. `paged_attention` — single-token decode attention consuming the
     serving engine's block-paged KV pool through per-slot block tables
     (ray_lightning_tpu.ops.pallas.paged_attention); the XLA reference
     path gathers a dense per-slot view first (identical semantics —
     that copy is exactly what the kernel retires, docs/SERVING.md).
  4. `paged_prefill` — the chunked causal twin for the serving
     engine's prefill lane (ray_lightning_tpu.ops.pallas.paged_prefill):
     a CH-token query chunk per group row against the same pool, which
     retires the prefill lane's per-group gathered view the same way.
(1)/(2) take [B, S, H, D] (batch, seq, heads, head_dim) and support GQA
by repeating KV heads (XLA turns the repeat into a broadcast, no HBM
copy); (3) takes one query token per slot, [C, H, D]; (4) takes the
group's chunk, [B, CH, H, D].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_causal_mask(q_len: int, kv_len: int, q_offset: int = 0) -> jnp.ndarray:
    """Boolean [q_len, kv_len] mask, True = attend. q_offset shifts the
    query positions (used by sequence-parallel shards / decoding)."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return q_pos >= kv_pos


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[B, S, H_kv, D] -> [B, S, H_kv*n_rep, D] for GQA."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d))
    return x.reshape(b, s, h * n_rep, d)


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    mask: jnp.ndarray | None = None,
    q_offset: int = 0,
    scale: float | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Reference SDPA: [B, S, H, D] in, [B, S, H, D] out; f32 softmax.
    ``window``: a query at ``t`` sees the keys in ``(t - window, t]``."""
    if k.shape[2] != q.shape[2]:
        n_rep = q.shape[2] // k.shape[2]
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    # [B, H, S, S]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        cm = make_causal_mask(q.shape[1], k.shape[1], q_offset)
        scores = jnp.where(cm[None, None], scores, -jnp.inf)
    if window is not None:
        q_pos = jnp.arange(q.shape[1])[:, None] + q_offset
        band = q_pos - jnp.arange(k.shape[1])[None, :] < window
        scores = jnp.where(band[None, None], scores, -jnp.inf)
    if mask is not None:
        # mask: [B, S_kv] padding mask or [B, 1, S_q, S_kv]
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        scores = jnp.where(mask, scores, -jnp.inf)
    # Rows with no visible key (fully-padded sequence, or padding ∩ causal
    # leaving nothing) would softmax over all -inf → NaN; emit zeros there.
    any_visible = jnp.isfinite(scores).any(axis=-1, keepdims=True)
    probs = jax.nn.softmax(
        jnp.where(any_visible, scores, 0.0), axis=-1
    ).astype(q.dtype)
    probs = jnp.where(any_visible, probs, 0.0).astype(q.dtype)
    # f32 accumulator over the S_kv extent (numcheck RLT801), one
    # rounding back to the compute dtype — matches the pallas kernel's
    # f32 VMEM accumulator, so the parity gap stays rounding-only
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v,
        preferred_element_type=jnp.float32).astype(q.dtype)


def flash_uses_pallas(q_shape, k_shape, use_pallas: bool | None = None,
                      masked: bool = False) -> bool:
    """Would `flash_attention` take the pallas kernel for these shapes
    and arguments? ONE predicate shared with the dispatch itself so
    callers that must know the outcome (the block-level remat annotation
    in models/llama.py: the pallas path's residuals are saved through the
    kernel's own `remat_opt` hoist, and naming its output again would
    double-save a [B, S, H·hd] tensor per layer) can never drift from
    what actually runs."""
    from ray_lightning_tpu.ops import dispatch

    if masked or not dispatch.use_pallas(use_pallas):
        return False
    from ray_lightning_tpu.ops.pallas.flash import shapes_supported

    return shapes_supported(q_shape, k_shape)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    mask: jnp.ndarray | None = None,
    q_offset: int = 0,
    use_pallas: bool | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Tiled attention. Dispatches to the pallas TPU kernel when on TPU
    (or forced via RLT_PALLAS=1 with interpret mode on CPU) and the shape
    tiles cleanly; otherwise the XLA reference path (which XLA still fuses
    reasonably — flash matters at long S where the S×S scores don't fit).
    ``window`` (static; needs ``causal``): a query at ``t`` sees the keys in
    ``(t - window, t]``; the kernels skip the blocks behind the band."""
    if flash_uses_pallas(q.shape, k.shape, use_pallas,
                         masked=mask is not None):
        from ray_lightning_tpu.ops.pallas.flash import flash_attention_pallas

        return flash_attention_pallas(q, k, v, causal=causal,
                                      q_offset=q_offset, window=window)
    return dot_product_attention(q, k, v, causal=causal, mask=mask,
                                 q_offset=q_offset, window=window)


def flash_attention_on_mesh(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    causal: bool = True,
    use_pallas: bool | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """`flash_attention` for GSPMD-sharded [B, S, H, D] operands.

    XLA refuses to partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so on a mesh of more than one device
    the pallas path runs inside a manual region: batch over the mesh's
    data-parallel axes (`parallel.mesh.dp_axis_names`; replicated when
    the batch does not divide them, e.g. a one-prompt `generate` after
    a sharded fit), heads over ``tensor``, sequence whole (sharded
    sequences go through ring/ulysses). Off-TPU the kernel is
    interpreted into ordinary ops, which would partition on their own;
    they take the same region so the 8-device CPU tests run the program
    the chips run. The XLA reference path and single-device meshes are
    passed through untouched."""
    if (mesh is None or mesh.size == 1
            or not flash_uses_pallas(q.shape, k.shape, use_pallas)):
        return flash_attention(q, k, v, causal=causal,
                               use_pallas=use_pallas, window=window)
    from jax.sharding import PartitionSpec as P

    from ray_lightning_tpu.parallel.mesh import (
        batch_size_divisor,
        dp_axis_names,
    )

    t = mesh.shape.get("tensor", 1)
    if q.shape[2] % t or k.shape[2] % t:
        raise ValueError(
            f"flash attention on a tensor={t} mesh needs n_heads "
            f"({q.shape[2]}) and n_kv_heads ({k.shape[2]}) divisible by "
            "it: the kernel runs per head shard")
    batch_axes = (dp_axis_names(mesh)
                  if q.shape[0] % batch_size_divisor(mesh) == 0 else None)
    spec = P(batch_axes, None, "tensor" if t > 1 else None, None)

    def local(q, k, v):
        return flash_attention(q, k, v, causal=causal,
                               use_pallas=use_pallas, window=window)

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ---- paged decode attention (the serving engine's fused hot op) -----------


@jax.tree_util.register_pytree_node_class
class PagedDecodeView:
    """The decode lane's runtime view of the block-paged KV pool
    (serve/kv_cache.py layout; one entry per slot, all int32):

    ``tables [C, M]`` slot -> pool block ids (0 = reserved scratch);
    ``lengths [C]`` valid cache positions incl. the current token;
    ``write_block/write_offset [C]`` where THIS tick's K/V token lands
    (already scratch-redirected for slots not in the decode phase).

    ``use_pallas`` is STATIC pytree aux, not a leaf: it carries the
    serve engine's build-time dispatch decision through `Llama.apply`
    and the layer scan into `paged_attention`'s call site, so the
    compiled attention can never diverge from what
    `DecodeEngine.attention_path` reports (a trace-time backend
    re-probe could pick differently if, e.g., the jit traces after a
    `force_pallas` context has exited). None defers to the ambient
    dispatch policy.

    ``window_tables [C, M]`` / ``window_write_block [C]`` are the same
    two things for the WINDOW group of a decoder whose sliding-window
    layers keep their K/V in a group of their own (serve/kv_cache.py
    "two groups"): the same logical indexing by ``pos // P``, an entry
    behind the window names scratch block 0. None (no leaf at all) for a
    decoder with one group.

    ``state_moves [C]`` bool, for a decoder whose recurrent layers keep a
    row a slot (serve/kv_cache.py "a row a slot"): the slots whose state
    this tick's token advances; every other slot's stays as it is. None
    for a decoder without."""

    def __init__(self, tables, lengths, write_block, write_offset,
                 window_tables=None, window_write_block=None,
                 state_moves=None, use_pallas: bool | None = None):
        self.tables = tables
        self.lengths = lengths
        self.write_block = write_block
        self.write_offset = write_offset
        self.window_tables = window_tables
        self.window_write_block = window_write_block
        self.state_moves = state_moves
        self.use_pallas = use_pallas

    def tree_flatten(self):
        return ((self.tables, self.lengths, self.write_block,
                 self.write_offset, self.window_tables,
                 self.window_write_block, self.state_moves),
                self.use_pallas)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, use_pallas=aux)


def _gather_blocks(pool, tables, layer):
    """The table-named blocks of one layer: ``pool[tables]`` of a 4-D
    pool, ``pool[layer, tables]`` of the 5-D stack (one gather, the
    layer's pool is never sliced out)."""
    if pool.ndim == 4:
        return pool[tables]
    return pool[layer, tables]


def paged_attention_reference(
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
    """XLA reference with the kernel's exact semantics: gather each
    slot's blocks into a dense [C, M*P, Hkv, hd] view (the copy the
    pallas kernel exists to retire), mask `pad <= kv_pos < length` (and
    `kv_pos >= length - window` under a sliding window), and
    run the shared masked-SDPA reference. Scratch-block garbage and
    table tails are masked to exact softmax zeros, so a longer table
    cannot perturb the visible reduction (the serving numerics
    contract, docs/SERVING.md). A 5-D pool is the stack
    ``[L, n_blocks, P, Hkv, hd]``, gathered at ``layer``."""
    c, h, hd = q.shape
    p, hkv = pool_k.shape[-3:-1]
    m = tables.shape[1]
    k = _gather_blocks(pool_k, tables, layer).reshape(c, m * p, hkv, hd)
    v = _gather_blocks(pool_v, tables, layer).reshape(c, m * p, hkv, hd)
    kv_pos = jnp.arange(m * p)[None, :]
    mask = kv_pos < lengths[:, None]
    if pad is not None:
        mask = mask & (kv_pos >= pad[:, None])
    if window is not None:
        mask = mask & (kv_pos >= lengths[:, None] - window)
    return dot_product_attention(q[:, None], k, v, causal=False,
                                 mask=mask, scale=scale)[:, 0]


@jax.tree_util.register_pytree_node_class
class PagedPrefillView:
    """The prefill lane's runtime view of the block-paged KV pool
    (serve/kv_cache.py layout; one entry per head-group row, all
    int32):

    ``tables [B, M]`` row -> pool block ids (0 = reserved scratch;
    vacant group rows carry an all-scratch table);
    ``write_block/write_offset [B, CH]`` where each of the chunk's CH
    K/V tokens lands (already scratch-redirected for vacant rows) —
    the chunk is scattered into OWNED pool blocks before attention
    runs (write-then-attend, the decode lane's ordering), so the dense
    per-group gathered view never exists on this path.

    ``use_pallas`` is STATIC pytree aux, not a leaf — the same
    baked-dispatch discipline as `PagedDecodeView`: it carries the
    serve engine's build-time decision through `Llama.apply` and the
    layer scan into `paged_prefill`'s call site, so the compiled
    attention can never diverge from what
    `DecodeEngine.prefill_path` reports. None defers to the ambient
    dispatch policy.

    ``window_tables [B, M]`` / ``window_write_block [B, CH]``: the window
    group's, as in `PagedDecodeView`; None for a decoder with one
    group.

    ``state_slot`` (scalar) / ``real_rows [2]``, for a decoder whose
    recurrent layers keep a row a slot: the slot whose state the chunk
    advances, and the first and last of the chunk's rows that are REAL
    (not sent before, not past the prompt's end; ``last = first - 1``:
    none): a recurrence advances on those alone. None for a decoder
    without."""

    def __init__(self, tables, write_block, write_offset,
                 window_tables=None, window_write_block=None,
                 state_slot=None, real_rows=None,
                 use_pallas: bool | None = None):
        self.tables = tables
        self.write_block = write_block
        self.write_offset = write_offset
        self.window_tables = window_tables
        self.window_write_block = window_write_block
        self.state_slot = state_slot
        self.real_rows = real_rows
        self.use_pallas = use_pallas

    def tree_flatten(self):
        return ((self.tables, self.write_block, self.write_offset,
                 self.window_tables, self.window_write_block,
                 self.state_slot, self.real_rows), self.use_pallas)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, use_pallas=aux)


@jax.tree_util.register_pytree_node_class
class PagedJoinedView:
    """A tick's two lanes in ONE model call (serve/engine.py, a decoder
    with `joins_lanes`): the call's ``C + CH`` rows are the ``C`` slots'
    decode tokens, then the prefill chunk's ``CH``.

    ``decode`` is the `PagedDecodeView` of the first ``C`` rows and
    ``prefill`` the `PagedPrefillView` (one group row) of the rest, each
    what its own lane would be handed: the rows part only for attention
    and the K/V writes, every product that reads weights runs once over
    all of them. ``last_row`` (scalar int32) is the chunk row whose logits
    the step keeps (`prefill_last_row`; -1: the prompt continues and the
    row the head reads in its place is discarded), so the head reads
    ``C + 1`` rows and not the chunk's ``CH``. The call's ``pos`` is a
    ``[C + CH]`` vector, each row's cache position."""

    def __init__(self, decode, prefill, last_row):
        self.decode = decode
        self.prefill = prefill
        self.last_row = last_row

    def tree_flatten(self):
        return (self.decode, self.prefill, self.last_row), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def paged_prefill_reference(
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
    """XLA reference with the prefill kernel's exact semantics: gather
    each row's blocks into a dense [B, M*P, Hkv, hd] view (the copy the
    pallas kernel exists to retire), mask
    ``pad[b] <= kv_pos <= pos + j`` (causal against the chunk's cache
    positions), and run the shared masked-SDPA reference. Scratch-block
    garbage, table tails and future in-chunk positions are masked to
    exact softmax zeros; a fully-masked query row (a pad column) emits
    zeros (the serving numerics contract, docs/SERVING.md). A 5-D pool
    is the stack ``[L, n_blocks, P, Hkv, hd]``, gathered at
    ``layer``. A sliding ``window`` adds ``kv_pos > pos + j - window``."""
    b, ch, h, hd = q.shape
    p, hkv = pool_k.shape[-3:-1]
    m = tables.shape[1]
    k = _gather_blocks(pool_k, tables, layer).reshape(b, m * p, hkv, hd)
    v = _gather_blocks(pool_v, tables, layer).reshape(b, m * p, hkv, hd)
    kv_pos = jnp.arange(m * p)[None, None, :]
    q_pos = (pos + jnp.arange(ch))[None, :, None]
    mask = kv_pos <= q_pos
    if window is not None:
        mask = mask & (kv_pos > q_pos - window)
    if pad is not None:
        mask = mask & (kv_pos >= pad[:, None, None])
    else:
        mask = jnp.broadcast_to(mask, (b, ch, m * p))
    return dot_product_attention(q, k, v, causal=False,
                                 mask=mask[:, None], scale=scale)


def paged_prefill_uses_pallas(q_shape, pool_shape,
                              use_pallas: bool | None = None) -> bool:
    """Would `paged_prefill` take the pallas kernel for these shapes?
    ONE predicate shared with the dispatch itself (the
    `paged_attention_uses_pallas` discipline): the serving engine keys
    its fused-vs-reference PREFILL lane on this at build time, and the
    audit/plan legs (`serve/audit.py`) key the per-group gathered-view
    HBM charge on it — so what is charged can never drift from what
    runs."""
    from ray_lightning_tpu.ops import dispatch

    if not dispatch.use_pallas(use_pallas):
        return False
    from ray_lightning_tpu.ops.pallas.paged_prefill import (
        paged_prefill_shapes_supported,
    )

    return paged_prefill_shapes_supported(q_shape, pool_shape)


def paged_prefill(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    tables: jnp.ndarray,
    pos,
    pad: jnp.ndarray | None = None,
    scale: float | None = None,
    use_pallas: bool | None = None,
    layer=0,
    window: int | None = None,
) -> jnp.ndarray:
    """Chunked causal prefill attention over the block-paged KV pool:
    q [B, CH, H, hd], pool [n_blocks, P, Hkv, hd] (or the stack
    [L, n_blocks, P, Hkv, hd] read at ``layer``), tables [B, M],
    pos scalar (chunk token j sits at cache position pos + j) ->
    [B, CH, H, hd]. Dispatches to the fused pallas kernel when on TPU
    (or forced, with interpret mode off-TPU) and the shapes tile;
    otherwise the gathering XLA reference path — identical semantics,
    but the dense per-group view is materialized (and charged by the
    serve planner). ``window`` (static) is a sliding-window layer's: query
    j sees ``pos + j - window < kv_pos <= pos + j``; None = every cached
    token, the program of a full-attention layer."""
    if paged_prefill_uses_pallas(q.shape, pool_k.shape, use_pallas):
        from ray_lightning_tpu.ops.pallas.paged_prefill import (
            paged_prefill_pallas,
        )

        return paged_prefill_pallas(
            q, pool_k, pool_v, tables, pos, pad=pad, scale=scale,
            layer=layer, window=window)
    return paged_prefill_reference(q, pool_k, pool_v, tables, pos,
                                   pad=pad, scale=scale, layer=layer,
                                   window=window)


def paged_attention_uses_pallas(q_shape, pool_shape,
                                use_pallas: bool | None = None) -> bool:
    """Would `paged_attention` take the pallas kernel for these shapes?
    ONE predicate shared with the dispatch itself (the
    `flash_uses_pallas` discipline): the serving engine keys its whole
    fused-vs-reference decode lane on this at build time, and the
    audit/plan legs (`serve/audit.py`) key the gathered-view HBM charge
    on it — so what is charged can never drift from what runs."""
    from ray_lightning_tpu.ops import dispatch

    if not dispatch.use_pallas(use_pallas):
        return False
    from ray_lightning_tpu.ops.pallas.paged_attention import (
        paged_shapes_supported,
    )

    return paged_shapes_supported(q_shape, pool_shape)


def paged_attention(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    pad: jnp.ndarray | None = None,
    scale: float | None = None,
    use_pallas: bool | None = None,
    layer=0,
    window: int | None = None,
) -> jnp.ndarray:
    """Decode attention over the block-paged KV pool: q [C, H, hd],
    pool [n_blocks, P, Hkv, hd] (or the stack
    [L, n_blocks, P, Hkv, hd] read at ``layer``), tables [C, M],
    lengths [C] -> [C, H, hd].
    Dispatches to the fused pallas kernel when on TPU (or
    forced, with interpret mode off-TPU) and the shapes tile; otherwise
    the gathering XLA reference path — identical semantics, but the
    dense per-slot view is materialized (and charged by the serve
    planner). ``window`` (static) is a sliding-window layer's: a slot
    sees ``length - window <= kv_pos < length``; None = every cached
    token, the program of a full-attention layer."""
    if paged_attention_uses_pallas(q.shape, pool_k.shape, use_pallas):
        from ray_lightning_tpu.ops.pallas.paged_attention import (
            paged_attention_pallas,
        )

        return paged_attention_pallas(
            q, pool_k, pool_v, tables, lengths, pad=pad, scale=scale,
            layer=layer, window=window)
    return paged_attention_reference(q, pool_k, pool_v, tables, lengths,
                                     pad=pad, scale=scale, layer=layer,
                                     window=window)


# ---- heads of 64, two a 128-lane row ------------------------------------------
#
# Mosaic slices a pool block out of HBM only where a cached row is whole
# 128-lane tiles (`ops/pallas/paged_attention.py:_copies_in_kernel`), so a
# decoder with heads of 64 keeps TWO KV HEADS SIDE BY SIDE in one row of its
# pool leaf and hands both paged kernels heads of 128: KV heads ``2g`` and
# ``2g + 1`` are the halves of row ``g``, a query head sits in the half of
# its own KV head with zeros in the other (so the 128-wide score is the
# 64-wide one, exactly), and its output is that half of the 128. The caller
# passes the model's ``scale`` (``64 ** -0.5``): the kernels' default would
# be the padded width's.


def pair_kv_heads(x: jnp.ndarray) -> jnp.ndarray:
    """``[.., Hkv, hd]`` -> ``[.., Hkv / 2, 2 hd]``: the same bytes, two
    heads a row."""
    *lead, hkv, hd = x.shape
    return x.reshape(*lead, hkv // 2, 2 * hd)


def _half_of(n_heads: int, n_kv_heads: int) -> jnp.ndarray:
    """[H] 0/1: which half of its paired row query head h reads (its KV
    head ``h // n_rep`` is even or odd)."""
    return (jnp.arange(n_heads) // (n_heads // n_kv_heads)) % 2


def pair_query_heads(q: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
    """``[.., H, hd]`` -> ``[.., H, 2 hd]``: each query head in its KV
    head's half of the lanes, zeros in the other. Paired row ``g`` then
    serves query heads ``[g * 2 n_rep, (g + 1) * 2 n_rep)``: the kernels'
    own head map at ``Hkv / 2`` KV heads."""
    low = (_half_of(q.shape[-2], n_kv_heads) == 0)[:, None]
    zero = jnp.zeros_like(q)
    return jnp.concatenate([jnp.where(low, q, zero),
                            jnp.where(low, zero, q)], axis=-1)


def unpair_heads(out: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
    """``[.., H, 2 hd]`` -> ``[.., H, hd]``: each head's own half (the
    other half is its pair's values under this head's probabilities)."""
    hd = out.shape[-1] // 2
    low = (_half_of(out.shape[-2], n_kv_heads) == 0)[:, None]
    return jnp.where(low, out[..., :hd], out[..., hd:])


# ---- latent attention (MLA) over the latent paged pool -----------------------
#
# One cached row a token a layer, ``[c_kv | k_r]``; every query head reads
# the same row (scores over all ``Dk`` columns, value = the first
# ``value_dim``). The views are the paged lanes' own (`PagedDecodeView`,
# `PagedPrefillView`); the pool is ONE leaf ``[n_blocks, P, Dk]`` or the
# stack ``[L, n_blocks, P, Dk]`` read at ``layer``.


def _gather_latent(pool, tables, layer):
    """A row's blocks as one dense ``[B, M * P, Dk]`` view (the copy the
    kernels exist to retire)."""
    rows = pool[tables] if pool.ndim == 3 else pool[layer, tables]
    b, m, p, dk = rows.shape
    return rows.reshape(b, m * p, dk)


def _latent_sdpa(q, k, visible, value_dim, scale):
    """q [B, R, H, Dk], k [B, T, Dk], visible [B, R, T] -> [B, R, H, Dv];
    float32 scores and softmax, a fully masked query emits zeros."""
    s = jnp.einsum("brhd,btd->brht", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(visible[:, :, None, :], s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(visible[:, :, None, :],
                  jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.where(den == 0.0, 1.0, den)
    return jnp.einsum("brht,btv->brhv", p.astype(k.dtype),
                      k[..., :value_dim],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def mla_decode_reference(q, pool, tables, lengths, value_dim: int,
                         scale: float, layer=0):
    """`jax.numpy` twin of `mla_decode_pallas`: q [C, H, Dk] -> [C, H, Dv],
    slot c sees ``kv_pos < lengths[c]``."""
    k = _gather_latent(pool, tables, layer)
    visible = jnp.arange(k.shape[1])[None, None, :] < lengths[:, None, None]
    return _latent_sdpa(q[:, None], k, visible, value_dim, scale)[:, 0]


def mla_prefill_reference(q, pool, tables, pos, value_dim: int,
                          scale: float, layer=0):
    """`jax.numpy` twin of `mla_prefill_pallas`: q [B, CH, H, Dk], query j
    sees ``kv_pos <= pos + j``."""
    k = _gather_latent(pool, tables, layer)
    q_pos = jnp.asarray(pos, jnp.int32) + jnp.arange(q.shape[1])
    visible = jnp.arange(k.shape[1])[None, None, :] <= q_pos[None, :, None]
    visible = jnp.broadcast_to(visible, (q.shape[0],) + visible.shape[1:])
    return _latent_sdpa(q, k, visible, value_dim, scale)


def mla_uses_pallas(q_shape, pool_shape, value_dim: int,
                    use_pallas: bool | None = None) -> bool:
    """Would `mla_decode` / `mla_prefill` take the pallas kernel for these
    shapes? The one predicate the dispatch and the serving engine's
    build-time lane decision share."""
    from ray_lightning_tpu.ops import dispatch

    if not dispatch.use_pallas(use_pallas):
        return False
    from ray_lightning_tpu.ops.pallas.mla_attention import (
        mla_shapes_supported,
    )

    return mla_shapes_supported(q_shape, pool_shape, value_dim)


def mla_decode(q, pool, tables, lengths, value_dim: int, scale: float,
               use_pallas: bool | None = None, layer=0):
    """Decode attention over the latent pool, q [C, H, Dk] -> [C, H, Dv]:
    the fused kernel on TPU (or forced, interpreted elsewhere) when the
    shapes tile, else the gathering reference."""
    if mla_uses_pallas(q.shape, pool.shape, value_dim, use_pallas):
        from ray_lightning_tpu.ops.pallas.mla_attention import (
            mla_decode_pallas,
        )

        return mla_decode_pallas(q, pool, tables, lengths, value_dim,
                                 scale, layer=layer)
    return mla_decode_reference(q, pool, tables, lengths, value_dim, scale,
                                layer=layer)


def mla_prefill(q, pool, tables, pos, value_dim: int, scale: float,
                use_pallas: bool | None = None, layer=0):
    """Chunked causal prefill attention over the latent pool,
    q [B, CH, H, Dk] -> [B, CH, H, Dv]; dispatch as `mla_decode`."""
    if mla_uses_pallas(q.shape, pool.shape, value_dim, use_pallas):
        from ray_lightning_tpu.ops.pallas.mla_attention import (
            mla_prefill_pallas,
        )

        return mla_prefill_pallas(q, pool, tables, pos, value_dim, scale,
                                  layer=layer)
    return mla_prefill_reference(q, pool, tables, pos, value_dim, scale,
                                 layer=layer)
