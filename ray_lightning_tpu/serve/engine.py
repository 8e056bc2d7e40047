"""The continuous-batching decode engine: ONE jitted step for a serving
replica's whole lifetime.

Shape discipline is the design (docs/SERVING.md): the step is compiled
once for a fixed slot ``capacity``, pool geometry, and prefill chunk
width; admission, retirement, and per-request sampling knobs arrive as
*runtime* int/float arrays, so request churn can never retrace — the
engine pins its own compile count (`compile_count`) and the smoke gate
asserts it stays 1 across a full churned workload.

One step does two things, both masked, both fixed-shape:

  * **decode lane** — for every slot: split its RNG, sample the next
    token from the slot's carried ``last_logits`` (greedy /
    temperature / top-k chosen by *runtime* per-slot values), run the
    model's single-token cache path on the sampled token over the
    slot's gathered paged view, and scatter the new K/V into the pool
    at ``pos``. Slots not in the decode phase are redirected to the
    scratch block and their state is `where`-masked through unchanged.
  * **prefill lane** — at most one slot advances its prompt by one
    fixed-width chunk through the model's chunked cache path
    (``lax.cond``-gated: a step with no admission pays no prefill
    compute). The final chunk also projects the last real prompt row
    through the lm_head into ``last_logits`` — the logits the decode
    lane will sample the first generated token from, exactly where
    single-stream `generate`'s prefill leaves it.

Numerics: every lane reuses the model's OWN cache path (`Llama.apply`
vmapped per slot), the sampling math mirrors `generate`'s per step
(same split sequence, same categorical call shape), and every padded /
scratch position is masked to exact-zero influence before softmax —
per-request token streams are **bitwise-identical** to independent
single-stream `generate` runs on the XLA reference path (test-pinned;
the smoke gate re-proves it on every format.sh run).

HBM: the pool is donated through the step along with ``last_logits``,
so steady-state serving holds one pool, not two. On the **reference
attention path** the decode lane additionally materializes one dense
gathered view per step; on the **fused path**
(`ops.pallas.paged_attention`, selected at build time by
`ops.attention.paged_attention_uses_pallas` — the flash dispatch
discipline) the decode lane consumes the pool directly through the
block tables and that view never exists (`serve/audit.py` prices both
stories in the ``plan --serve`` leg).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.pipeline.compile_cache import enable_persistent_cache
from ray_lightning_tpu.serve.kv_cache import (
    PagedPoolSpec,
    init_pool,
    pool_leaf_shapes,
    pool_partition_spec,
    state_pool_spec,
    validate_pool_tp,
    window_pool_spec,
    window_ring_table,
)
from ray_lightning_tpu.telemetry.spans import annotate


@dataclasses.dataclass(frozen=True)
class DraftConfig:
    """Speculative-decoding knob (docs/SERVING.md "speculative
    decoding"): a small DRAFT model proposes ``k - 1`` greedy tokens
    per tick and the target verifies all ``k`` (the carried token plus
    the proposals) in ONE k-wide chunk riding the same multi-token
    machinery as chunked prefill. Greedy accept/reject keeps the
    emitted stream token-identical to plain greedy decode; ``k = 1``
    degenerates to the base engine (no proposals, one verify row)."""

    #: tokens verified per tick (1 carried + k-1 draft proposals)
    k: int = 4

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"draft k must be >= 1, got {self.k}")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static shape of one serving replica's compiled step."""

    #: concurrent request slots (the decode lane's fixed batch)
    capacity: int = 8
    #: tokens per pool block
    block_size: int = 16
    #: per-slot block-table width — caps prompt + generation length at
    #: ``blocks_per_slot * block_size``
    blocks_per_slot: int = 8
    #: pool blocks (None = dense worst case: capacity * blocks_per_slot
    #: + scratch). Smaller oversubscribes — the paged bet.
    n_blocks: Optional[int] = None
    #: prefill chunk width: one admitting slot advances this many prompt
    #: tokens per step (TTFT = ceil(prompt / chunk) steps + one sample)
    prefill_chunk: int = 32
    #: prefill lane batch (ROADMAP 1d): up to this many queued prompts
    #: advance TOGETHER each tick through the model's left-padded
    #: ragged-batch cache path (`generate(prompt_lengths=...)`'s pad
    #: mechanism): the scheduler admits FIFO groups right-aligned to a
    #: shared chunk-multiple width, each row's pad columns masked out
    #: of attention forever. 1 (default) lowers the identical
    #: historical single-slot program — no pad inputs anywhere.
    prefill_batch: int = 1
    #: speculative decoding (None = the base single-token step). Set,
    #: the engine requires a draft model/params at construction, runs
    #: `build_spec_step`'s k-token verify tick, and the scheduler
    #: enforces greedy-only sampling plus the k-1 slot-overflow
    #: headroom in `validate_request`.
    draft: Optional[DraftConfig] = None

    def __post_init__(self):
        if isinstance(self.draft, dict):
            # survive the dataclasses.asdict round trip the process
            # replica backend ships configs through
            object.__setattr__(self, "draft", DraftConfig(**self.draft))
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if not 1 <= self.prefill_batch <= self.capacity:
            raise ValueError(
                f"prefill_batch {self.prefill_batch} must be within "
                f"[1, capacity={self.capacity}]")
        if self.prefill_chunk > self.blocks_per_slot * self.block_size:
            # the scheduler slides the chunk window back to keep the
            # full width inside the slot; a chunk wider than the slot
            # itself has no valid window at all
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} exceeds "
                f"max_slot_len "
                f"{self.blocks_per_slot * self.block_size}")
        if self.draft is not None and self.prefill_batch != 1:
            raise ValueError(
                "speculative decoding (draft=...) requires "
                "prefill_batch == 1 — the verify chunk rides the "
                "single-slot program")
        if self.draft is not None and \
                self.draft.k > self.blocks_per_slot * self.block_size:
            raise ValueError(
                f"draft k {self.draft.k} exceeds max_slot_len "
                f"{self.blocks_per_slot * self.block_size}")

    @property
    def pool_spec(self) -> PagedPoolSpec:
        n = self.n_blocks
        if n is None:
            n = 1 + self.capacity * self.blocks_per_slot
        return PagedPoolSpec(n_blocks=n, block_size=self.block_size,
                             blocks_per_slot=self.blocks_per_slot)

    @property
    def max_slot_len(self) -> int:
        return self.pool_spec.gathered_len


def _pool_spec(model, cfg: EngineConfig) -> PagedPoolSpec:
    """The configuration's pool with the groups ``model`` declares beside
    the blocks paged on demand: a ring a slot for sliding-window layers, a
    row a slot for recurrent ones, each sized from what the engine has."""
    return state_pool_spec(
        window_pool_spec(cfg.pool_spec, model.kv_window, cfg.capacity,
                         cfg.prefill_chunk), model.slot_state, cfg.capacity)


def _kth_largest(x, k):
    """The ``k``-th largest element of the row ``x`` ``[V]`` for a
    runtime ``k`` in ``[1, V]``, selected, not sorted: the same value as
    ``jnp.sort(x)[::-1][k - 1]`` and ``lax.top_k(x, k)[0][-1]``, ties,
    ``+-inf`` and every ``k`` included, at a cost that does not depend
    on ``k``.

    A float32's bits, with the sign bit flipped for a non-negative and
    all bits flipped for a negative, are a uint32 key in the floats' own
    order. The key of the k-th largest is the largest ``c`` with
    ``count(keys >= c) >= k``, and is settled one bit a pass from the
    most significant down: a pass is one compare and one row sum, no
    gather, no scatter, no shape that depends on the data. (More bits a
    pass, three or fifteen counts a read, were slower on the chip:
    PERF.md section 6, PR 32.)

    ``-0.0`` and ``0.0`` are two keys and one value: where the k-th
    largest is a zero its sign may differ from the sort's (which orders
    them by position), and ``x >= kth`` does not. A NaN has no order: one
    with the sign bit clear ranks above ``+inf`` (where the sort puts
    it), one with it set below ``-inf``; a row that holds one has no
    meaningful draw either way."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    top = jnp.uint32(1 << 31)
    keys = jnp.where(bits >= top, ~bits, bits | top)

    def settle(i, found):
        trial = found | (top >> i.astype(jnp.uint32))
        return jnp.where(jnp.sum(keys >= trial) >= k, trial, found)

    key = jax.lax.fori_loop(0, 32, settle, jnp.uint32(0))
    return jax.lax.bitcast_convert_type(
        jnp.where(key >= top, key ^ top, ~key), jnp.float32).astype(x.dtype)


def _sample_one(logits, key, temp, top_k):
    """Per-slot sampling, runtime-switched, mirroring `generate`'s
    static-python `sample` bit for bit per mode:

      * temp == 0      -> argmax (the categorical draw is computed and
                          discarded — fixed shapes beat a branch)
      * top_k > 0      -> k-th-largest threshold filter; the threshold
                          VALUE, selected by `_kth_largest` for the
                          runtime k (clipped to ``[1, V]``), equals
                          ``lax.top_k(x, k)[0][:, -1]``: no sort of the
                          vocabulary, no bound on k
      * else           -> plain temperature sampling

    The categorical call takes ``[1, V]`` exactly like `generate`'s
    B=1 call so the drawn bits match under vmap."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temp, jnp.finfo(logits.dtype).tiny)
    kth = _kth_largest(scaled, jnp.clip(top_k, 1, scaled.shape[0]))
    filtered = jnp.where(scaled >= kth, scaled, -jnp.inf)
    sampled_from = jnp.where(top_k > 0, filtered, scaled)
    drawn = jax.random.categorical(
        key, sampled_from[None, :])[0].astype(jnp.int32)
    return jnp.where(temp == 0.0, greedy, drawn)


def joins_lanes(model, cfg: EngineConfig, fused: bool,
                fused_prefill: bool) -> bool:
    """Does a tick that carries a chunk go through ``model`` in ONE call
    (`build_step`)? Where the decoder says it serves a joined view
    (`joins_lanes`), both lanes are paged and the chunk is one slot's:
    what the engine can observe, no option."""
    return bool(getattr(model, "joins_lanes", False) and fused
                and fused_prefill and cfg.prefill_batch == 1)


def build_step(model, cfg: EngineConfig, fused: bool = False,
               fused_prefill: bool = False):
    """The jitted continuous-batching step for ``model`` (a decoder of
    `models.serving.serving_model`) under ``cfg``. Returned uncompiled —
    `DecodeEngine` jits it with the pool/logits donated; `serve.audit`
    traces it abstractly.

    The model declares its pool: `model.cfg.pool_leaf_shapes(n_blocks,
    block_size)` names the leaves (Llama: K and V ``[L, n_blocks, P,
    Hkv, hd]``; a latent-attention decoder: one leaf ``[L, n_blocks, P,
    row]``), and the step takes and returns them in that order between
    ``params`` and ``last_logits``. On the fused lanes the model is
    handed the leaves whole (``cache=pool``) beside a paged view and
    hands them back. A model with `tick_counters` returns their values
    from a paged call too; the step joins the two lanes' as each
    counter says (sum / max, or the union of a bitset, counted once the
    lanes are joined: `_join_words`) and returns them after ``emitted``;
    the step that joins its lanes (below) returns the ONE call's, which
    already hold both lanes' rows.
    A decoder with sliding-window layers (`model.kv_window`) has a second
    group of leaves, a ring a slot (`serve/kv_cache.py` "two groups"): the step
    makes that group's table rows from the positions it holds
    (`window_ring_table`) and hands them to the model in the same views. A
    decoder with recurrent layers (`model.slot_state`) has leaves that hold
    a row a slot (`serve/kv_cache.py` "a row a slot"): the step tells it,
    from the positions it already holds, which rows of the prefill chunk
    are real and whose state the decode lane moves, and keeps the K/V of a
    row that is not real out of the attention group.

    Which step a decoder gets (`joins_lanes`). A tick's products read
    their weights whatever the rows, so a tick that calls the model once
    for the C decode rows and again for the chunk reads them twice (63%
    of the dense docs step was the second read: PERF.md section 6, PR
    45). Where the decoder serves a `PagedJoinedView` (its
    ``joins_lanes``), both lanes are paged and the chunk is one slot's
    (``prefill_batch == 1``), the step is ``sample``, then ONE model call:
    over ``C + CH`` rows in a tick that carries a chunk, each lane's view
    what its own call would be handed, and the decode lane's call alone
    in a tick without. A counting decoder's counts ride the two passes'
    conds (zeros in; the pass that runs puts its own in their place).
    Same arguments, results and donated buffers as the two-pass step,
    which every other case keeps: the reference lanes (the
    bitwise anchor against `generate()`), a batched prefill group, the
    speculative step, and the decoders that have no joined branch yet
    (their programs are untouched: `scripts/step_jaxpr_same.py`). The
    choice is read off the model and the build's own arguments: no
    option selects it. The two-pass path goes when the last decoder has
    joined (ROADMAP Queue 1 item 1).

    ``fused`` selects the decode lane at BUILD time (the dispatch
    decision is static, like a kernel choice — it can never retrace):

      * False — the reference lane: the model's single-token cache path
        vmapped per slot over a dense gathered view of each slot's
        blocks. The bitwise anchor against single-stream `generate()`.
      * True — the fused lane: ONE batched model call whose cache is
        the pool itself (`models.llama` paged branch +
        `ops.attention.paged_attention`); the per-slot dense view is
        never materialized. Pinned to the reference lane within the
        flash kernel's tolerance discipline (tests/test_paged_attention).

    On the fused lanes the donated stack ``[L, n_blocks, P, Hkv, hd]``
    goes into `Llama.__call__` whole and comes back whole: the model
    carries it through its layer scan, writes a tick's K/V rows at
    ``[layer, block, offset]`` and hands the kernels the stack with the
    layer index, so the step moves token rows and table-named tiles,
    never a layer's pool (docs/SERVING.md "How the pool passes through
    the step"; tests/test_tpu_aot_compile.py pins the v5e compile).

    ``fused_prefill`` selects the PREFILL lane the same way
    (independently — the two kernels have separate shape gates):

      * False — the reference lane: gather the group's blocks into a
        dense ``[L, B, G, Hkv, hd]`` view and run the model's chunked
        cache path over it (the historical program).
      * True — the fused lane: the model's paged-prefill branch
        scatters the chunk's K/V straight into owned pool blocks
        (scratch-redirected for vacant rows) and
        `ops.attention.paged_prefill` attends causally through the
        block tables — the per-group gather never exists
        (tests/test_paged_prefill).
    """
    mcfg = model.cfg
    window = model.kv_window
    slot_state = model.slot_state
    spec = _pool_spec(model, cfg)
    C, P, G, CH = cfg.capacity, spec.block_size, spec.gathered_len, \
        cfg.prefill_chunk
    B = cfg.prefill_batch
    n_pool = len(pool_leaf_shapes(mcfg, spec))
    counters = tuple(model.tick_counters)
    tiled_decode = model.decode_tile_tokens(
        spec.block_size, cfg.blocks_per_slot) is not None
    joined = joins_lanes(model, cfg, fused, fused_prefill)
    if not (fused and fused_prefill):
        # the reference lanes gather a dense K/V view: Llama's cache path
        L, HKV, HD = mcfg.n_layers, mcfg.n_kv_heads, mcfg.head_dim

    def _paged_apply(params, tokens, pool, pos, pad, view):
        # the model's paged call: (logits, pool) and, where the model
        # counts, its counters' values
        out = model.apply({"params": params}, tokens, cache=pool, pos=pos,
                          pad=pad, paged=view)
        return out[0], tuple(out[1]), (out[2] if counters else None)

    unions = any(c[1] == "union" for c in counters)
    # int32 words of one paged call's counts (a bitset takes its own)
    n_count_words = sum(span.stop - span.start
                        for _, span in _counter_spans(counters))

    def _join(a, b):
        # a tick's two lanes' counts, each as its counter says
        if unions:
            return _join_words(counters, a, b)
        return jnp.stack([a[i] + b[i] if how == "sum"
                          else jnp.maximum(a[i], b[i])
                          for i, (_, how) in enumerate(counters)])

    def _counted(counts):
        # a paged call's counts as the step carries them: none where the
        # decoder has no `tick_counters`
        return (counts,) if counters else ()

    def _settled(counted):
        # a tick's counts as the step returns them, after ``emitted``
        if unions:
            return (_settle_words(counters, counted[0]),)
        return counted

    def _decode_one(params, tok, kc, vc, pos):
        # the model's OWN single-token cache path ([1, 1] batch), new
        # K/V extracted at the write position for the pool scatter
        logits, (nk, nv) = model.apply(
            {"params": params}, tok[None, None],
            cache=(kc[:, None], vc[:, None]), pos=pos)
        k_tok = jax.lax.dynamic_slice_in_dim(nk[:, 0], pos, 1,
                                             axis=1)[:, 0]
        v_tok = jax.lax.dynamic_slice_in_dim(nv[:, 0], pos, 1,
                                             axis=1)[:, 0]
        return logits[0, 0], k_tok, v_tok

    def _decode_one_padded(params, tok, kc, vc, pos, pad):
        # the left-pad-aware twin (prefill_batch > 1): same program
        # with the model's pad mask/RoPE shift live (pad == 0 rows
        # compute bitwise-identically to `_decode_one`)
        logits, (nk, nv) = model.apply(
            {"params": params}, tok[None, None],
            cache=(kc[:, None], vc[:, None]), pos=pos, pad=pad[None])
        k_tok = jax.lax.dynamic_slice_in_dim(nk[:, 0], pos, 1,
                                             axis=1)[:, 0]
        v_tok = jax.lax.dynamic_slice_in_dim(nv[:, 0], pos, 1,
                                             axis=1)[:, 0]
        return logits[0, 0], k_tok, v_tok

    def _write_index(tables, pos, decoding):
        # where this tick's K/V token lands; slots not in the decode
        # phase are redirected to the scratch block
        bi = jnp.where(
            decoding,
            jnp.take_along_axis(tables, (pos // P)[:, None],
                                axis=1)[:, 0],
            0)
        off = jnp.where(decoding, pos % P, 0)
        return bi, off

    def _decode_reference(params, pool, tables, pos, decoding,
                          emitted, slot_pad):
        # one dense gathered view per step — the copy the fused lane
        # retires (charged by serve_memory_summary on this path only)
        pool_k, pool_v = pool
        with jax.named_scope("kv_pool"):
            gk = pool_k[:, tables].reshape(L, C, G, HKV, HD)
            gv = pool_v[:, tables].reshape(L, C, G, HKV, HD)
        if slot_pad is None:
            logits2, k_tok, v_tok = jax.vmap(
                _decode_one, in_axes=(None, 0, 1, 1, 0),
                out_axes=(0, 1, 1),
            )(params, emitted, gk, gv, pos)
        else:
            logits2, k_tok, v_tok = jax.vmap(
                _decode_one_padded, in_axes=(None, 0, 1, 1, 0, 0),
                out_axes=(0, 1, 1),
            )(params, emitted, gk, gv, pos, slot_pad)
        with jax.named_scope("kv_pool"):
            bi, off = _write_index(tables, pos, decoding)
            pool_k = pool_k.at[:, bi, off].set(k_tok)
            pool_v = pool_v.at[:, bi, off].set(v_tok)
        return (pool_k, pool_v), logits2, None

    def _decode_view(tables, pos, decoding):
        # what the model's paged decode branch is told of the C slots
        from ray_lightning_tpu.ops.attention import PagedDecodeView

        bi, off = _write_index(tables, pos, decoding)
        lengths = pos + 1
        if tiled_decode:
            # a slot that is not decoding asks for nothing: its row is
            # discarded below (`where(decoding, ...)`), and at length 0
            # the kernel gives it no tile, so an idle slot costs no
            # fetch and the slot being prefilled is not read twice
            lengths = jnp.where(decoding, lengths, 0)
        # use_pallas=True (static aux) bakes the build-time decision
        # into the program: fused=True MEANS the kernel, wherever and
        # whenever the jit happens to trace (the shape gate already
        # passed at DecodeEngine init)
        ring = {}
        if window is not None:
            # the window group's rows: the blocks that hold a position the
            # slot's query sees, [pos + 1 - window, pos]
            wtab = jnp.where(decoding[:, None], window_ring_table(
                spec, jnp.arange(C), jnp.maximum(pos + 1 - window, 0), pos),
                0)
            ring = dict(window_tables=wtab,
                        window_write_block=_write_index(wtab, pos,
                                                        decoding)[0])
        if slot_state:
            # a slot that is idle or being prefilled keeps its state
            ring["state_moves"] = decoding
        return PagedDecodeView(tables=tables, lengths=lengths,
                               write_block=bi, write_offset=off,
                               use_pallas=True, **ring)

    def _decode_fused(params, pool, tables, pos, decoding,
                      emitted, slot_pad):
        # the fused lane: the pool IS the cache — the model's paged
        # branch scatters the new K/V at the (scratch-redirected) write
        # index and `paged_attention` streams block-table-named tiles,
        # so no [L, C, G, Hkv, hd] copy exists on this path
        view = _decode_view(tables, pos, decoding)
        logits2, pool, counts = _paged_apply(
            params, emitted[:, None], pool, pos, slot_pad, view)
        return pool, logits2[:, 0], counts

    _decode = _decode_fused if fused else _decode_reference

    @jax.named_scope("sample")
    def _sample(last_logits, decoding, temp, top_k, rngs):
        keys = jax.random.wrap_key_data(rngs)
        split = jax.vmap(jax.random.split)(keys)
        nxt, sub = split[:, 0], split[:, 1]
        # RNG advances exactly once per EMITTED token (generate's body
        # splits once per loop trip) — idle/prefilling slots hold still
        new_rngs = jnp.where(decoding[:, None],
                             jax.random.key_data(nxt), rngs)
        emitted = jax.vmap(_sample_one)(last_logits, sub, temp, top_k)
        return emitted, new_rngs

    def _prefill_reference(params, pool, rows, n, tokens, prefill_pos,
                           pad):
        # gather the blocks of the group's ``n`` rows into a dense
        # [L, n, G, Hkv, hd] view and run the model's chunked cache path
        # over it (B = 1 takes the historical single-slot program, no
        # pad anywhere)
        pool_k, pool_v = pool
        with jax.named_scope("kv_pool"):
            kc = pool_k[:, rows].reshape(L, n, G, HKV, HD)
            vc = pool_v[:, rows].reshape(L, n, G, HKV, HD)
        logits, (nk, nv) = model.apply(
            {"params": params}, tokens, cache=(kc, vc), pos=prefill_pos,
            pad=pad)
        return logits, nk, nv

    def _chunk_view(slot, row, pos, prefill_pos, prefill_last_row):
        # what the model's paged prefill branch is told of the one chunk
        # (B == 1) that ``slot``, whose table row is ``row``, takes. The
        # pool IS the cache: the branch scatters the CH-wide chunk at the
        # table-named write indices and `paged_prefill` streams block
        # tiles, so the [L, 1, G, Hkv, hd] gather never exists. The full
        # CH-wide write stays safe past a partial tail chunk for the same
        # reason as the reference lane: tail garbage lands in OWNED blocks
        # and is overwritten before any mask exposes it.
        from ray_lightning_tpu.ops.attention import PagedPrefillView

        wpos = prefill_pos + jnp.arange(CH)
        ring = {}
        if window is not None:
            # the window group's row: the blocks the chunk writes and its
            # first row sees back to
            wrow = window_ring_table(
                spec, slot, jnp.maximum(prefill_pos - window + 1, 0),
                prefill_pos + CH - 1)
            ring = dict(window_tables=wrow,
                        window_write_block=wrow[:, wpos // P])
        tables1, wblock = row[None], row[wpos // P][None]
        if slot_state:
            # the chunk's REAL rows: from the slot's first unsent position
            # (the scheduler may have slid the window back over rows it
            # sent before) to the prompt's last (zeros follow it). A
            # recurrence advances on those alone, and a row sent before
            # computes its K/V from a state that has moved on: it goes to
            # the scratch block, the first stays
            first = jnp.clip(pos[slot] - prefill_pos, 0, CH)
            last = jnp.where(prefill_last_row >= 0, prefill_last_row,
                             CH - 1)
            chunk_rows = jnp.arange(CH)[None]
            wblock = jnp.where((chunk_rows >= first)
                               & (chunk_rows <= last), wblock, 0)
            ring.update(state_slot=slot,
                        real_rows=jnp.stack([first, last]))
        return PagedPrefillView(
            tables=tables1, write_block=wblock,
            write_offset=(wpos % P)[None], use_pallas=True, **ring)

    if B == 1:
        def step(params, *args):
            """One engine tick: ``step(params, *pool, last_logits,
            tables, pos, decoding, temp, top_k, rngs, prefill_slot,
            prefill_tokens, prefill_pos, prefill_last_row)``. Donated:
            the pool's leaves and last_logits (`DecodeEngine` owns
            them).

            Host-owned runtime inputs (plain numpy per call):
              tables   [C, M] i32   slot -> pool block ids (0 = scratch)
              pos      [C]    i32   tokens written to each slot's cache
              decoding [C]    bool  slot is in the decode phase
              temp     [C]    f32 / top_k [C] i32 / rngs [C, 2] u32
              prefill_slot  i32     slot taking this step's chunk (-1
                                    none)
              prefill_tokens [CH] i32 / prefill_pos i32
              prefill_last_row i32  row of the last REAL prompt token
                                    within this chunk (-1: prompt
                                    continues)

            Returns (*pool, last_logits, rngs', emitted [C] i32) and,
            for a model that counts, counts [n] i32 after them.
            ``emitted[s]`` is meaningful only where ``decoding[s]`` —
            the scheduler masks by its own phase bookkeeping.
            """
            pool = tuple(args[:n_pool])
            (last_logits, tables, pos, decoding, temp, top_k, rngs,
             prefill_slot, prefill_tokens, prefill_pos,
             prefill_last_row) = args[n_pool:]
            # ---- decode lane: sample, then advance every slot --------
            emitted, new_rngs = _sample(last_logits, decoding, temp,
                                        top_k, rngs)
            if joined:
                def decode_lane(*carried):
                    # a tick WITHOUT a chunk: the decode lane alone
                    pool, logits2, counts = _decode_fused(
                        params, carried[:n_pool], tables, pos, decoding,
                        emitted, None)
                    return (*pool, jnp.where(decoding[:, None], logits2,
                                             carried[n_pool]),
                            *_counted(counts))

                def both_lanes(*carried):
                    # a tick WITH a chunk: the C decode rows and the CH
                    # rows of the chunk in ONE model call, each lane's view
                    # what its own call would be handed. The model parts
                    # the rows only for attention and the K/V writes, and
                    # its head reads the decode rows and the one row of
                    # the chunk that `prefill_last_row` keeps.
                    from ray_lightning_tpu.ops.attention import (
                        PagedJoinedView,
                    )

                    slot = jnp.maximum(prefill_slot, 0)
                    view = PagedJoinedView(
                        _decode_view(tables, pos, decoding),
                        _chunk_view(slot, tables[slot], pos, prefill_pos,
                                    prefill_last_row), prefill_last_row)
                    logits, pool, counts = _paged_apply(
                        params,
                        jnp.concatenate([emitted, prefill_tokens])[None],
                        carried[:n_pool],
                        jnp.concatenate([pos,
                                         prefill_pos + jnp.arange(CH)]),
                        None, view)
                    last_logits = jnp.where(decoding[:, None],
                                            logits[0, :C], carried[n_pool])
                    # the slot that took the chunk was not decoding: where
                    # the chunk ended its prompt, the kept row is what its
                    # first token is drawn from
                    last_logits = jnp.where(
                        (jnp.arange(C) == slot)[:, None]
                        & (prefill_last_row >= 0),
                        logits[0, C][None, :], last_logits)
                    return (*pool, last_logits, *_counted(counts))

                # one of the two runs, each the TRUE branch of a cond of its
                # own beside one that passes the pool through: XLA orders a
                # conditional's branch 0 before its branch 1 when it looks
                # for a buffer's last reader, so a pass that wrote the pool
                # in place from branch 0 of ONE cond over both passes would
                # copy every layer's stack in and out
                # (tests/test_tpu_aot_compile.py pins the compile)
                # (a decoder that counts returns the one call's counts: the
                # pass that runs puts them in place of the zeros)
                no_counts = (jnp.zeros((n_count_words,), jnp.int32),
                             ) if counters else ()
                carried = jax.lax.cond(
                    prefill_slot >= 0, both_lanes, lambda *a: a,
                    *pool, last_logits, *no_counts)
                carried = jax.lax.cond(
                    prefill_slot < 0, decode_lane, lambda *a: a, *carried)
                return (*carried[:n_pool + 1], new_rngs, emitted,
                        *_settled(carried[n_pool + 1:]))
            pool, logits2, counts = _decode(
                params, pool, tables, pos, decoding, emitted, None)
            last_logits = jnp.where(decoding[:, None], logits2,
                                    last_logits)

            # ---- prefill lane: one chunk for one admitting slot ------
            def do_prefill(*carried):
                pool, last_logits = carried[:n_pool], carried[n_pool]
                slot = jnp.maximum(prefill_slot, 0)
                row = tables[slot]
                if fused_prefill:
                    view = _chunk_view(slot, row, pos, prefill_pos,
                                       prefill_last_row)
                    logits, pool, pf_counts = _paged_apply(
                        params, prefill_tokens[None], pool, prefill_pos,
                        None, view)
                else:
                    pool_k, pool_v = pool
                    logits, nk, nv = _prefill_reference(
                        params, pool, row, 1, prefill_tokens[None],
                        prefill_pos, None)
                    kw = jax.lax.dynamic_slice_in_dim(
                        nk[:, 0], prefill_pos, CH, axis=1)
                    vw = jax.lax.dynamic_slice_in_dim(
                        nv[:, 0], prefill_pos, CH, axis=1)
                    # the full CH-wide write is safe past a partial tail
                    # chunk: positions >= prompt_len hold garbage the
                    # decode lane overwrites before any mask ever
                    # exposes them
                    with jax.named_scope("kv_pool"):
                        wpos = prefill_pos + jnp.arange(CH)
                        wbi = row[wpos // P]
                        pool = (pool_k.at[:, wbi, wpos % P].set(kw),
                                pool_v.at[:, wbi, wpos % P].set(vw))
                done_row = logits[0, prefill_last_row]
                finished = prefill_last_row >= 0
                last_logits = jnp.where(
                    (jnp.arange(C) == slot)[:, None] & finished,
                    done_row[None, :], last_logits)
                if counters:
                    return (*pool, last_logits,
                            _join(carried[-1], pf_counts))
                return (*pool, last_logits)

            carried = (*pool, last_logits, *_counted(counts))
            carried = jax.lax.cond(
                prefill_slot >= 0, do_prefill, lambda *a: a, *carried)
            return (*carried[:n_pool + 1], new_rngs, emitted,
                    *_settled(carried[n_pool + 1:]))

        return step

    def step(params, *args):
        """The batched-prefill twin (prefill_batch > 1): ``step(params,
        *pool, last_logits, tables, pos, decoding, temp, top_k, rngs,
        slot_pad, prefill_slots, prefill_tokens, prefill_pos,
        prefill_last_row, prefill_pad)``. Extra runtime inputs over the
        single-slot step:

          slot_pad [C] i32      per-slot left pad (0 once unpadded) —
                                the decode lanes mask pad columns and
                                shift RoPE exactly like
                                `generate(prompt_lengths=...)`
          prefill_slots [B] i32 the head FIFO group's slots (-1 =
                                vacant row, scratch-redirected)
          prefill_tokens [B, CH] i32  this chunk of the group's
                                LEFT-PADDED prompts (right-aligned to
                                the shared chunk-multiple width)
          prefill_pos i32       the group's shared cache write offset
          prefill_last_row i32  in-chunk column of every row's last
                                real token (-1: prompts continue; the
                                right-alignment makes it shared)
          prefill_pad [B] i32   per-row left pad within the group
        """
        pool = tuple(args[:n_pool])
        (last_logits, tables, pos, decoding, temp, top_k, rngs, slot_pad,
         prefill_slots, prefill_tokens, prefill_pos, prefill_last_row,
         prefill_pad) = args[n_pool:]
        emitted, new_rngs = _sample(last_logits, decoding, temp, top_k,
                                    rngs)
        pool, logits2, _ = _decode(
            params, pool, tables, pos, decoding, emitted, slot_pad)
        last_logits = jnp.where(decoding[:, None], logits2, last_logits)

        # ---- prefill lane: one chunk for the head FIFO group ---------
        def do_prefill(*carried):
            pool, last_logits = carried[:n_pool], carried[n_pool]
            slots = jnp.maximum(prefill_slots, 0)
            active = prefill_slots >= 0
            rows = jnp.where(active[:, None], tables[slots], 0)
            wpos = prefill_pos + jnp.arange(CH)
            if fused_prefill:
                # the fused lane: the group's left-padded chunk is
                # scattered straight into owned pool blocks (vacant
                # rows carry all-scratch tables — their writes and
                # reads land in masked block 0) and `paged_prefill`
                # attends causally through the tables; the
                # [L, B, G, Hkv, hd] per-group gather never exists on
                # this path. Pad columns land real K/V in owned blocks
                # exactly as on the reference lane — masked out of
                # every attention forever.
                from ray_lightning_tpu.ops.attention import (
                    PagedPrefillView,
                )

                view = PagedPrefillView(
                    tables=rows, write_block=rows[:, wpos // P],
                    write_offset=jnp.broadcast_to(wpos % P, (B, CH)),
                    use_pallas=True)
                logits, pool, _ = _paged_apply(
                    params, prefill_tokens, pool, prefill_pos,
                    prefill_pad, view)
            else:
                pool_k, pool_v = pool
                logits, nk, nv = _prefill_reference(
                    params, pool, rows, B, prefill_tokens, prefill_pos,
                    prefill_pad)
                kw = jax.lax.dynamic_slice_in_dim(nk, prefill_pos, CH,
                                                  axis=2)
                vw = jax.lax.dynamic_slice_in_dim(nv, prefill_pos, CH,
                                                  axis=2)
                # pad columns land real K/V in owned blocks; they are
                # masked out of every attention forever (the model's
                # pad contract), so like partial-tail garbage they can
                # never reach an unmasked reduction
                with jax.named_scope("kv_pool"):
                    wbi = rows[:, wpos // P]
                    woff = jnp.broadcast_to(wpos % P, (B, CH))
                    pool = (pool_k.at[:, wbi, woff].set(kw),
                            pool_v.at[:, wbi, woff].set(vw))
            done = active & (prefill_last_row >= 0)
            done_rows = logits[:, prefill_last_row]      # [B, V]
            # scatter each finished row's logits into its slot via a
            # one-hot contraction: vacant rows map to slot -1 (never
            # matches), and <= 1 row per slot makes the sum exact
            sel = (jnp.arange(C)[:, None]
                   == jnp.where(done, slots, -1)[None, :])
            contrib = sel.astype(done_rows.dtype) @ done_rows
            last_logits = jnp.where(sel.any(axis=1)[:, None], contrib,
                                    last_logits)
            return (*pool, last_logits)

        carried = jax.lax.cond(
            jnp.any(prefill_slots >= 0), do_prefill, lambda *a: a,
            *pool, last_logits)
        return (*carried, new_rngs, emitted)

    return step


def build_spec_step(model, draft_model, cfg: EngineConfig):
    """The speculative-decoding twin of `build_step` (single-slot
    prefill lane only; reference attention lanes only — the verify
    chunk and the draft's gathered view are priced honestly by
    `serve.audit.speculative_plan`).

    Per tick, per decoding slot, with ``k = cfg.draft.k``:

      1. ``t0 = sample(last_logits)`` — the SAME `_sample` trip as the
         base step (greedy when temp == 0; the scheduler enforces
         greedy-only for draft-armed engines).
      2. The DRAFT model runs ``k`` single-token feedback steps over
         its own paged pool (same block tables), feeding
         ``[t0, d1..d_{k-1}]`` and writing draft K/V at positions
         ``pos..pos+k-1`` — so at full acceptance the draft cache is
         complete through the last accepted position. The k-th greedy
         proposal is discarded.
      3. The TARGET verifies the whole chunk ``[t0, d1..d_{k-1}]`` in
         ONE k-wide call through its chunked cache path (the same
         dense mid-sequence branch chunked prefill rides), writing
         target K/V at ``pos..pos+k-1`` and producing logits
         ``l_0..l_{k-1}`` where ``g_{j+1} = argmax(l_j)`` is the token
         plain greedy decode would emit after position ``pos+j``.
      4. Greedy accept: ``m`` = longest prefix with ``d_j == g_j``
         (cumprod of the match mask). The slot emits
         ``[t0, g_1..g_m]`` (``n_emit = 1 + m``) and carries
         ``last_logits = l_m`` so ``g_{m+1}`` becomes the NEXT tick's
         ``t0`` — emitted exactly once. K/V written past ``pos+m`` is
         conditioned on rejected tokens; it is causally masked
         (kv_pos <= q_pos) and overwritten before the stream ever
         reaches it, the same partial-tail-garbage discipline as
         chunked prefill. ``k = 1`` reduces to the base step's math
         exactly (no proposals, one verify row, ``m = 0``).

    Returns ``(pool_k, pool_v, dpool_k, dpool_v, last_logits, rngs',
    toks [C, k] i32, n_emit [C] i32)``.
    """
    assert cfg.prefill_batch == 1 and cfg.draft is not None
    mcfg, dcfg = model.cfg, draft_model.cfg
    spec = cfg.pool_spec
    L, HKV, HD = mcfg.n_layers, mcfg.n_kv_heads, mcfg.head_dim
    DL, DHKV, DHD = dcfg.n_layers, dcfg.n_kv_heads, dcfg.head_dim
    C, P, G, CH = cfg.capacity, spec.block_size, spec.gathered_len, \
        cfg.prefill_chunk
    K = cfg.draft.k

    def _draft_one(dparams, tok, kc, vc, pos):
        logits, (nk, nv) = draft_model.apply(
            {"params": dparams}, tok[None, None],
            cache=(kc[:, None], vc[:, None]), pos=pos)
        k_tok = jax.lax.dynamic_slice_in_dim(nk[:, 0], pos, 1,
                                             axis=1)[:, 0]
        v_tok = jax.lax.dynamic_slice_in_dim(nv[:, 0], pos, 1,
                                             axis=1)[:, 0]
        return logits[0, 0], k_tok, v_tok

    def _verify_one(params, toks, kc, vc, pos):
        # the target's K-wide chunk through its own chunked cache path
        # — the multi-token-advance machinery chunked prefill built
        logits, (nk, nv) = model.apply(
            {"params": params}, toks[None],
            cache=(kc[:, None], vc[:, None]), pos=pos)
        kw = jax.lax.dynamic_slice_in_dim(nk[:, 0], pos, K, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(nv[:, 0], pos, K, axis=1)
        return logits[0], kw, vw

    def step(params, dparams, pool_k, pool_v, dpool_k, dpool_v,
             last_logits, tables, pos, decoding, temp, top_k, rngs,
             prefill_slot, prefill_tokens, prefill_pos,
             prefill_last_row):
        """One speculative tick. Donated: both pools + last_logits
        (positions 2-6). Runtime inputs as in the base step."""
        # ---- t0: the carried token, sampled exactly like the base ---
        with jax.named_scope("sample"):
            keys = jax.random.wrap_key_data(rngs)
            split = jax.vmap(jax.random.split)(keys)
            new_rngs = jnp.where(decoding[:, None],
                                 jax.random.key_data(split[:, 0]), rngs)
            t0 = jax.vmap(_sample_one)(last_logits, split[:, 1], temp,
                                       top_k)

        # ---- draft lane: K feedback trips over the draft pool --------
        def propose(carry, _):
            dpk, dpv, tok, off = carry
            gk = dpk[:, tables].reshape(DL, C, G, DHKV, DHD)
            gv = dpv[:, tables].reshape(DL, C, G, DHKV, DHD)
            wp = pos + off
            dlogits, k_tok, v_tok = jax.vmap(
                _draft_one, in_axes=(None, 0, 1, 1, 0),
                out_axes=(0, 1, 1),
            )(dparams, tok, gk, gv, wp)
            bi = jnp.where(
                decoding,
                jnp.take_along_axis(tables, (wp // P)[:, None],
                                    axis=1)[:, 0],
                0)
            woff = jnp.where(decoding, wp % P, 0)
            dpk = dpk.at[:, bi, woff].set(k_tok)
            dpv = dpv.at[:, bi, woff].set(v_tok)
            nxt = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
            return (dpk, dpv, nxt, off + 1), tok

        (dpool_k, dpool_v, _, _), chunk = jax.lax.scan(
            propose, (dpool_k, dpool_v, t0, jnp.int32(0)), None,
            length=K)
        chunk = jnp.moveaxis(chunk, 0, 1)   # [C, K] = [t0, d1..d_{K-1}]

        # ---- verify lane: ONE K-wide target chunk per slot -----------
        gk = pool_k[:, tables].reshape(L, C, G, HKV, HD)
        gv = pool_v[:, tables].reshape(L, C, G, HKV, HD)
        vlogits, kw, vw = jax.vmap(
            _verify_one, in_axes=(None, 0, 1, 1, 0), out_axes=(0, 1, 1),
        )(params, chunk, gk, gv, pos)        # [C, K, V], [L, C, K, ...]
        wp = pos[:, None] + jnp.arange(K)[None, :]          # [C, K]
        bi = jnp.where(decoding[:, None],
                       jnp.take_along_axis(tables, wp // P, axis=1), 0)
        woff = jnp.where(decoding[:, None], wp % P, 0)
        pool_k = pool_k.at[:, bi, woff].set(kw)
        pool_v = pool_v.at[:, bi, woff].set(vw)

        # ---- greedy accept ------------------------------------------
        g = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)  # [C, K]
        ok = jnp.cumprod(
            (chunk[:, 1:] == g[:, :-1]).astype(jnp.int32), axis=1)
        m = ok.sum(axis=1).astype(jnp.int32)                # [C]
        n_emit = jnp.where(decoding, 1 + m, 0).astype(jnp.int32)
        # emitted stream: t0 then g_1..g_m. g_{m+1} is NOT emitted —
        # carrying l_m makes it the next tick's t0, emitted once there.
        toks = jnp.concatenate([t0[:, None], g[:, :-1]], axis=1)
        picked = jnp.take_along_axis(
            vlogits, m[:, None, None], axis=1)[:, 0]        # [C, V]
        last_logits = jnp.where(decoding[:, None], picked, last_logits)

        # ---- prefill lane: reference chunk, target AND draft ---------
        def do_prefill(pool_k, pool_v, dpool_k, dpool_v, last_logits):
            slot = jnp.maximum(prefill_slot, 0)
            row = tables[slot]
            kc = pool_k[:, row].reshape(L, 1, G, HKV, HD)
            vc = pool_v[:, row].reshape(L, 1, G, HKV, HD)
            logits, (nk, nv) = model.apply(
                {"params": params}, prefill_tokens[None],
                cache=(kc, vc), pos=prefill_pos)
            kw = jax.lax.dynamic_slice_in_dim(
                nk[:, 0], prefill_pos, CH, axis=1)
            vw = jax.lax.dynamic_slice_in_dim(
                nv[:, 0], prefill_pos, CH, axis=1)
            wpos = prefill_pos + jnp.arange(CH)
            wbi = row[wpos // P]
            pool_k = pool_k.at[:, wbi, wpos % P].set(kw)
            pool_v = pool_v.at[:, wbi, wpos % P].set(vw)
            # the draft rides the SAME chunk/window so its cache tracks
            # the target position for position — its logits are unused
            # during prefill (the first proposal each tick feeds t0)
            dkc = dpool_k[:, row].reshape(DL, 1, G, DHKV, DHD)
            dvc = dpool_v[:, row].reshape(DL, 1, G, DHKV, DHD)
            _, (dnk, dnv) = draft_model.apply(
                {"params": dparams}, prefill_tokens[None],
                cache=(dkc, dvc), pos=prefill_pos)
            dkw = jax.lax.dynamic_slice_in_dim(
                dnk[:, 0], prefill_pos, CH, axis=1)
            dvw = jax.lax.dynamic_slice_in_dim(
                dnv[:, 0], prefill_pos, CH, axis=1)
            dpool_k = dpool_k.at[:, wbi, wpos % P].set(dkw)
            dpool_v = dpool_v.at[:, wbi, wpos % P].set(dvw)
            done_row = logits[0, prefill_last_row]
            finished = prefill_last_row >= 0
            last_logits = jnp.where(
                (jnp.arange(C) == slot)[:, None] & finished,
                done_row[None, :], last_logits)
            return pool_k, pool_v, dpool_k, dpool_v, last_logits

        pool_k, pool_v, dpool_k, dpool_v, last_logits = jax.lax.cond(
            prefill_slot >= 0, do_prefill,
            lambda *a: a, pool_k, pool_v, dpool_k, dpool_v, last_logits)
        return (pool_k, pool_v, dpool_k, dpool_v, last_logits,
                new_rngs, toks, n_emit)

    return step


def _copy_pool_block(pool, src, dst):
    """Copy one block within every leaf of a (donated) pool — the
    copy-on-write fork primitive. Jitted separately from the step so
    the engine's `compile_count` pin (== 1) is undisturbed."""
    return tuple(leaf.at[:, dst].set(leaf[:, src]) for leaf in pool)


def idle_prefill(cfg: EngineConfig):
    """The step's no-prefill sentinel: (slot, tokens, pos, last_row)
    for the single-slot lane, (slots, tokens, pos, last_row, pads) for
    the batched lane."""
    if cfg.prefill_batch == 1:
        return (np.int32(-1), np.zeros(cfg.prefill_chunk, np.int32),
                np.int32(0), np.int32(-1))
    B = cfg.prefill_batch
    return (np.full(B, -1, np.int32),
            np.zeros((B, cfg.prefill_chunk), np.int32),
            np.int32(0), np.int32(-1), np.zeros(B, np.int32))


def tick_fields(cfg: EngineConfig):
    """The tick's runtime inputs as ``(name, shape, dtype)``, in the order
    the step takes them after ``last_logits``: the layout of the ONE
    ``int32`` vector the host hands the device a tick (docs/SERVING.md "What
    crosses to the chip in a tick"). Every element is one word: ``temp`` and
    ``rngs`` cross by their bits, ``decoding`` and ``fresh`` as 0/1.
    ``fresh`` is the wrapper's (`_packed`), not the step's: a slot's key is
    the host's ``rngs`` row where it is set and the one the device carries
    where it is not."""
    C, CH, B = cfg.capacity, cfg.prefill_chunk, cfg.prefill_batch
    i32 = np.int32
    fields = [("tables", (C, cfg.blocks_per_slot), i32), ("pos", (C,), i32),
              ("decoding", (C,), np.bool_), ("temp", (C,), np.float32),
              ("top_k", (C,), i32), ("rngs", (C, 2), np.uint32),
              ("fresh", (C,), np.bool_)]
    if B == 1:
        fields += [("prefill_slot", (), i32), ("prefill_tokens", (CH,), i32)]
    else:
        fields += [("slot_pad", (C,), i32), ("prefill_slots", (B,), i32),
                   ("prefill_tokens", (B, CH), i32)]
    fields += [("prefill_pos", (), i32), ("prefill_last_row", (), i32)]
    if B > 1:
        fields.append(("prefill_pad", (B,), i32))
    return tuple(fields)


def _counter_spans(counters):
    """(join, slice) of each of `model.tick_counters` in a lane's vector of
    int32 words: one word a counter, or the declared words of a ``(name,
    "union", words)`` bitset."""
    at = 0
    for counter in counters:
        words = counter[2] if counter[1] == "union" else 1
        yield counter[1], slice(at, at + words)
        at += words


def _join_words(counters, a, b):
    """`build_step`'s join for a decoder that declares a ``"union"``
    counter: a bitset joins by OR (an element set in either lane is set
    once)."""
    joins = {"sum": jnp.add, "max": jnp.maximum, "union": jnp.bitwise_or}
    return jnp.concatenate([joins[how](a[span], b[span])
                            for how, span in _counter_spans(counters)])


def _settle_words(counters, words):
    """A tick's joined words as one count a counter: a bitset's is how many
    elements it holds."""
    return jnp.stack([
        jnp.sum(jax.lax.population_count(words[span])) if how == "union"
        else words[span][0] for how, span in _counter_spans(counters)])


def result_fields(cfg: EngineConfig, n_counters: int):
    """What the step returns after ``last_logits``, as `tick_fields` has the
    inputs: the layout of the ONE ``int32`` vector the host reads a tick."""
    C = cfg.capacity
    fields = [("rngs", (C, 2), np.uint32)]
    if cfg.draft is not None:
        fields += [("toks", (C, cfg.draft.k), np.int32),
                   ("n_emit", (C,), np.int32)]
    else:
        fields.append(("emitted", (C,), np.int32))
        if n_counters:
            fields.append(("counts", (n_counters,), np.int32))
    return tuple(fields)


def _words(fields) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in fields)


def pack_words(fields, values) -> np.ndarray:
    """``values`` (host arrays, one a field) as one ``int32`` vector: a
    4-byte dtype by its bits, a bool as 0/1."""
    out = np.empty(_words(fields), np.int32)
    at = 0
    for (name, shape, dtype), value in zip(fields, values):
        value = np.asarray(value, dtype)
        if value.shape != shape:
            raise ValueError(f"tick input {name}: shape {value.shape}, "
                             f"the step was built for {shape}")
        if dtype is not np.bool_:
            value = value.view(np.int32)
        out[at:at + value.size] = value.reshape(-1)
        at += value.size
    return out


def unpack_words(fields, words):
    """The fields of one ``int32`` vector, a host array's (numpy: views of
    it) or a traced one's (inside the step), bit for bit."""
    on_host = isinstance(words, np.ndarray)
    out, at = [], 0
    for _, shape, dtype in fields:
        n = int(np.prod(shape))
        value = words[at:at + n].reshape(shape)
        at += n
        if dtype is np.bool_:
            value = value != 0
        elif dtype is not np.int32:
            value = value.view(dtype) if on_host else \
                jax.lax.bitcast_convert_type(value, dtype)
        out.append(value)
    return out


def _packed(inner, n_carried: int, fields):
    """``inner`` (what `build_step` / `build_spec_step` returns, called
    unchanged) behind the tick's protocol: ``step(*resident, rngs, words)``
    takes the device-resident arguments (parameters, pool leaves,
    ``last_logits``), the per-slot keys the device carries ``[C, 2]
    uint32`` and the one packed vector of `tick_fields`, and returns the
    ``n_carried`` donated buffers (pool leaves, ``last_logits``), the keys
    the step advanced, and the rest as one packed vector of `result_fields`.
    A slot the host marks ``fresh`` (admitted this tick) starts from the
    host's key; every other slot's key never leaves the device. Slices,
    reshapes, bitcasts and one `where` of a few kilobytes either side of
    the same program."""
    names = [name for name, _, _ in fields]

    def step(*args):
        *resident, rngs, words = args
        values = dict(zip(names, unpack_words(fields, words)))
        fresh = values.pop("fresh")
        values["rngs"] = jnp.where(fresh[:, None], values["rngs"], rngs)
        out = inner(*resident, *values.values())
        return (*out[:n_carried], out[n_carried], jnp.concatenate([
            (x if x.dtype == jnp.int32 else
             jax.lax.bitcast_convert_type(x, jnp.int32)).reshape(-1)
            for x in out[n_carried:]]))

    return step


def _global_put(x, sharding):
    """Place a host array as a GLOBAL jax array under ``sharding`` —
    single- or multi-process alike. Every process holds the full value
    (params come off the same npz, runtime inputs off the same
    lockstep scheduler), so each process carves out its addressable
    devices' slices and assembles the global view — the
    `resilience.faults` respawn-placement idiom. `jax.device_put`
    cannot do this cross-process in general (non-addressable devices),
    and `make_array_from_process_local_data` expects per-process
    SHARDS, not the replicated whole."""
    x = np.asarray(x)
    idx_map = sharding.addressable_devices_indices_map(x.shape)
    arrs = [jax.device_put(x[idx], d) for d, idx in idx_map.items()]
    return jax.make_array_from_single_device_arrays(
        x.shape, sharding, arrs)


def serving_param_specs(model, params, axis_names):
    """Per-leaf ``(path, PartitionSpec)`` list (tree_leaves order) for
    a replica's weights: the model's published per-leaf specs
    (`model.serving_param_specs()`; Llama's: wqkv/gate_up column-split,
    wo/w_down row-split, embeddings vocab-split) looked up by exact
    leaf path, every unknown leaf
    REPLICATED. Specs naming axes outside ``axis_names`` fall back to
    replicated too — serving meshes are tensor-only. Shared by the
    engine's device placement and `serve.audit`'s collective pricing,
    so the audited layout IS the served one."""
    from jax.sharding import PartitionSpec

    from ray_lightning_tpu.utils.pytree import named_leaves

    if hasattr(model, "param_specs"):       # the trainer-side wrapper
        specs = model.param_specs(params)
    elif hasattr(model, "serving_param_specs"):
        # the flax decoder the engine serves publishes its own placement
        specs = model.serving_param_specs()
    else:
        specs = {}
    axes = set(axis_names)
    out = []
    for path, _ in named_leaves(params):
        spec = specs.get(path)
        if spec is None or any(
                ax not in axes
                for entry in tuple(spec) if entry is not None
                for ax in ((entry,) if isinstance(entry, str) else entry)):
            spec = PartitionSpec()
        out.append((path, spec))
    return out


def serving_param_shardings(model, params, mesh):
    """`serving_param_specs` as per-leaf NamedShardings on the
    replica's own mesh (the pytree `DecodeEngine` places weights
    with)."""
    from jax.sharding import NamedSharding

    flat = [NamedSharding(mesh, spec) for _, spec in
            serving_param_specs(model, params, mesh.axis_names)]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), flat)


def why_unsupported(model, feature: str, default: str) -> str:
    """The decoder's own reason for refusing ``feature``, where its
    `serving_unsupported` is a mapping that gives one; else ``default``."""
    no = model.serving_unsupported
    return no[feature] if isinstance(no, dict) else default


def _refusal(model, cfg: EngineConfig, mesh) -> Optional[str]:
    """Why ``model`` cannot be served under ``cfg``/``mesh``, by what it
    declares in `serving_unsupported`; None where it can."""
    name = type(model).__name__
    no = set(model.serving_unsupported)
    why = lambda feature, default: why_unsupported(model, feature, default)
    if "speculative" in no and cfg.draft is not None:
        return (f"{name} cannot be a speculative-decoding target: "
                + why("speculative",
                      "the verify chunk rides the dense reference cache "
                      "path, which this decoder does not have")
                + " (set draft=None)")
    if "prefill_batch" in no and cfg.prefill_batch > 1:
        return (f"{name} prefills one slot a tick: "
                + why("prefill_batch", "its cache path has no left-padded "
                      "group form")
                + f" (prefill_batch {cfg.prefill_batch}; set "
                "prefill_batch=1)")
    if "tensor_parallel" in no and mesh is not None and mesh.size > 1:
        return (f"{name} has no tensor-parallel replica: "
                + why("tensor_parallel",
                      "it publishes no parameter placement and its paged "
                      "kernels have no manual region")
                + " (serve it with mesh=None, one chip a replica)")
    return None


class DecodeEngine:
    """One replica's compiled step + its device-resident buffers.

    Owns ``pool`` (the leaves the model declares: `pool_k`/`pool_v`
    name a K/V pair's), ``last_logits`` and ``rngs`` (the slots' keys,
    ``[C, 2] uint32``; all donated through every step — callers must
    never hold references to them) and the compile-count pin. The
    host-side request state lives in `serve.scheduler`.
    """

    def __init__(self, model, params, cfg: EngineConfig,
                 max_seq_len_check: bool = True,
                 use_pallas: Optional[bool] = None,
                 metrics=None, mesh=None,
                 draft_model=None, draft_params=None, device=None):
        """``device`` is where an unsharded replica (``mesh=None``)
        keeps its weights, pool and logits; the driver hands each
        inline replica its own. Default: the first local device."""
        # before the step compiles: a respawned replica deserializes it
        enable_persistent_cache()
        if max_seq_len_check and cfg.max_slot_len > model.cfg.max_seq_len:
            raise ValueError(
                f"engine max_slot_len {cfg.max_slot_len} exceeds the "
                f"model's max_seq_len {model.cfg.max_seq_len} — RoPE "
                "tables would be read out of range")
        self.model = model
        # the attention-path decision is made ONCE, at build time, by
        # the same predicate the op's dispatch uses (flash discipline:
        # ops.attention.paged_attention_uses_pallas) — on TPU (or under
        # force_pallas/RLT_PALLAS with interpret mode) and a tiling
        # shape, the decode lane is the fused paged-attention kernel
        # and the dense gathered view is never built; otherwise the
        # reference lane, the bitwise anchor against generate().
        #: a decoder with sliding-window layers gets its second group
        #: here: one ring a slot, sized from what the engine already has
        spec = _pool_spec(model, cfg)
        refused = _refusal(model, cfg, mesh)
        if refused:
            raise ValueError(refused)
        if use_pallas is None and not model.cfg.use_flash:
            use_pallas = False  # reference-forced model config
        if mesh is not None and mesh.size > 1:
            # XLA cannot partition a Mosaic kernel and the paged kernels
            # have no manual region yet: a sharded replica takes the
            # reference lanes, and attention_path/prefill_path say so
            use_pallas = False
        # the model's own predicates (the ones its ops dispatch on): the
        # two lanes have separate shape gates (the prefill kernel also
        # tiles the chunk width), so the decisions are independent but
        # share the use_pallas resolution
        self.fused, self.fused_prefill = model.paged_lanes(
            cfg.capacity, cfg.prefill_batch, cfg.prefill_chunk,
            (spec.n_blocks, spec.block_size), use_pallas)
        if "reference_lanes" in model.serving_unsupported and not (
                self.fused and self.fused_prefill):
            raise ValueError(
                f"{type(model).__name__} has no reference (gathered-"
                "view) lanes: it serves through its paged kernels only "
                f"(decode {self.fused}, prefill {self.fused_prefill} at "
                f"these shapes). Run on a TPU, or pass use_pallas=True "
                "(RLT_PALLAS=1) for interpret mode, with a block_size "
                "and head count the kernels tile")
        self.draft_model = draft_model
        self.dpool_k = self.dpool_v = self.draft_params = None
        if cfg.draft is not None:
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "cfg.draft is set but no draft model/params were "
                    "given — pass draft_model= and draft_params=")
            if mesh is not None:
                raise ValueError(
                    "speculative decoding requires an unsharded "
                    "replica (mesh=None)")
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.cfg.vocab_size} != "
                    f"target vocab {model.cfg.vocab_size} — greedy "
                    "verify compares token ids across the two models")
            if max_seq_len_check and \
                    cfg.max_slot_len > draft_model.cfg.max_seq_len:
                raise ValueError(
                    f"engine max_slot_len {cfg.max_slot_len} exceeds "
                    f"the DRAFT model's max_seq_len "
                    f"{draft_model.cfg.max_seq_len}")
            # the verify chunk and the draft feedback trips run the
            # reference lanes only — the fused kernels are single-token
            # / prefill shaped. Priced honestly: serve.audit's
            # speculative_plan charges the gathered views.
            self.fused = False
            self.fused_prefill = False
        #: a tick's chunk rides the decode lane's pass (`build_step`)
        self.joined = joins_lanes(model, cfg, self.fused, self.fused_prefill)
        #: tokens of the fused decode kernel's KV tile (`_step_work`'s
        #: ``decode_tiles``); None on the reference lane and for a
        #: decoder that states none
        self._decode_tile = model.decode_tile_tokens(
            spec.block_size, cfg.blocks_per_slot) if self.fused else None
        #: (query tile rows, KV tile tokens) of the fused prefill kernel
        #: (`_step_work`'s ``prefill_tiles``); None likewise
        self._prefill_tile = model.prefill_tile_shape(
            cfg.prefill_batch, cfg.prefill_chunk, spec.block_size,
            cfg.blocks_per_slot) if self.fused_prefill else None
        self.cfg = cfg
        self.spec = spec
        #: replica-group mesh (docs/SERVING.md "sharded replicas"):
        #: None = the historical single-device replica; a mesh with a
        #: ``tensor`` axis lowers the SAME one-compile step as an SPMD
        #: program — params shard per `models.llama.llama_param_specs`,
        #: the pool shards over KV heads, and every runtime input +
        #: sampled output stays replicated so the host-side scheduler
        #: (which lives on every rank, lockstep) is tp-oblivious.
        self.mesh = mesh
        self.tp = 1 if mesh is None else int(mesh.shape.get("tensor", 1))
        n_pool = len(pool_leaf_shapes(model.cfg, spec))
        #: names of the device-side counts the step returns, fetched
        #: with the tick's tokens into `last_counters`
        self._counter_names = tuple(c[0] for c in model.tick_counters)
        self.last_counters: dict = {}
        #: the tick's protocol: one packed ``int32`` vector in, one out
        #: (`tick_fields`, `result_fields`), their layout a function of
        #: shapes settled here
        self._in_fields = tick_fields(cfg)
        self._h2d_bytes = 4 * _words(self._in_fields)
        self._out_fields = result_fields(cfg, len(self._counter_names))
        n_carried = n_pool + 1 if cfg.draft is None else 5
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            validate_pool_tp(model.cfg, self.tp)
            self._repl_sh = NamedSharding(mesh, PartitionSpec())
            pool_sh = NamedSharding(mesh,
                                    pool_partition_spec(self.tp))
            param_sh = serving_param_shardings(model, params, mesh)
            self.params = jax.tree_util.tree_map(_global_put, params,
                                                 param_sh)
            # out shardings pin the donated-buffer cycle: pool in/out
            # identical (donation holds), logits + the packed result
            # replicated so every rank reads the same host values
            self._step = jax.jit(
                _packed(build_step(model, cfg, fused=self.fused,
                                   fused_prefill=self.fused_prefill),
                        n_carried, self._in_fields),
                donate_argnums=tuple(range(1, n_pool + 3)),
                out_shardings=(pool_sh,) * n_pool + (self._repl_sh,) * 3)
            self.pool = tuple(_global_put(leaf, pool_sh)
                              for leaf in init_pool(model.cfg, self.spec))
            self.last_logits = _global_put(
                jnp.zeros((cfg.capacity, model.cfg.vocab_size),
                          jnp.float32), self._repl_sh)
            self.rngs = _global_put(
                np.zeros((cfg.capacity, 2), np.uint32), self._repl_sh)
        else:
            # canonicalize the weights' placement: trainer-produced
            # params arrive committed to a NamedSharding over the
            # training mesh, and a step closed over those emits
            # NamedSharding outputs — so the donated pool buffers
            # (built SingleDeviceSharding by init_pool) change
            # signature after the first tick and the step compiles a
            # SECOND executable (observed in the fine-tune -> serve
            # flow; test-pinned). Committing the weights to one
            # concrete device keeps every signature
            # SingleDeviceSharding from the first tick on.
            if device is None:
                device = jax.local_devices()[0]
            self.device = device
            self.params = jax.device_put(params, device)
            if cfg.draft is not None:
                # donated: both pools + last_logits (positions 2-6 of
                # the spec signature — params/draft params stay) and the
                # carried keys behind them
                self._step = jax.jit(
                    _packed(build_spec_step(model, draft_model, cfg),
                            n_carried, self._in_fields),
                    donate_argnums=(2, 3, 4, 5, 6, 7))
            else:
                self._step = jax.jit(
                    _packed(build_step(model, cfg, fused=self.fused,
                                       fused_prefill=self.fused_prefill),
                            n_carried, self._in_fields),
                    donate_argnums=tuple(range(1, n_pool + 3)))
            # COMMIT the device-resident buffers to the same device as
            # the weights: a fresh jnp.zeros is uncommitted, but the
            # step's outputs are committed, so an uncommitted
            # first-tick signature would compile a second executable
            # the moment the donated outputs cycle back in (same
            # phantom-recompile class as the params placement above;
            # the churn pin covers both)
            self.pool = tuple(jax.device_put(leaf, device)
                              for leaf in init_pool(model.cfg, self.spec))
            self.last_logits = jax.device_put(
                jnp.zeros((cfg.capacity, model.cfg.vocab_size),
                          jnp.float32),
                device)
            self.rngs = jax.device_put(
                np.zeros((cfg.capacity, 2), np.uint32), device)
            if cfg.draft is not None:
                self.draft_params = jax.device_put(draft_params, device)
                dpk, dpv = init_pool(draft_model.cfg, self.spec)
                self.dpool_k = jax.device_put(dpk, device)
                self.dpool_v = jax.device_put(dpv, device)
        # the copy-on-write fork primitive (scheduler-driven): its own
        # tiny jit so the step's compile_count pin is undisturbed
        self._copy = jax.jit(_copy_pool_block, donate_argnums=(0,))
        self.steps = 0
        #: dispatched steps whose result `collect` has not read yet
        self._uncollected = 0
        # live metrics (telemetry/metrics.py): per-tick prefill/decode
        # token counts + the compile counter. The registry NEVER enters
        # build_step — metrics on or off lowers a byte-identical
        # program (test-pinned), and every recorded value is computed
        # from the host-owned numpy inputs the tick already received
        # (no new host syncs). Assignable after construction: the serve
        # loop arms it once the run dir is known.
        from ray_lightning_tpu.telemetry.metrics import NULL_METRICS

        self.metrics = metrics if metrics is not None else NULL_METRICS

    @property
    def pool_k(self):
        """The K leaf of a K/V pool (the model's first leaf)."""
        return self.pool[0]

    @property
    def pool_v(self):
        """The V leaf of a K/V pool; a one-leaf (latent) pool has none."""
        if len(self.pool) < 2:
            raise AttributeError(
                f"{type(self.model).__name__}'s pool has one leaf")
        return self.pool[1]

    # ---- compile accounting ---------------------------------------------

    @property
    def attention_path(self) -> str:
        """Which decode attention ran for this replica's lifetime —
        surfaced by the bench serving leg and the smoke verdicts."""
        return "paged-pallas" if self.fused else "reference-gather"

    @property
    def prefill_path(self) -> str:
        """Which prefill attention ran — the prefill twin of
        `attention_path` (the fused lane retires the per-group
        gathered view; docs/SERVING.md 'paged prefill kernel')."""
        return "paged-pallas" if self.fused_prefill else \
            "reference-gather"

    @property
    def compile_count(self) -> int:
        """Distinct compiled programs behind the step — the churn gate
        pins this at 1. Falls back to -1 (unknown) on a jax without the
        cache-size introspection rather than failing serving."""
        try:
            return int(self._step._cache_size())
        except Exception:  # noqa: BLE001 — introspection is advisory
            return -1

    def idle_inputs(self) -> dict:
        """`tick`'s arguments with no slot live and no chunk."""
        C = self.cfg.capacity
        return dict(
            tables=np.zeros((C, self.spec.blocks_per_slot), np.int32),
            pos=np.zeros(C, np.int32),
            decoding=np.zeros(C, bool),
            temp=np.zeros(C, np.float32),
            top_k=np.zeros(C, np.int32),
            rngs=np.zeros((C, 2), np.uint32),
            prefill=idle_prefill(self.cfg),
            pad=np.zeros(C, np.int32),
        )

    def lower_idle(self):
        """The step lowered on an idle tick's inputs, as `dispatch` calls
        it: what a test or an audit reads the served program from
        (``.as_text()``, ``.compile()``)."""
        return self._step.lower(*self._resident(), self.rngs,
                                self._pack(**self.idle_inputs()))

    def warmup(self) -> None:
        """Compile (or deserialize, when a persistent compile cache is
        armed — `pipeline.compile_cache`) the step before the replica
        is marked live: an idle tick on the zero pool. P99 TTFT is a
        compile-cache metric (ROADMAP item 1)."""
        self.tick(**self.idle_inputs())

    # ---- copy-on-write fork ----------------------------------------------

    def copy_block(self, src: int, dst: int) -> None:
        """Copy pool block ``src`` into ``dst`` (K and V; the draft
        pool too when speculative decoding is armed) — the scheduler's
        fork primitive: before a prefill chunk's write window touches a
        block with refcount > 1, the slot's table is repointed at a
        fresh block populated by this copy, so a shared block is never
        written by a non-exclusive owner."""
        s, d = jnp.int32(src), jnp.int32(dst)
        self.pool = self._copy(self.pool, s, d)
        if self.dpool_k is not None:
            self.dpool_k, self.dpool_v = self._copy(
                (self.dpool_k, self.dpool_v), s, d)

    # ---- the tick --------------------------------------------------------

    def _resident(self) -> tuple:
        """The step's device-resident arguments, in its order."""
        if self.cfg.draft is not None:
            return (self.params, self.draft_params, *self.pool,
                    self.dpool_k, self.dpool_v, self.last_logits)
        return (self.params, *self.pool, self.last_logits)

    def _pack(self, tables, pos, decoding, temp, top_k, rngs, prefill,
              pad=None, fresh=None) -> np.ndarray:
        """`dispatch`'s arguments as the one vector of `tick_fields`."""
        C = self.cfg.capacity
        lead = (tables, pos, decoding, temp, top_k, rngs,
                np.ones(C, bool) if fresh is None else fresh)
        if self.cfg.prefill_batch > 1:
            lead += (np.zeros(C, np.int32) if pad is None else pad,)
        return pack_words(self._in_fields, (*lead, *prefill))

    def _put(self, words: np.ndarray):
        """The tick's ONE host-to-device placement."""
        if self.mesh is None:
            return jax.device_put(words, self.device)
        # replicated over the replica's own mesh: each rank computed the
        # SAME host values (lockstep scheduler), so assembling the global
        # view is pure placement, no wire traffic
        return _global_put(words, self._repl_sh)

    def _fetch(self, words) -> list:
        """The tick's ONE device-to-host read (its copy was queued behind
        the step at dispatch): the fields of `result_fields`, host arrays
        the caller owns."""
        return unpack_words(self._out_fields, np.array(words))

    def dispatch(self, tables, pos, decoding, temp, top_k, rngs, prefill,
                 pad=None, fresh=None):
        """The first half of a tick: send one step to the device and come
        back without waiting for it. Returns the handle `collect` reads the
        step's tokens from. The donated device buffers are swapped here, so
        the next step can be dispatched on them before this one's tokens
        are read; at most one earlier step may be uncollected then.

        ``rngs`` ([C, 2] u32) are the host's keys and ``fresh`` ([C] bool)
        the slots that take them this step: every other slot draws from
        the key the device carries for it (`_packed`). Without ``fresh``
        every slot takes the host's. ``pad`` ([C] i32 per-slot left pad)
        exists only on the batched-prefill program (prefill_batch > 1) and
        is ignored otherwise — the single-slot program is the historical
        one, with no pad inputs.

        The host crosses to the device once each way (docs/SERVING.md
        "What crosses to the chip in a tick"): the arguments go as one
        packed vector, and the step's small results come back as one,
        whose copy to the host is queued right behind the step."""
        if self._uncollected > 1:
            raise RuntimeError(
                "two dispatched steps are uncollected: collect() the "
                "older before building the next")
        with annotate("serve.put", h2d_arrays=1, h2d_bytes=self._h2d_bytes):
            words = self._put(self._pack(tables, pos, decoding, temp,
                                         top_k, rngs, prefill, pad, fresh))
        with annotate("serve.dispatch", ahead=self._uncollected,
                      **self._step_work(pos, decoding, prefill, temp,
                                        top_k)):
            *carried, self.rngs, result = self._step(
                *self._resident(), self.rngs, words)
            if self.mesh is not None:
                # replicated: any addressable shard IS the global value
                # (np.array on a multi-process global array would raise)
                result = result.addressable_data(0)
            result.copy_to_host_async()
        if self.cfg.draft is not None:
            (*pool, self.dpool_k, self.dpool_v, self.last_logits) = carried
        else:
            *pool, self.last_logits = carried
            # counted from the host-owned inputs while the step runs
            self._record(int(np.sum(np.asarray(decoding))), prefill)
        self.pool = tuple(pool)
        self.steps += 1
        self._uncollected += 1
        # the base step emits one token a decoding slot: its ``n_emit`` is
        # the mask it was sent, known before the result is
        return result, np.asarray(decoding).astype(np.int32), prefill

    def collect(self, handle):
        """The second half of a tick: the ONE blocking read of a dispatched
        step's result. Returns ``(toks [C, W] i32 np, n_emit [C] i32 np,
        rngs' [C, 2] u32 np)`` — ``toks[s, :n_emit[s]]`` are slot s's
        tokens of that step, oldest first. W == 1 on the base step
        (``n_emit`` = the decoding mask it was dispatched with); W ==
        cfg.draft.k on a speculative engine, where ``n_emit`` counts the
        carried token plus accepted proposals. ``rngs'`` are the keys the
        step left on the device, for a reader that wants them; the next
        step does not need them back."""
        result, n_emit, prefill = handle
        self._uncollected -= 1
        with annotate("serve.fetch", d2h_arrays=1):
            new_rngs, *rest = self._fetch(result)
        if self.cfg.draft is not None:
            toks, n_emit = rest
            self._record(int(n_emit.sum()), prefill)
            return toks, n_emit, new_rngs
        emitted, *counts = rest
        if counts:
            # the model's device-side counts ride the same read
            self.last_counters = dict(zip(
                self._counter_names, (int(v) for v in counts[0])))
        return emitted[:, None], n_emit, new_rngs

    def tick(self, tables, pos, decoding, temp, top_k, rngs, prefill,
             pad=None, fresh=None):
        """One step, dispatched and collected: `collect` of `dispatch`."""
        return self.collect(self.dispatch(
            tables, pos, decoding, temp, top_k, rngs, prefill, pad, fresh))

    def _record(self, n_dec: int, prefill) -> None:
        """A tick's live metrics: ``n_dec`` tokens emitted, and the chunk
        positions advanced (pad columns on the batched lane included), both
        from host values the tick holds anyway, so metrics adds no host
        sync (the speculative step's ``n_emit`` is a result the scheduler
        needs)."""
        m = self.metrics
        if not m.enabled:
            return
        if self.cfg.prefill_batch == 1:
            n_pf_rows = 1 if int(prefill[0]) >= 0 else 0
        else:
            n_pf_rows = int(np.sum(np.asarray(prefill[0]) >= 0))
        if n_dec:
            m.count("decode_tokens", n_dec)
        if n_pf_rows:
            m.count("prefill_tokens", n_pf_rows * self.cfg.prefill_chunk)
        m.gauge("engine_steps", self.steps)
        m.gauge("compile_count", self.compile_count)

    def _step_work(self, pos, decoding, prefill, temp, top_k) -> dict:
        """What this step's attention and sampling are asked to do, as
        `rlt.serve.dispatch` carries it into a profiler trace: from the
        host arrays the tick already holds, no device value is read.

          decode_slots  slots in the decode phase
          sampled_slots of those, the ones that draw (``temp > 0``)
          topk_slots    of those, the ones the top-k threshold filters
                        (``top_k > 0``): the step finds a threshold for
                        every slot whatever its mode, and only these use
                        it
          kv_tokens     cache tokens their queries read: each reads its
                        ``pos`` written tokens and the one this step
                        writes (the kernel's ``lengths = pos + 1``)
          decode_tiles  KV tiles a layer the decode kernel computes for
                        them, ``sum(ceil((pos + 1) / tile))`` with the
                        kernel's own tile (`decode_tile_tokens`); left
                        out where the decoder states no tile
          prefill_rows  real prompt rows in this step's chunk (pad
                        columns and the zero tail past a prompt's end
                        are not work); 0 without a chunk
          prefill_ctx   tokens already in those rows' caches before the
                        chunk (summed over the group's rows)
          joined_rows   rows of the chunk that went through the model in
                        the decode lane's pass, the weights read once for
                        both: the whole chunk's CH (pad and tail rows ride
                        too) in a tick with a chunk of a decoder that joins
                        its lanes (`joins_lanes`), else 0
          prefill_tiles KV tiles a layer the prefill kernel computes for
                        the chunk: for each row of the group (a vacant
                        one rides the scratch table and is computed too)
                        and each query tile, the tiles with a position in
                        ``[pad, start + (qi + 1) * bq)``, by the kernel's
                        own tiles (`prefill_tile_shape`); 0 without a
                        chunk, left out where the decoder states no tile

        For a decoder with sliding-window layers (`model.kv_window`), what
        ONE WINDOW LAYER is asked to do beside what a full layer is (the
        counters above):

          kv_tokens_window     ``sum(min(pos + 1, window))``
          prefill_ctx_window   cached tokens before the chunk that its rows
                               can see on a window layer
          decode_tiles_window, prefill_tiles_window
                               the live tiles behind a raised floor
                               (`decode_live_tiles`, `prefill_live_tiles`)

        For a decoder with recurrent layers (`model.slot_state`), what ONE
        RECURRENT LAYER is asked to do, under the names the decoder's own
        `tick_counters` END in (`SsmHybrid`: ``scan_rows``,
        ``state_slots``; `DeltaHybrid`: ``delta_rows``, ``state_slots``;
        `ConvMoe`: ``conv_rows``, ``state_slots``):

          <rows>        rows of the chunk its recurrence advances on: the
                        real ones, ``prefill_rows`` less those the scheduler
                        sent before (a window slid back at a slot's end)
          <slots>       slots whose state the decode lane moves by a row
        """
        dec = np.asarray(decoding)
        if self.cfg.prefill_batch == 1:
            pslot, _toks, start, last = prefill
            active = np.asarray([pslot]) >= 0
            pads = np.zeros(1, np.int64)
        else:
            pslots, _toks, start, last, pads = prefill
            active = np.asarray(pslots) >= 0
            pads = np.asarray(pads, np.int64)
        start, last = int(start), int(last)
        cols = last + 1 if last >= 0 else self.cfg.prefill_chunk
        lead = np.clip(pads - start, 0, cols)   # pad columns in the chunk
        lengths = np.asarray(pos)[dec] + 1
        sampled = dec & (np.asarray(temp) > 0)
        work = {
            "decode_slots": int(dec.sum()),
            "sampled_slots": int(sampled.sum()),
            "topk_slots": int((sampled & (np.asarray(top_k) > 0)).sum()),
            "kv_tokens": int(lengths.sum()),
            "prefill_rows": int(((cols - lead) * active).sum()),
            "prefill_ctx": int((np.maximum(start - pads, 0) * active).sum()),
            "joined_rows": (self.cfg.prefill_chunk
                            if self.joined and active.any() else 0),
        }
        window = self.model.kv_window
        if window is not None:
            work["kv_tokens_window"] = int(np.minimum(lengths, window).sum())
            work["prefill_ctx_window"] = int(
                (np.minimum(np.maximum(start - pads, 0), window - 1)
                 * active).sum())
        if self.model.slot_state:
            # the chunk's real rows as the step reckons them: from the
            # slot's first unsent position to the prompt's last
            sent = (int(np.asarray(pos)[int(pslot)]) - start
                    if active.any() else 0)
            rows, slots = self._counter_names[-2:]
            work[rows] = int(max(cols - max(sent, 0), 0) * active.sum())
            work[slots] = work["decode_slots"]
        if self._decode_tile:
            work["decode_tiles"] = int(
                np.ceil(lengths / self._decode_tile).sum())
            if window is not None:
                from ray_lightning_tpu.ops.pallas.paged_attention import (
                    decode_live_tiles,
                )

                work["decode_tiles_window"] = decode_live_tiles(
                    lengths, self._decode_tile, window)
        if self._prefill_tile:
            from ray_lightning_tpu.ops.pallas.paged_prefill import (
                prefill_live_tiles,
            )

            tiles = lambda floor=None: prefill_live_tiles(
                start, pads, self.cfg.prefill_chunk, *self._prefill_tile,
                self.cfg.max_slot_len, floor) if active.any() else 0
            work["prefill_tiles"] = tiles()
            if window is not None:
                work["prefill_tiles_window"] = tiles(window)
        return work
