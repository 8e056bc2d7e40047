"""Block-paged KV cache: a shared block pool + per-slot block tables.

The training-side cache (`models.llama.init_cache`) is dense: one
``[L, B, S_max, Hkv, hd]`` buffer per request batch, sized for the
worst case. Serving cannot afford that shape — requests are ragged,
arrive and retire continuously, and the cache is the dominant HBM
consumer — so the serving engine stores KV in fixed-size **blocks**
drawn from one shared pool:

    pool_k, pool_v : [L, n_blocks, block_size, Hkv, hd]
    block_table    : [capacity, blocks_per_slot] int32  (host-owned)

A slot's logical cache position ``p`` lives at pool block
``table[slot, p // block_size]``, offset ``p % block_size``. The device
step receives the table as a plain int32 input each call: admission and
retirement only rewrite table rows and host-side scalars, so the
compiled step never changes shape (the no-recompile-under-churn
guarantee the engine pins).

Block 0 is the **scratch block**: never allocated, and every index the
step must not really write (idle slots, the prefill lane when nothing
is prefilling) is redirected to it. Scratch contents are garbage by
design; every read of the gathered view is masked by position
(``kv_pos <= q_pos``) before it can influence attention, and masked
scores contribute *exactly* zero through the softmax — the bitwise
parity with single-stream `generate` rests on this (docs/SERVING.md
"numerics").

**Two groups.** A decoder with sliding-window layers (`model.kv_window`)
keeps their K/V apart from its full layers': a full layer's blocks grow
with the context, on demand, from the one `BlockAllocator` as above; a
window layer never reads behind ``pos - window + 1``, so its leaves
``[L_win, window_blocks, P, Hkv, hd]`` are a RING a slot: slot ``c`` owns
blocks ``1 + c * ring .. (c + 1) * ring`` for good, logical block ``b``
(the same indexing by ``pos // P``) lives at ``b % ring``, and writing on
overwrites what fell out of sight. ``ring = ceil((window +
prefill_chunk) / P) + 1`` (`window_ring_blocks`) holds everything a chunk
and its slid-back twin can see, so the group never runs dry, nothing is
taken or returned, and admission, growth and preemption keep one
allocator to ask. Its table is arithmetic (`window_ring_table`), made
inside the step from the positions it already has; an entry behind the
window names scratch block 0.

**A row a slot.** A decoder with recurrent layers (`model.slot_state`)
keeps for each of them a state that does not grow with the context: its
leaves have a SLOT axis where the others have blocks, ``spec.state_slots``
long (the engine's capacity, `state_pool_spec`), the decoder says what a
row holds and in which type (`cfg.pool_leaf_shapes(n_blocks, P,
state_slots=...)`; a leaf that is not in the activations' type is a
`jax.ShapeDtypeStruct`). Slot ``c`` owns row ``c`` for good: nothing is
taken, returned, grown or preempted, there is no table and no scratch row.
What a row-a-token pool gets for free it does not: a K/V row written twice
is the same row, a state advanced twice is not. So the step tells the
decoder which rows of a prefill chunk are real (`PagedPrefillView.
real_rows`) and which slots the decode lane moves (`PagedDecodeView.
state_moves`), a chunk whose first real row is position 0 starts from
zeros, and a row the scheduler sends a second time keeps its first K/V
(docs/SERVING.md "three kinds of cached state").
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class PagedPoolSpec:
    """Shape of the paged pool for one model config.

    ``gathered_len = blocks_per_slot * block_size`` is the dense view
    the step materializes per slot — the per-slot maximum of
    ``prompt_len + max_new_tokens`` the scheduler can admit.
    """

    n_blocks: int
    block_size: int
    blocks_per_slot: int
    #: the window group (module text, "two groups"): blocks of one slot's
    #: ring and the slots that have one; 0 for a decoder with one group.
    #: Set by the engine (`window_pool_spec`), never by a configuration.
    window_ring: int = 0
    window_slots: int = 0
    #: rows of the leaves that hold a row a slot (module text, "a row a
    #: slot"); 0 for a decoder without. Set by the engine
    #: (`state_pool_spec`), never by a configuration.
    state_slots: int = 0

    def __post_init__(self):
        if self.block_size < 1 or self.blocks_per_slot < 1:
            raise ValueError("block_size and blocks_per_slot must be >= 1")
        if self.n_blocks < 2:
            # block 0 is reserved scratch — a pool of 1 block can hold
            # no request at all
            raise ValueError("n_blocks must be >= 2 (block 0 is scratch)")

    @property
    def gathered_len(self) -> int:
        return self.blocks_per_slot * self.block_size

    @property
    def window_blocks(self) -> int:
        """Blocks of the window group's leaves: every slot's ring and the
        scratch block; 0 without a window group."""
        return 1 + self.window_slots * self.window_ring \
            if self.window_ring else 0

    @classmethod
    def for_capacity(cls, capacity: int, max_len: int,
                     block_size: int = 16,
                     oversubscribe: float = 1.0) -> "PagedPoolSpec":
        """A spec sized so ``capacity`` slots of up to ``max_len`` tokens
        fit. ``oversubscribe < 1`` shrinks the pool below the dense
        worst case — the paged bet that real lengths are ragged; the
        scheduler's on-demand mode defers admissions (or preempts) when
        the bet loses."""
        bps = -(-max_len // block_size)
        blocks = max(2, 1 + int(round(capacity * bps * oversubscribe)))
        return cls(n_blocks=blocks, block_size=block_size,
                   blocks_per_slot=bps)


def window_ring_blocks(window: int, prefill_chunk: int, block_size: int,
                       blocks_per_slot: int) -> int:
    """Blocks of one slot's ring in the window group: ``ceil((window +
    prefill_chunk) / P) + 1``, at most a whole table. A chunk's rows at
    ``[start, start + chunk)`` see back to ``start - window + 1``, a span
    of ``window + chunk - 1`` tokens that touches at most that many
    blocks; the chunk is written whole before it attends, the scheduler's
    slid-back last chunk restarts less than a chunk behind the previous
    one's end, and a decoded token sees less than either."""
    ring = -(-(window + prefill_chunk) // block_size) + 1
    return min(ring, blocks_per_slot)


def window_pool_spec(spec: PagedPoolSpec, window, capacity: int,
                     prefill_chunk: int) -> PagedPoolSpec:
    """``spec`` with the window group a decoder of sliding window
    ``window`` needs for ``capacity`` slots; unchanged for None."""
    if window is None:
        return spec
    return dataclasses.replace(
        spec, window_slots=capacity, window_ring=window_ring_blocks(
            window, prefill_chunk, spec.block_size, spec.blocks_per_slot))


def window_ring_table(spec: PagedPoolSpec, slots, first, last):
    """The window group's table rows ``[n, M]`` int32 for ``slots`` [n]
    whose rows see cache positions ``first .. last`` ([n] each, or
    scalars): logical block ``b`` of slot ``c`` is ring block ``1 + c *
    ring + b % ring`` where it holds one of those positions, scratch
    block 0 elsewhere (behind the window, or not written yet). `jax.numpy`
    throughout: the engine's step makes it from the positions it has."""
    ring, p = spec.window_ring, spec.block_size
    b = jnp.arange(spec.blocks_per_slot, dtype=jnp.int32)[None, :]
    slots = jnp.asarray(slots, jnp.int32).reshape(-1, 1)
    first = jnp.asarray(first, jnp.int32).reshape(-1, 1)
    last = jnp.asarray(last, jnp.int32).reshape(-1, 1)
    live = (b >= first // p) & (b <= last // p)
    return jnp.where(live, 1 + slots * ring + b % ring, 0)


def state_pool_spec(spec: PagedPoolSpec, slot_state: bool,
                    capacity: int) -> PagedPoolSpec:
    """``spec`` with a row for each of ``capacity`` slots where the decoder
    keeps a state a slot (`model.slot_state`); unchanged where not."""
    if not slot_state:
        return spec
    return dataclasses.replace(spec, state_slots=capacity)


def pool_leaf_shapes(cfg, spec: PagedPoolSpec):
    """The leaves ``cfg`` declares for ``spec``: a decoder with a window
    group is told that group's blocks too, one that keeps a row a slot how
    many slots."""
    if spec.state_slots:
        return cfg.pool_leaf_shapes(spec.n_blocks, spec.block_size,
                                    state_slots=spec.state_slots)
    if spec.window_ring:
        return cfg.pool_leaf_shapes(spec.n_blocks, spec.block_size,
                                    spec.window_blocks)
    return cfg.pool_leaf_shapes(spec.n_blocks, spec.block_size)


def _typed(cfg, leaf):
    """(shape, dtype) of a declared leaf: a plain shape is in the model's
    activation dtype, a `jax.ShapeDtypeStruct` says its own."""
    return (tuple(getattr(leaf, "shape", leaf)),
            jnp.dtype(getattr(leaf, "dtype", cfg.dtype)))


def init_pool(cfg, spec: PagedPoolSpec):
    """The zeroed pool: one leaf a shape the model's config declares
    (`cfg.pool_leaf_shapes(n_blocks, block_size)`), in the model's
    activation dtype. A K/V decoder declares two, ``(pool_k, pool_v)``,
    each ``[n_layers, n_blocks, block_size, n_kv_heads, head_dim]`` —
    the same per-position layout as `models.llama.init_cache`,
    block-chunked over the sequence axis; a latent-attention decoder
    one, ``[n_layers, n_blocks, block_size, row]``; a decoder with
    sliding-window layers four, its full layers' K and V and its window
    group's (module text, "two groups"); a decoder with recurrent layers
    its attention layers' K and V and the leaves that hold a row a slot,
    each in the type it declares."""
    return tuple(jnp.zeros(*_typed(cfg, leaf))
                 for leaf in pool_leaf_shapes(cfg, spec))


def validate_pool_tp(cfg, tp: int) -> None:
    """A tensor-parallel replica shards the pool over the KV-head axis
    (the one axis every pool consumer — gather, scatter, both fused
    kernels — treats as embarrassingly parallel), so the head count
    must divide evenly: an uneven split would give ranks different
    pool shapes and the one-compile step different programs per rank."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if cfg.n_kv_heads % tp:
        raise ValueError(
            f"tensor-parallel degree {tp} must divide n_kv_heads "
            f"{cfg.n_kv_heads}: the paged pool shards over the KV-head "
            "axis (docs/SERVING.md 'sharded replicas')")


def pool_partition_spec(tp: int = 1):
    """PartitionSpec of one pool leaf ``[L, n_blocks, block_size, Hkv,
    hd]`` on a replica's own mesh: KV heads over the ``tensor`` axis,
    every other axis replicated. Block identity is untouched — the SAME
    host-side block table drives every shard, so the allocator and the
    scheduler stay tp-oblivious."""
    from jax.sharding import PartitionSpec as P

    if tp <= 1:
        return P()
    return P(None, None, None, "tensor", None)


def pool_shard_bytes(cfg, spec: PagedPoolSpec, tp: int = 1) -> int:
    """Per-device HBM of one rank's pool shard (k + v): the head axis
    divides by ``tp``, everything else is carried whole."""
    validate_pool_tp(cfg, tp)
    return int(pool_bytes(cfg, spec)) // tp


def pool_bytes(cfg, spec: PagedPoolSpec) -> int:
    """HBM held by the pool itself (every leaf the model declares)."""
    import math

    return sum(math.prod(shape) * dtype.itemsize for shape, dtype in (
        _typed(cfg, leaf) for leaf in pool_leaf_shapes(cfg, spec)))


def gathered_view_bytes(cfg, spec: PagedPoolSpec, capacity: int) -> int:
    """HBM of the dense per-slot gathered view the REFERENCE decode
    lane materializes (k + v): ``[L, capacity, gathered_len, Hkv, hd]``.
    The reference engine pays this copy for correctness-first paged
    semantics; the fused paged-attention kernel
    (ops/pallas/paged_attention.py) consumes the pool through the block
    tables and this term vanishes (docs/SERVING.md "paged-attention
    kernel") — the planner charges whichever path the engine would
    select (`serve_kv_plan_bytes(fused=...)`)."""
    per = (cfg.n_layers * capacity * spec.gathered_len
           * cfg.n_kv_heads * cfg.head_dim)
    return 2 * per * jnp.dtype(cfg.dtype).itemsize


def serve_kv_plan_bytes(cfg, spec: PagedPoolSpec, capacity: int,
                        fused: bool = False,
                        prefill_batch: int = 1,
                        fused_prefill: bool = False,
                        tp: int = 1) -> dict:
    """The serving cache's HBM story for the ``plan --serve`` leg:
    itemized pool + gathered view + the per-slot logits buffer the
    engine keeps device-resident between steps.

    ``fused`` selects the DECODE attention path being priced;
    ``fused_prefill`` the PREFILL path (the two kernels gate shapes
    independently). On the fused decode path the capacity-wide dense
    view is RETIRED — what survives is the prefill lane's per-group
    gather (``[L, prefill_batch, gathered_len, Hkv, hd]``), itemized
    separately as ``prefill_gather_bytes``; with the fused PREFILL
    kernel that last copy vanishes too and the view term reaches
    zero. The retired bytes are itemized so `plan --serve` can state
    the per-replica HBM the kernels bought back.

    ``tp > 1`` prices ONE RANK of a tensor-parallel replica: the pool
    and every gathered view carry the KV-head axis and divide by
    ``tp``; ``last_logits`` is replicated per rank (docs/SERVING.md
    "sharded replicas") and does not."""
    validate_pool_tp(cfg, tp)
    logits = capacity * cfg.vocab_size * 4  # f32 last_logits, replicated
    dense = int(gathered_view_bytes(cfg, spec, capacity)) // tp
    prefill_gather = int(gathered_view_bytes(
        cfg, spec, min(prefill_batch, capacity))) // tp
    if fused_prefill:
        prefill_gather = 0
    if fused:
        view = prefill_gather
    else:
        # the reference decode lane's capacity-wide copy dominates; the
        # group-sized prefill gather is a slice of the same story (it
        # is only itemized separately once the decode view is retired)
        view = dense
        prefill_gather = min(prefill_gather, view)
    return {
        "pool_bytes": int(pool_bytes(cfg, spec)) // tp,
        "gathered_view_bytes": view,
        "gathered_view_retired_bytes": dense - view,
        "prefill_gather_bytes": prefill_gather,
        "last_logits_bytes": int(logits),
    }


class BlockAllocator:
    """Host-side free-list over the pool's blocks, with per-block
    REFCOUNTS so prefix sharing can map one physical block into many
    slot tables (docs/SERVING.md "prefix sharing"). Block 0 (scratch)
    is never handed out. Pure bookkeeping — the device never sees this
    object, only the int32 tables the scheduler builds from it.

    ``alloc`` grants blocks at refcount 1; ``incref`` adds a sharer;
    ``decref`` (and its alias ``free``) drops one reference and returns
    the block to the free list only when the LAST reference dies. A
    decref of a block that is already free refuses with the same
    "double free" error the unref'd allocator raised — releasing a
    reference you do not hold is the bookkeeping bug that silently
    corrupts a *different* request's cache."""

    def __init__(self, spec: PagedPoolSpec):
        self.spec = spec
        self._free: List[int] = list(range(1, spec.n_blocks))
        #: block id -> live reference count (allocated blocks only)
        self._refs: Dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def refcount(self, b: int) -> int:
        """Live references on block ``b`` (0 when free)."""
        return self._refs.get(int(b), 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` block ids at refcount 1, or None when the pool cannot
        satisfy the request (the caller defers admission / preempts —
        never a partial grant, which would strand blocks on a failed
        admit)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids, self._free = self._free[:n], self._free[n:]
        for b in ids:
            self._refs[b] = 1
        return ids

    def incref(self, ids) -> None:
        """Add one reference per id — mapping an already-resident block
        into another slot's table (prefix sharing)."""
        for b in ids:
            b = int(b)
            if self._refs.get(b, 0) < 1:
                raise ValueError(f"incref of unallocated block {b}")
            self._refs[b] += 1

    def decref(self, ids) -> List[int]:
        """Drop one reference per id; returns the ids whose LAST
        reference died (now back on the free list)."""
        freed: List[int] = []
        for b in ids:
            b = int(b)
            if b <= 0 or b >= self.spec.n_blocks:
                raise ValueError(f"freeing invalid block {b}")
            rc = self._refs.get(b, 0)
            if rc < 1:
                raise ValueError(f"double free of block {b}")
            if rc == 1:
                del self._refs[b]
                self._free.append(b)
                freed.append(b)
            else:
                self._refs[b] = rc - 1
        return freed

    def free(self, ids) -> None:
        """Alias for :meth:`decref` — every historical release site
        (retirement, preemption, drain-eviction) is one dropped
        reference, which only *frees* when nothing shares the block."""
        self.decref(ids)


def prefix_block_hashes(tokens, block_size: int) -> List[bytes]:
    """Cumulative digest per FULL block of ``tokens``: digest ``i``
    identifies tokens ``0 .. (i+1)*block_size`` as a chain, so equal
    digests imply equal prefixes (not merely equal blocks — K/V at
    position ``p`` depends on every earlier token, so a block is only
    shareable together with its whole prefix). hashlib keeps the key
    deterministic across processes, unlike Python's seeded ``hash``."""
    toks = np.asarray(tokens, dtype=np.int32).reshape(-1)
    out: List[bytes] = []
    h = b""
    for i in range(toks.size // block_size):
        chunk = toks[i * block_size:(i + 1) * block_size].tobytes()
        h = hashlib.sha1(h + chunk).digest()
        out.append(h)
    return out


class PrefixCache:
    """Prompt-prefix → block-chain cache over one :class:`BlockAllocator`
    (docs/SERVING.md "prefix sharing").

    Maps the cumulative token-hash of each FULL prompt block to the
    pool block holding its K/V. The cache holds exactly ONE reference
    per cached block, so a cached chain outlives the request that
    prefilled it and a later request with the same prefix re-attaches
    by ``incref`` instead of re-prefilling. Entries are LRU-ordered;
    eviction frees only blocks at refcount 1 (the cache is the sole
    holder — a block some live slot still maps is never yanked)."""

    def __init__(self, alloc: BlockAllocator):
        self.alloc = alloc
        #: digest -> block id, oldest-touched first (LRU order)
        self._chain: "OrderedDict[bytes, int]" = OrderedDict()
        #: counters for shared_block_fraction / the smoke's
        #: prefill-once assertion (host bookkeeping only)
        self.shared_tokens = 0
        self.prompt_tokens = 0

    def __len__(self) -> int:
        return len(self._chain)

    def match(self, hashes: Sequence[bytes],
              max_blocks: Optional[int] = None) -> List[int]:
        """Longest cached chain prefix of ``hashes`` (block ids, in
        chain order), capped at ``max_blocks``. Touches hits for LRU."""
        blocks: List[int] = []
        limit = len(hashes) if max_blocks is None else min(
            max_blocks, len(hashes))
        for h in hashes[:limit]:
            b = self._chain.get(h)
            if b is None:
                break
            self._chain.move_to_end(h)
            blocks.append(b)
        return blocks

    def register(self, hashes: Sequence[bytes], blocks: Sequence[int]
                 ) -> None:
        """Publish a prefilled chain: cache each (digest, block) pair
        not yet present, taking one reference per newly cached block. A
        digest already cached under a DIFFERENT block (two requests
        racing the same prefix through separate slots) keeps the first
        publication — the duplicate's blocks stay owned by its slot."""
        for h, b in zip(hashes, blocks):
            if h in self._chain:
                self._chain.move_to_end(h)
                continue
            self.alloc.incref([b])
            self._chain[h] = int(b)

    def evict(self, n_blocks: int) -> int:
        """Free up to ``n_blocks`` pool blocks by dropping LRU entries
        whose block the cache alone holds (refcount 1). Entries whose
        block is still shared by a live slot are skipped — their chain
        suffix may become unreachable until they age out, which is
        bounded by the same LRU walk. Returns blocks actually freed."""
        freed = 0
        for h in list(self._chain):
            if freed >= n_blocks:
                break
            b = self._chain[h]
            if self.alloc.refcount(b) == 1:
                del self._chain[h]
                self.alloc.decref([b])
                freed += 1
        return freed

    @property
    def shared_block_fraction(self) -> float:
        """Fraction of admitted prompt tokens served from cached
        chains instead of prefill (0.0 when nothing shared)."""
        if not self.prompt_tokens:
            return 0.0
        return self.shared_tokens / self.prompt_tokens


def new_block_table(spec: PagedPoolSpec, capacity: int) -> np.ndarray:
    """All-scratch table: every entry points at block 0 until the
    scheduler assigns real blocks on admission."""
    return np.zeros((capacity, spec.blocks_per_slot), np.int32)
