"""A decoder of gated short convolutions with a full-attention layer every
few, and an expert layer that may hold every expert, for serving.

The architecture of Liquid AI's `lfm2_moe` family as its published
configurations give it (LFM2-24B-A2B is one): ``layer_types`` names each
layer ``conv`` or ``full_attention``; the first ``n_dense_layers`` layers
have a SwiGLU MLP, every later one an expert layer (sigmoid scores, a bias
that decides the choice and never the weight, weights normalised, no shared
expert, no groups); RMSNorm in front of both halves and before the head;
tied embeddings.

    h  = x + op(RMSNorm(x))
    x' = h + ffn(RMSNorm(h))

    conv op, on rows y [S, D]:
      [B, C, u] = y W_in                          (D -> 3D, no bias)
      v   = B * u
      c_t = sum_{j < K} w[j] * v_{t - K + 1 + j}  (depthwise, causal, K taps,
                                                   no bias, zeros before 0)
      out = (C * c) W_out                         (D -> D, no bias)

    attention op: q (D -> H x hd), k, v (D -> Hkv x hd), RMSNorm over each
    head of q and of k, rotation at ``rope_theta`` (rotate-half), causal
    softmax at scale hd^-0.5, GQA, o (H x hd -> D), no biases.

Four calling conventions, one set of parameters (as `models/ssm_hybrid.py`):

  * ``model(tokens)`` -> logits: the full forward pass from a zero state;
  * ``model(tokens, cache=pool, pos=.., paged=PagedPrefillView)``: a chunk
    of one slot's prompt;
  * ``model(tokens, cache=pool, pos=.., paged=PagedDecodeView)``: one token
    a slot;
  * ``model(tokens, cache=pool, pos=.., paged=PagedJoinedView)``: both in
    ONE pass (`joins_lanes`; a tick that carries a chunk): ``B == 1``, the
    rows are the ``C`` slots' decode tokens and then the chunk's ``CH``,
    ``pos`` each row's cache position. Every product that reads weights
    runs once over all rows, so a layer's experts are streamed once a
    tick; the rows part only where a lane has a state of its own: the
    attention kernels and the convolution's tails. The head reads the
    decode rows and the one chunk row the view's ``last_row`` keeps.

The paged calls return ``(logits, pool, counts)``. **The pool has two
groups** (`ConvMoeConfig.pool_leaf_shapes`): the attention layers' K and V,
paged by token through the view's ``tables``; and the convolution layers'
tail, A ROW A SLOT whatever the context: the last K - 1 rows of ``v``,
``[L_conv, slots, K - 1, D / 128, 128]`` in the activations' type (the
layout `ops/selective_scan.py`'s `causal_conv_update` reads in place). A
convolution has no other state: there is no recurrence. The real-rows-once
rule of the recurrent decoders holds for the tail word for word (a chunk's
rows that were sent before, or lie past the prompt's end, neither enter the
tail nor write K/V; a chunk whose first real row is position 0 starts from
zeros).

**Heads of 64 on the kernels of heads of 128.** Mosaic slices a pool block
out of HBM only where a cached row is whole 128-lane tiles, so with
``head_dim`` 64 the attention group keeps TWO KV HEADS SIDE BY SIDE in one
row, ``[L_attn, n_blocks, P, Hkv / 2, 128]`` (`ops.attention.pair_kv_heads`:
the same bytes a token, relabelled). A query head is laid into its KV
head's half of the lanes with zeros in the other
(`ops.attention.pair_query_heads`), so the kernels' body for 128-wide heads
computes the right scores unchanged, at the model's scale ``64^-0.5``; a
head's output is its half of the 128 (`ops.attention.unpair_heads`). Twice
the score and value FLOPs, no more bytes.

The expert layer is `models/held_experts.py:HeldExperts` with
``expert_choice = "noaux_tc"`` at one group; the experts of all expert
layers are one stack at the tree's top level, read at the layer's index.
Consecutive layers of one kind are one scanned module (``run_<i>``), the
runs one after another: a stack that is a parameter of the program is read
in place (`models/ssm_hybrid.py` says what a scan around scans costs).

Scopes (`docs/OBSERVABILITY.md`): ``shortconv`` a convolution mixer whole,
``shortconv_state`` inside it the reads and writes of the carried tail,
``attn`` / ``kv_pool`` the attention mixer's, ``mlp`` the dense layers',
``moe_router`` / ``moe_dispatch`` / ``moe_experts``, ``lm_head``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.held_experts import (  # noqa: F401
    HeldExperts, _mm, _normal, generate_greedy,
)
from ray_lightning_tpu.ops.norms import rms_norm
from ray_lightning_tpu.ops.rope import apply_rope, rope_frequencies
from ray_lightning_tpu.ops.selective_scan import (
    causal_conv, causal_conv_update, lane_join, lane_split, state_shape,
)

CONV, ATTENTION = "conv", "full_attention"
_LANES = 128


@dataclasses.dataclass(frozen=True)
class ConvMoeConfig:
    vocab_size: int = 65536
    dim: int = 2048
    #: the kind of each layer, in order: "conv" or "full_attention"
    layer_types: Tuple[str, ...] = (CONV, CONV, ATTENTION, CONV)
    #: leading layers whose ffn is a SwiGLU MLP; every later one routes
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    #: the dense MLP's width
    hidden_dim: int = 11776
    moe_hidden_dim: int = 1536
    #: the router's width: every expert of the layer, held here or not
    n_routed_experts: int = 64
    n_experts_per_tok: int = 4
    #: the experts this chip holds: [first, first + held); None = all
    experts_first: int = 0
    experts_held: Optional[int] = None
    routed_scaling_factor: float = 1.0
    #: taps of the depthwise convolution (`conv_L_cache`)
    conv_taps: int = 3
    max_seq_len: int = 4096
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    dtype: Any = jnp.float32
    #: the serving engine's ambient kernel policy (False = never pallas)
    use_flash: bool = True

    #: how `held_experts.route` chooses (no field: the family has one way):
    #: the biased scores' top k, at ONE group, the chosen scores normalised
    #: over their sum + `route_norm_eps`
    expert_choice = "noaux_tc"
    n_group = 1
    topk_group = 1
    route_norm_eps = 1e-6

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        other = set(self.layer_types) - {CONV, ATTENTION}
        if other or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(other)}; a layer is "
                             f"{CONV!r} or {ATTENTION!r}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError(f"n_dense_layers {self.n_dense_layers} lies "
                             f"outside the {len(self.layer_types)} layers")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if not 0 <= self.experts_first <= (
                self.n_routed_experts - self.held):
            raise ValueError(
                f"held experts [{self.experts_first}, "
                f"{self.experts_first + self.held}) lie outside the "
                f"router's {self.n_routed_experts}")
        state_shape(self.conv_taps - 1, self.dim)      # the channels split

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    @property
    def n_attn_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def n_conv_layers(self) -> int:
        return self.layer_types.count(CONV)

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def pairs_heads(self) -> bool:
        """Two KV heads a 128-lane row of the pool (module text)."""
        return 2 * self.head_dim == _LANES and self.n_kv_heads % 2 == 0

    @property
    def kv_row(self) -> Tuple[int, int]:
        """One cached token's K (or V) in an attention layer's leaf."""
        if self.pairs_heads:
            return (self.n_kv_heads // 2, _LANES)
        return (self.n_kv_heads, self.head_dim)

    @property
    def hit_words(self) -> int:
        """int32 words of a tick's (expert layer, expert) bitset."""
        return -(-self.n_expert_layers * self.held // 32)

    def runs(self):
        """Consecutive layers of one kind and one ffn, as (attention,
        dense, first layer, layers) in order: each is one scanned module."""
        out = []
        for i, kind in enumerate(self.layer_types):
            key = (kind == ATTENTION, i < self.n_dense_layers)
            if out and out[-1][:2] == key:
                out[-1] = (*key, out[-1][2], out[-1][3] + 1)
            else:
                out.append((*key, i, 1))
        return out

    def pool_leaf_shapes(self, n_blocks: int, block_size: int,
                         state_slots: int):
        """The pool's leaves: the attention layers' K and V over the
        allocator's ``n_blocks`` (a row a token), then the convolution
        layers' tail (a row a slot, ``state_slots`` of them)."""
        kv = (self.n_attn_layers, n_blocks, block_size, *self.kv_row)
        return (kv, kv,
                (self.n_conv_layers, state_slots,
                 *state_shape(self.conv_taps - 1, self.dim)))

    @classmethod
    def tiny(cls, **kw) -> "ConvMoeConfig":
        """CPU-test size whose shapes still pass the kernels' gates: a
        dense convolution layer, then two periods of an attention layer
        and convolution layers, heads of 64 in pairs."""
        base = dict(vocab_size=96, dim=128,
                    layer_types=(CONV, ATTENTION, CONV, CONV, ATTENTION,
                                 CONV),
                    n_dense_layers=1, n_heads=4, n_kv_heads=2, head_dim=64,
                    hidden_dim=96, moe_hidden_dim=32, n_routed_experts=8,
                    n_experts_per_tok=2, max_seq_len=256)
        base.update(kw)
        return cls(**base)


def _is_joined_view(paged) -> bool:
    """Is this paged view a tick's two lanes joined (the decode rows, then
    the chunk's, in one call)?"""
    from ray_lightning_tpu.ops.attention import PagedJoinedView

    return isinstance(paged, PagedJoinedView)


class ConvMoeBlock(nn.Module):
    """One layer: ``attention`` says which op, ``dense`` which ffn.
    ``group_layer`` is its index among the layers of its op's kind (its row
    of that group's leaves), ``expert_layer`` among the expert layers (its
    row of the experts' stack)."""

    cfg: ConvMoeConfig
    attention: bool = False
    dense: bool = False

    def _attention(self, u, cos, sin, cache, pos, paged, group_layer,
                   use_pallas):
        cfg = self.cfg
        dt = cfg.dtype
        d, nh, nkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        p = self.param
        ones = nn.initializers.ones
        wq = p("wq", _normal(), (d, nh * hd))
        wk = p("wk", _normal(), (d, nkv * hd))
        wv = p("wv", _normal(), (d, nkv * hd))
        wo = p("wo", _normal(), (nh * hd, d))
        head_norm = lambda name, x: rms_norm(
            x, p(name, ones, (hd,)), cfg.norm_eps, use_pallas=False)
        b, s = u.shape[:2]
        q = head_norm("q_norm", _mm(u, wq, dt).reshape(b, s, nh, hd))
        k = head_norm("k_norm", _mm(u, wk, dt).reshape(b, s, nkv, hd))
        v = _mm(u, wv, dt).reshape(b, s, nkv, hd)
        joined = _is_joined_view(paged)
        if cache is None:
            positions = None
        elif joined:               # a position a row, decode rows first
            positions = pos[None, :]
        elif pos.ndim == 0:        # a chunk: token j at pos + j
            positions = jnp.broadcast_to(
                pos + jnp.arange(s)[None, :], (b, s))
        else:                      # decode: one token a slot
            positions = pos[:, None]
        q = apply_rope(q, cos, sin, positions=positions)
        k = apply_rope(k, cos, sin, positions=positions)
        if cache is None:
            from ray_lightning_tpu.ops.attention import dot_product_attention

            out = dot_product_attention(q, k, v, causal=True)
            return _mm(out.reshape(b, s, nh * hd), wo, dt), None
        from ray_lightning_tpu.ops.attention import (
            PagedPrefillView, pair_kv_heads, pair_query_heads,
            paged_attention, paged_prefill, unpair_heads,
        )

        if cfg.pairs_heads:
            q, k, v = (pair_query_heads(q, nkv), pair_kv_heads(k),
                       pair_kv_heads(v))
        pk, pv = cache[:2]
        prefill = isinstance(paged, PagedPrefillView)
        if joined:
            # both lanes' rows in one call: the decode rows land at their
            # (scratch-redirected) write index and the chunk's through its
            # own, ONE scatter a leaf. The slot that takes the chunk is not
            # decoding, so neither lane sees a row the other wrote
            assert b == 1, "one chunk a tick"
            dec, chunk = paged.decode, paged.prefill
            n_dec = dec.tables.shape[0]
            rows = lambda x: x[0]
            at = (group_layer,
                  jnp.concatenate([dec.write_block, chunk.write_block[0]]),
                  jnp.concatenate([dec.write_offset, chunk.write_offset[0]]))
        else:
            assert prefill or s == 1, "the decode path takes one token a slot"
            rows = (lambda x: x) if prefill else (lambda x: x[:, 0])
            at = (group_layer, paged.write_block, paged.write_offset)
        # write-then-attend, the paged lanes' ordering
        with jax.named_scope("kv_pool"):
            pk = pk.at[at].set(rows(k).astype(pk.dtype))
            pv = pv.at[at].set(rows(v).astype(pv.dtype))
        # the model's scale, whatever width the kernel sees
        kw = dict(scale=hd ** -0.5, use_pallas=use_pallas,
                  layer=group_layer)
        if joined:
            # the rows part for the kernels, each called as its own lane
            # calls it, over the same carried pool
            out = jnp.concatenate([
                paged_attention(q[0, :n_dec], pk, pv, dec.tables,
                                dec.lengths, **kw)[None],
                paged_prefill(q[:, n_dec:], pk, pv, chunk.tables,
                              pos[n_dec], **kw)], axis=1)
        elif prefill:
            out = paged_prefill(q, pk, pv, paged.tables, pos, **kw)
        else:
            out = paged_attention(q[:, 0], pk, pv, paged.tables,
                                  paged.lengths, **kw)[:, None]
        if cfg.pairs_heads:
            out = unpair_heads(out, nkv)
        return (_mm(out.reshape(b, s, nh * hd), wo, dt),
                (pk, pv, *cache[2:]))

    def _short_conv(self, u, cache, pos, paged, group_layer):
        cfg = self.cfg
        dt = cfg.dtype
        d, kc = cfg.dim, cfg.conv_taps
        p = self.param
        w_in = p("in_proj", _normal(), (d, 3 * d))
        conv_w = p("conv_weight", _normal(0.2), (kc, d))
        w_out = p("out_proj", _normal(), (d, d))
        no_bias = jnp.zeros((d,), jnp.float32)

        b, s = u.shape[:2]
        bg, cg, x = jnp.split(_mm(u, w_in, dt), 3, axis=-1)    # [b, s, D]
        v = bg * x
        if cache is None:
            # the whole sequence from zeros: every row is real
            tail = jnp.zeros((kc - 1, d), dt)
            conv = jax.vmap(lambda rows: causal_conv(
                rows, tail, conv_w, no_bias, 0, s - 1)[0])(v)
            return _mm(cg.astype(jnp.float32) * conv, w_out, dt), None
        from ray_lightning_tpu.ops.attention import PagedPrefillView

        tails = cache[2]
        if _is_joined_view(paged):
            # both lanes' rows went through `in_proj` together and go
            # through `out_proj` together; they part for the convolution
            # alone, each lane's as its own branch below has it. The slot
            # that takes the chunk is not decoding: its row of the leaf is
            # the chunk's, every other row the decode lane's
            dec, chunk = paged.decode, paged.prefill
            n_dec = dec.tables.shape[0]
            slot = chunk.state_slot
            first, last = chunk.real_rows[0], chunk.real_rows[1]
            with jax.named_scope("shortconv_state"):
                tail = tails[group_layer]          # [C, K - 1, Ds, 128]
                began = lane_join(jnp.where(
                    pos[n_dec] + first > 0, tail[slot],
                    0.0).astype(tails.dtype))
            stepped, moved = causal_conv_update(v[0, :n_dec], tail, conv_w,
                                                no_bias)
            chunked, ended = causal_conv(v[0, n_dec:], began, conv_w,
                                         no_bias, first, last)
            conv = jnp.concatenate([stepped, chunked])[None]
            with jax.named_scope("shortconv_state"):
                moved = jnp.where(dec.state_moves[:, None, None, None],
                                  moved, tail)
                tails = tails.at[group_layer].set(
                    moved.at[slot].set(lane_split(ended)))
        elif isinstance(paged, PagedPrefillView):
            # one slot's chunk: its real rows, once
            slot = paged.state_slot
            first, last = paged.real_rows[0], paged.real_rows[1]
            with jax.named_scope("shortconv_state"):
                # a chunk whose first real row is position 0 starts from
                # zeros: whatever the slot held is another request's
                tail = lane_join(jnp.where(
                    pos + first > 0, tails[group_layer, slot],
                    0.0).astype(tails.dtype))
            conv, tail = causal_conv(v[0], tail, conv_w, no_bias, first,
                                     last)
            conv = conv[None]
            with jax.named_scope("shortconv_state"):
                tails = tails.at[group_layer, slot].set(lane_split(tail))
        else:
            assert s == 1, "the decode path takes one token a slot"
            moves = paged.state_moves
            with jax.named_scope("shortconv_state"):
                tail = tails[group_layer]          # [C, K - 1, Ds, 128]
            conv, moved = causal_conv_update(v[:, 0], tail, conv_w, no_bias)
            conv = conv[:, None]
            with jax.named_scope("shortconv_state"):
                tails = tails.at[group_layer].set(jnp.where(
                    moves[:, None, None, None], moved, tail))
        return (_mm(cg.astype(jnp.float32) * conv, w_out, dt),
                (*cache[:2], tails))

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, pos=None, paged=None,
                 group_layer=None, expert_layer=None, stacks=None):
        cfg = self.cfg
        d, dt = cfg.dim, cfg.dtype
        # the view's STATIC use_pallas (the serve engine's build-time
        # decision) pins the kernels; absent that, the ambient policy
        # (a joined view's lanes carry the same one)
        lane = paged.decode if _is_joined_view(paged) else paged
        use_pallas = None if lane is None else lane.use_pallas
        if use_pallas is None and not cfg.use_flash:
            use_pallas = False
        norm = lambda name, v: rms_norm(
            v, self.param(name, nn.initializers.ones, (d,)), cfg.norm_eps,
            use_pallas=False)
        u = norm("operator_norm", x)
        if self.attention:
            with jax.named_scope("attn"):
                mixed, new_cache = self._attention(
                    u, cos, sin, cache, pos, paged, group_layer, use_pallas)
        else:
            with jax.named_scope("shortconv"):
                mixed, new_cache = self._short_conv(u, cache, pos, paged,
                                                    group_layer)
        h = x + mixed.astype(x.dtype)
        y = norm("ffn_norm", h)
        if self.dense:
            f = cfg.hidden_dim
            with jax.named_scope("mlp"):
                gate, up = jnp.split(_mm(
                    y, self.param("gate_up", _normal(), (d, 2 * f)), dt),
                    2, axis=-1)
                out = _mm(nn.silu(gate) * up,
                          self.param("down", _normal(), (f, d)), dt)
            return h + out.astype(x.dtype), new_cache, ()
        b, s = y.shape[:2]
        routed, counts, hits = HeldExperts(cfg, with_hits=True,
                                           name="experts")(
            y.reshape(b * s, d), stacks, expert_layer, use_pallas)
        return (h + routed.reshape(b, s, d).astype(x.dtype), new_cache,
                (counts, hits))


def _pack_bits(bits):
    """bool ``[n]`` -> int32 ``[ceil(n / 32)]``, bit ``i % 32`` of word
    ``i // 32``."""
    n = bits.shape[0]
    words = jnp.pad(bits, (0, -n % 32)).reshape(-1, 32).astype(jnp.uint32)
    packed = jnp.sum(words << jnp.arange(32, dtype=jnp.uint32), axis=1,
                     dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


class ConvMoe(nn.Module):
    """Token ids [B, S] -> logits [B, S, V] (see the module's text)."""

    cfg: ConvMoeConfig

    #: what the serving engine has to refuse for this decoder, each with
    #: its reason (`serve/engine.py:why_unsupported`)
    serving_unsupported = {
        "reference_lanes": "it serves through its paged kernels only",
        "speculative": (
            "a rejected draft token has already moved the convolution "
            "layers' tails on, which keep no earlier row to roll back to"),
        "prefill_batch": (
            "a left-padded group would run its pad columns through the "
            "convolutions' tails"),
        "tensor_parallel": (
            "it publishes no parameter placement, and its paged kernels "
            "and expert product have no manual region"),
        "prefix_cache": (
            "a shared block carries K/V and no tail: a request that "
            "skipped a cached prefix would start its convolution layers "
            "from zeros"),
    }
    kv_window = None
    #: its convolution layers keep a row a slot in the pool
    #: (`serve/kv_cache.py` "a row a slot")
    slot_state = True
    #: a `PagedJoinedView` is served: a tick's decode rows and its prefill
    #: chunk in one pass, every expert layer streamed once
    #: (`serve/engine.py:joins_lanes`)
    joins_lanes = True

    @property
    def tick_counters(self):
        """Device-side counts a paged call returns beside the pool, and how
        the engine joins those of a tick's two lanes: the rows the held
        experts got (summed over the expert layers) and the fullest
        expert's; the (expert layer, expert) pairs that got a row, a
        bitset a lane whose UNION the engine counts; then the slot-state
        pair, the rows the prefill lane's convolutions took as real and the
        slots whose tails the decode lane moved."""
        return (("expert_rows", "sum"), ("expert_rows_max", "max"),
                ("experts_hit", "union", self.cfg.hit_words),
                ("conv_rows", "sum"), ("state_slots", "sum"))

    def serving_param_specs(self):
        """No published placement: a replica holds the model whole."""
        return {}

    def _kernel_shapes(self, rows: Tuple[int, ...], block_size: int):
        """(q, pool block) as the paged kernels see them: heads of 64 in
        pairs are heads of 128 there."""
        cfg = self.cfg
        row = cfg.kv_row
        return (*rows, cfg.n_heads, row[1]), (block_size, *row)

    def decode_tile_tokens(self, block_size: int, blocks_per_slot: int):
        from ray_lightning_tpu.ops.pallas.paged_attention import (
            decode_tile_tokens,
        )

        return decode_tile_tokens(block_size, blocks_per_slot)

    def prefill_tile_shape(self, prefill_batch: int, prefill_chunk: int,
                           block_size: int, blocks_per_slot: int):
        from ray_lightning_tpu.ops.pallas.paged_prefill import (
            prefill_tile_shape,
        )

        return prefill_tile_shape(
            *self._kernel_shapes((prefill_batch, prefill_chunk), block_size),
            blocks_per_slot)

    def paged_lanes(self, capacity: int, prefill_batch: int,
                    prefill_chunk: int, pool_block, use_pallas):
        """(decode, prefill): would the paged lanes take the kernels at
        these shapes? ``pool_block`` = (n_blocks, block_size)."""
        from ray_lightning_tpu.ops.attention import (
            paged_attention_uses_pallas,
            paged_prefill_uses_pallas,
        )

        n_blocks, block_size = pool_block
        q, block = self._kernel_shapes((capacity,), block_size)
        qp, _ = self._kernel_shapes((prefill_batch, prefill_chunk),
                                    block_size)
        return (paged_attention_uses_pallas(q, (n_blocks, *block),
                                            use_pallas),
                paged_prefill_uses_pallas(qp, (n_blocks, *block),
                                          use_pallas))

    @nn.compact
    def __call__(self, tokens, cache=None, pos=None, pad=None, paged=None):
        cfg = self.cfg
        if pad is not None:
            raise ValueError("ConvMoe has no left-padded (batched "
                             "prefill) cache path")
        if (cache is None) != (paged is None):
            raise ValueError("ConvMoe's cache path is the paged pool: "
                             "pass cache=<its three leaves> together with "
                             "paged=<view>")
        slot_counts = None
        joined = _is_joined_view(paged)
        if paged is not None:
            from ray_lightning_tpu.ops.attention import PagedPrefillView

            # the slot-state pair, each half from the lane that has it
            lanes = (paged.decode, paged.prefill) if joined else (paged,)
            conv_rows = state_slots = jnp.int32(0)
            for lane in lanes:
                if isinstance(lane, PagedPrefillView):
                    if lane.real_rows is None or lane.state_slot is None:
                        raise ValueError(
                            "ConvMoe's prefill view names the chunk's real "
                            "rows and its slot (real_rows, state_slot)")
                    first, last = lane.real_rows[0], lane.real_rows[1]
                    conv_rows = jnp.maximum(last - first + 1, 0)
                else:
                    if lane.state_moves is None:
                        raise ValueError("ConvMoe's decode view says whose "
                                         "tails move (state_moves)")
                    state_slots = jnp.sum(
                        lane.state_moves.astype(jnp.int32))
            slot_counts = jnp.stack([conv_rows, state_slots])
        embed = self.param("tok_embed", _normal(), (cfg.vocab_size, cfg.dim))
        x = embed[tokens].astype(cfg.dtype)
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        if cache is None:
            cos, sin = cos[: tokens.shape[1]], sin[: tokens.shape[1]]

        # every expert layer's held experts in one stack, handed to the
        # scans whole beside the layer index (see `HeldExperts`)
        stacks = None
        if cfg.n_expert_layers:
            f, shape = cfg.moe_hidden_dim, (cfg.n_expert_layers, cfg.held)
            stacks = (self.param("experts_gate_up", _normal(),
                                 (*shape, cfg.dim, 2 * f)),
                      self.param("experts_down", _normal(),
                                 (*shape, f, cfg.dim)))

        def body(blk, carry, group_layer, expert_layer, cos, sin, pos,
                 paged, stacks):
            x, cache = carry
            x, cache, counted = blk(x, cos, sin, cache, pos, paged,
                                    group_layer, expert_layer, stacks)
            return (x, cache), counted

        carry, counted = (x, cache), []
        seen = {True: 0, False: 0}          # layers of each op so far
        for i, (attention, dense, first, n) in enumerate(cfg.runs()):
            carry, out = nn.scan(
                body, variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(0, 0) + (nn.broadcast,) * 5, length=n,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(ConvMoeBlock(cfg, attention, dense, name=f"run_{i}"), carry,
              seen[attention] + jnp.arange(n),
              first - cfg.n_dense_layers + jnp.arange(n), cos, sin, pos,
              paged, stacks)
            seen[attention] += n
            if not dense:
                counted.append(out)
        x, new_cache = carry

        if joined:
            # the head reads the C decode rows and the ONE row of the chunk
            # the step keeps, taken before the product: [C + 1, V] logits,
            # not [C + CH, V]
            n_dec = paged.decode.tables.shape[0]
            x = jnp.concatenate([
                x[:, :n_dec], jax.lax.dynamic_slice_in_dim(
                    x, n_dec + paged.last_row, 1, axis=1)], axis=1)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.dim,)), cfg.norm_eps,
                     use_pallas=False)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum(
                "bsd,vd->bsv", x.astype(cfg.dtype), embed.astype(cfg.dtype),
                preferred_element_type=jnp.float32)
        if cache is None:
            return logits
        if counted:
            rows = jnp.concatenate([c for c, _ in counted], 0)   # [Le, 2]
            hits = jnp.concatenate([h for _, h in counted], 0)   # [Le, held]
            expert_counts = jnp.stack([jnp.sum(rows[:, 0]),
                                       jnp.max(rows[:, 1])])
        else:
            hits = jnp.zeros((0, cfg.held), bool)
            expert_counts = jnp.zeros((2,), jnp.int32)
        return logits, new_cache, jnp.concatenate(
            [expert_counts, _pack_bits(hits.reshape(-1)), slot_counts])
