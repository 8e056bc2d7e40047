"""A decoder whose layers alternate sliding-window and full attention, with
a parallel attention-and-experts block, for serving.

The architecture of Cohere's `cohere2_moe` family as its published
configurations give it (command-a-plus's language model is one): periods
of ``period - 1`` sliding-window layers (RoPE, each row sees the last
``window`` tokens) and one full layer (no rotation, each row sees every
earlier token); GQA; ONE mean-centred LayerNorm a layer whose output both
halves read (`use_parallel_block`); every layer an expert layer: a plain
top-k of sigmoid scores over ``n_routed_experts``, weights normalised,
beside ``n_shared_experts`` shared experts averaged; tied embeddings and a
logit scale.

    h  = LayerNorm(x)
    x' = x + Attn(h) + sum_{chosen e HELD here} w_e E_e(h)
           + 1/n_shared sum_j S_j(h)

Three calling conventions, one set of parameters (as `models/mla_moe.py`):

  * ``model(tokens)`` -> logits: the full forward pass, the tests' anchor
    and `generate_greedy`;
  * ``model(tokens, cache=pool, pos=.., paged=PagedPrefillView)``: a chunk
    of one slot's prompt through the paged pool;
  * ``model(tokens, cache=pool, pos=.., paged=PagedDecodeView)``: one token
    a slot.

The paged calls return ``(logits, pool, counts)``. **The pool has two
groups** (`WindowMoeConfig.pool_leaf_shapes`): the full layers' K and V,
``[L_full, n_blocks, P, Hkv, hd]``, paged on demand through the view's
``tables`` exactly as a dense decoder's; and the window layers' K and V,
``[L_win, window_blocks, P, Hkv, hd]``, a ring a slot addressed through
the view's ``window_tables`` (`serve/kv_cache.py` "two groups"). Both are
carried whole through the scans and written at ``[layer, block, offset]``
(the stacked-pool rule of `models/llama.py`); attention over them is the
dense paged path (`ops.attention.paged_attention` / `paged_prefill`) with
the layer's ``window``. The model scans over PERIODS (an inner scan over
a period's window layers, then its full layer), not over alike layers.

The expert layer is `models/held_experts.py:HeldExperts` with
``expert_choice = "topk"``; the shared experts run as one SwiGLU of width
``n_shared x F`` times ``1 / n_shared`` (the mean of the experts is the
sum of their down-projected halves over n).

RoPE pairs dimension ``i`` with ``i + d/2`` (rotate-half, `ops/rope.py`);
the published code (`rope_gptj`) pairs ``2i`` with ``2i + 1``, which is
this model under a fixed permutation of the columns of ``wq`` and ``wk``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.held_experts import (  # noqa: F401
    HeldExperts, _mm, _normal, generate_greedy,
)
from ray_lightning_tpu.ops.norms import layer_norm
from ray_lightning_tpu.ops.rope import apply_rope, rope_frequencies


@dataclasses.dataclass(frozen=True)
class WindowMoeConfig:
    vocab_size: int = 262144
    dim: int = 4096
    n_layers: int = 32
    #: layers of one period: ``period - 1`` window layers, then a full one
    period: int = 4
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    #: tokens a window layer's row sees, itself included
    window: int = 4096
    moe_hidden_dim: int = 4096
    #: the router's width: every expert of the layer, held here or not
    n_routed_experts: int = 128
    n_experts_per_tok: int = 8
    n_shared_experts: int = 4
    #: the experts this chip holds: [first, first + held); None = all
    experts_first: int = 0
    experts_held: Optional[int] = None
    max_seq_len: int = 16384
    norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    logit_scale: float = 1.0
    dtype: Any = jnp.float32
    #: the serving engine's ambient kernel policy (False = never pallas)
    use_flash: bool = True

    #: how `held_experts.route` chooses (no field: the family has one way)
    expert_choice = "topk"

    def __post_init__(self):
        if self.period < 2 or self.n_layers % self.period:
            raise ValueError(
                f"n_layers {self.n_layers} must be whole periods of "
                f"{self.period} (window layers, then a full one)")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        if not 0 <= self.experts_first <= (
                self.n_routed_experts - self.held):
            raise ValueError(
                f"held experts [{self.experts_first}, "
                f"{self.experts_first + self.held}) lie outside the "
                f"router's {self.n_routed_experts}")

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    @property
    def n_window_layers(self) -> int:
        return self.n_periods * (self.period - 1)

    def pool_leaf_shapes(self, n_blocks: int, block_size: int,
                         window_blocks: int):
        """The paged pool's leaves by kind of layer: the full layers' K and
        V over the allocator's ``n_blocks``, then the window layers' K and V
        over the ``window_blocks`` of their rings (scratch block 0 in
        each)."""
        row = (block_size, self.n_kv_heads, self.head_dim)
        full = (self.n_periods, n_blocks, *row)
        win = (self.n_window_layers, window_blocks, *row)
        return (full, full, win, win)

    @classmethod
    def tiny(cls, **kw) -> "WindowMoeConfig":
        """CPU-test size whose shapes still pass the kernels' gates."""
        base = dict(vocab_size=96, dim=64, n_layers=4, period=4, n_heads=4,
                    n_kv_heads=2, head_dim=128, window=24, moe_hidden_dim=32,
                    n_routed_experts=16, n_experts_per_tok=4,
                    n_shared_experts=2, experts_first=0, experts_held=8,
                    max_seq_len=256)
        base.update(kw)
        return cls(**base)


class WindowMoeBlock(nn.Module):
    """One layer: ``windowed`` says which kind. ``group_layer`` is its index
    among the layers of its kind (its row of that group's pool leaves),
    ``layer`` its index among all layers (its row of the experts' stack)."""

    cfg: WindowMoeConfig
    windowed: bool = True

    def _attention(self, h, cos, sin, cache, pos, paged, group_layer,
                   use_pallas):
        cfg = self.cfg
        dt = cfg.dtype
        d, nh, nkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        window = cfg.window if self.windowed else None
        p = self.param
        wq = p("wq", _normal(), (d, nh * hd))
        wk = p("wk", _normal(), (d, nkv * hd))
        wv = p("wv", _normal(), (d, nkv * hd))
        wo = p("wo", _normal(), (nh * hd, d))
        b, s = h.shape[:2]
        q = _mm(h, wq, dt).reshape(b, s, nh, hd)
        k = _mm(h, wk, dt).reshape(b, s, nkv, hd)
        v = _mm(h, wv, dt).reshape(b, s, nkv, hd)
        if self.windowed:          # a full layer has no rotation (NoPE)
            if cache is None:
                positions = None
            elif pos.ndim == 0:    # a chunk: token j at pos + j
                positions = jnp.broadcast_to(
                    pos + jnp.arange(s)[None, :], (b, s))
            else:                  # decode: one token a slot
                positions = pos[:, None]
            q = apply_rope(q, cos, sin, positions=positions)
            k = apply_rope(k, cos, sin, positions=positions)
        if cache is None:
            from ray_lightning_tpu.ops.attention import dot_product_attention

            mask = None
            if window is not None:
                t = jnp.arange(s)
                mask = (t[:, None] - t[None, :] < window)[None, None]
            out = dot_product_attention(q, k, v, causal=True, mask=mask)
            return _mm(out.reshape(b, s, nh * hd), wo, dt), None
        from ray_lightning_tpu.ops.attention import (
            PagedPrefillView, paged_attention, paged_prefill,
        )

        # this layer's group of the pool, and that group's table
        full_k, full_v, win_k, win_v = cache
        if self.windowed:
            pk, pv = win_k, win_v
            tables, write_block = (paged.window_tables,
                                   paged.window_write_block)
        else:
            pk, pv = full_k, full_v
            tables, write_block = paged.tables, paged.write_block
        # write-then-attend, the paged lanes' ordering: a token's own K/V
        # is visible to its query. A chunk's rows go in whole; the decode
        # lane has one token a slot
        prefill = isinstance(paged, PagedPrefillView)
        assert prefill or s == 1, "the decode path takes one token a slot"
        rows = (lambda x: x) if prefill else (lambda x: x[:, 0])
        with jax.named_scope("kv_pool"):
            at = (group_layer, write_block, paged.write_offset)
            pk = pk.at[at].set(rows(k).astype(pk.dtype))
            pv = pv.at[at].set(rows(v).astype(pv.dtype))
        kw = dict(use_pallas=use_pallas, layer=group_layer, window=window)
        if prefill:
            out = paged_prefill(q, pk, pv, tables, pos, **kw)
        else:
            out = paged_attention(q[:, 0], pk, pv, tables, paged.lengths,
                                  **kw)[:, None]
        new_cache = ((full_k, full_v, pk, pv) if self.windowed
                     else (pk, pv, win_k, win_v))
        return _mm(out.reshape(b, s, nh * hd), wo, dt), new_cache

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, pos=None, paged=None,
                 group_layer=None, layer=None, stacks=None):
        cfg = self.cfg
        d, dt = cfg.dim, cfg.dtype
        # the view's STATIC use_pallas (the serve engine's build-time
        # decision) pins the kernels; absent that, the ambient policy
        use_pallas = None if paged is None else paged.use_pallas
        if use_pallas is None and not cfg.use_flash:
            use_pallas = False
        # one norm a layer: both halves read it (the parallel block)
        h = layer_norm(x, self.param("norm", nn.initializers.ones, (d,)),
                       cfg.norm_eps)
        with jax.named_scope("attn_window" if self.windowed
                             else "attn_full"):
            attn, new_cache = self._attention(
                h, cos, sin, cache, pos, paged, group_layer, use_pallas)
        b, s = h.shape[:2]
        routed, counts = HeldExperts(cfg, name="experts")(
            h.reshape(b * s, d), stacks, layer, use_pallas)
        with jax.named_scope("mlp"):
            # the shared experts side by side: the mean of n experts is
            # the down product of their joined halves over n
            n = cfg.n_shared_experts
            f = cfg.moe_hidden_dim * n
            gate, up = jnp.split(_mm(
                h, self.param("shared_gate_up", _normal(), (d, 2 * f)), dt),
                2, axis=-1)
            shared = _mm(nn.silu(gate) * up,
                         self.param("shared_down", _normal(), (f, d)), dt)
        y = (attn.astype(jnp.float32) + routed.reshape(b, s, d)
             + shared.astype(jnp.float32) / n)
        return x + y.astype(x.dtype), new_cache, counts


class WindowMoePeriod(nn.Module):
    """``period - 1`` window layers under one scan, then the full layer."""

    cfg: WindowMoeConfig

    @nn.compact
    def __call__(self, carry, index, cos, sin, pos, paged, stacks):
        cfg = self.cfg
        n_win = cfg.period - 1

        def body(blk, carry, j, index, cos, sin, pos, paged, stacks):
            x, cache = carry
            x, cache, counts = blk(x, cos, sin, cache, pos, paged,
                                   index * n_win + j,
                                   index * cfg.period + j, stacks)
            return (x, cache), counts

        carry, counts = nn.scan(
            body, variable_axes={"params": 0}, split_rngs={"params": True},
            in_axes=(0,) + (nn.broadcast,) * 6, length=n_win,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(WindowMoeBlock(cfg, True, name="window_layers"), carry,
          jnp.arange(n_win), index, cos, sin, pos, paged, stacks)
        x, cache = carry
        x, cache, last = WindowMoeBlock(cfg, False, name="full_layer")(
            x, cos, sin, cache, pos, paged, index,
            index * cfg.period + n_win, stacks)
        return (x, cache), jnp.concatenate([counts, last[None]], 0)


class WindowMoe(nn.Module):
    """Token ids [B, S] -> logits [B, S, V] (see the module's text)."""

    cfg: WindowMoeConfig

    #: device-side counts a paged call returns beside the pool, and how
    #: the engine joins those of a tick's two lanes
    tick_counters = (("expert_rows", "sum"), ("expert_rows_max", "max"))
    #: what the serving engine has to refuse for this decoder; the prefix
    #: cache because a window group keeps no block a later request could
    #: share (its ring is overwritten as the context moves on)
    serving_unsupported = ("reference_lanes", "speculative",
                           "prefill_batch", "tensor_parallel",
                           "prefix_cache")
    #: no recurrent layers: no leaf of the pool holds a row a slot
    slot_state = False

    @property
    def kv_window(self) -> int:
        """The window layers' window: the engine keeps their K/V in a
        group of its own (`serve/kv_cache.py:window_ring_blocks`)."""
        return self.cfg.window

    def serving_param_specs(self):
        """No published placement: a replica holds its share whole."""
        return {}

    def decode_tile_tokens(self, block_size: int, blocks_per_slot: int):
        """Tokens of the paged decode kernel's KV tile (`models/llama.py`
        says what stating one means)."""
        from ray_lightning_tpu.ops.pallas.paged_attention import (
            decode_tile_tokens,
        )

        return decode_tile_tokens(block_size, blocks_per_slot)

    def prefill_tile_shape(self, prefill_batch: int, prefill_chunk: int,
                           block_size: int, blocks_per_slot: int):
        """(query tile rows, KV tile tokens) of the paged prefill kernel
        for the engine's chunk, by the kernel's own rule."""
        from ray_lightning_tpu.ops.pallas.paged_prefill import (
            prefill_tile_shape,
        )

        cfg = self.cfg
        return prefill_tile_shape(
            (prefill_batch, prefill_chunk, cfg.n_heads, cfg.head_dim),
            (block_size, cfg.n_kv_heads, cfg.head_dim), blocks_per_slot)

    def paged_lanes(self, capacity: int, prefill_batch: int,
                    prefill_chunk: int, pool_block, use_pallas):
        """(decode, prefill): would the paged lanes take the kernels at
        these shapes? ``pool_block`` = (n_blocks, block_size)."""
        from ray_lightning_tpu.ops.attention import (
            paged_attention_uses_pallas,
            paged_prefill_uses_pallas,
        )

        cfg = self.cfg
        pool = (*pool_block, cfg.n_kv_heads, cfg.head_dim)
        return (paged_attention_uses_pallas(
                    (capacity, cfg.n_heads, cfg.head_dim), pool, use_pallas),
                paged_prefill_uses_pallas(
                    (prefill_batch, prefill_chunk, cfg.n_heads,
                     cfg.head_dim), pool, use_pallas))

    @nn.compact
    def __call__(self, tokens, cache=None, pos=None, pad=None, paged=None):
        cfg = self.cfg
        if pad is not None:
            raise ValueError("WindowMoe has no left-padded (batched "
                             "prefill) cache path")
        if (cache is None) != (paged is None):
            raise ValueError("WindowMoe's cache path is the paged pool: "
                             "pass cache=<its four leaves> together with "
                             "paged=<view>")
        if paged is not None and paged.window_tables is None:
            raise ValueError("WindowMoe's window layers read the view's "
                             "window_tables / window_write_block")
        embed = self.param("tok_embed", _normal(), (cfg.vocab_size, cfg.dim))
        x = embed[tokens].astype(cfg.dtype)
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        if cache is None:
            cos, sin = cos[: tokens.shape[1]], sin[: tokens.shape[1]]

        # every layer's held experts in one stack, handed to the scans
        # whole beside the layer index (see `HeldExperts`)
        f, shape = cfg.moe_hidden_dim, (cfg.n_layers, cfg.held)
        stacks = (self.param("experts_gate_up", _normal(),
                             (*shape, cfg.dim, 2 * f)),
                  self.param("experts_down", _normal(),
                             (*shape, f, cfg.dim)))

        def body(period, carry, index, cos, sin, pos, paged, stacks):
            return period(carry, index, cos, sin, pos, paged, stacks)

        (x, new_cache), counts = nn.scan(
            body, variable_axes={"params": 0}, split_rngs={"params": True},
            in_axes=(0,) + (nn.broadcast,) * 5, length=cfg.n_periods,
            metadata_params={nn.PARTITION_NAME: "periods"},
        )(WindowMoePeriod(cfg, name="periods"), (x, cache),
          jnp.arange(cfg.n_periods), cos, sin, pos, paged, stacks)

        x = layer_norm(x, self.param("final_norm", nn.initializers.ones,
                                     (cfg.dim,)), cfg.norm_eps)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum(
                "bsd,vd->bsv", x.astype(cfg.dtype), embed.astype(cfg.dtype),
                preferred_element_type=jnp.float32) * cfg.logit_scale
        if cache is None:
            return logits
        counts = counts.reshape(-1, 2)
        return logits, new_cache, jnp.stack(
            [jnp.sum(counts[:, 0]), jnp.max(counts[:, 1])])
