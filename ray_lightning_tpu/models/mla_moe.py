"""A decoder with latent attention (MLA) and routed experts, for serving.

The architecture of the DeepSeek-V3 family as its published
configurations give it (dots.vlm1's language model among them): pre-norm
residual blocks, multi-head latent attention with YaRN RoPE, the first
``n_dense_layers`` blocks with a dense SwiGLU, the rest with a layer of
routed experts (sigmoid scores, `noaux_tc` choice by groups, normalised
weights times a scaling factor) beside shared experts, an untied head.

Three calling conventions, one set of parameters:

  * ``model(tokens)`` -> logits: the full forward pass (expanded
    attention: K and V of every head from the latent), the tests' anchor
    and `generate_greedy`;
  * ``model(tokens, cache=(pool,), pos=.., paged=PagedPrefillView)``:
    a chunk of one slot's prompt through the latent paged pool;
  * ``model(tokens, cache=(pool,), pos=.., paged=PagedDecodeView)``:
    one token a slot.

The paged calls return ``(logits, (pool,), counts)``: the pool is ONE
leaf ``[L, n_blocks, P, row]`` whose rows are ``[c_kv (normalised) | k_r
(rotated) | 0..]``, carried whole through the layer scans and written at
``[layer, block, offset]`` (the stacked-pool rule of `models/llama.py`);
attention over it is the absorbed form (`ops.attention.mla_decode` /
`mla_prefill`): ``q_nope W_uk^T`` against the latent, the value the
latent itself, ``W_uv`` after. ``counts`` is int32 ``[2]``: the rows
routed to held experts summed over the expert layers, and the fullest
single expert of any layer (`MlaMoe.tick_counters`).

**Held experts.** The expert layer is told ``(experts_first,
experts_held)``: it routes over all ``n_routed_experts`` and computes the
part of the result its own experts give, for the rows routed to them,
plus the shared experts (`HeldExperts`). What absent experts would add is
left out; no code stands in for other chips. Rows are sorted by expert and multiplied
by `ops.grouped_matmul` over a static bound on rows that no routing can
exceed (``tokens x min(experts a token, experts held)``), so no row is
ever dropped and the step compiles once.

RoPE pairs dimension ``i`` with ``i + d/2`` (rotate-half, `ops/rope.py`);
the published code pairs ``2i`` with ``2i + 1``, which is this model
under a fixed permutation of the rope columns of ``wq_b`` and ``wkv_a``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.held_experts import (  # noqa: F401
    HeldExperts,
    _mm,
    _normal,
    generate_greedy,
    held_dispatch,
    held_rows_bound,
    route,
)
from ray_lightning_tpu.ops.norms import rms_norm
from ray_lightning_tpu.ops.rope import apply_rope


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 129280
    dim: int = 7168
    n_layers: int = 61
    #: leading layers with a dense FFN (`first_k_dense_replace`)
    n_dense_layers: int = 3
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_hidden_dim: int = 18432
    moe_hidden_dim: int = 2048
    #: the router's width: every expert of the layer, held here or not
    n_routed_experts: int = 256
    n_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    n_shared_experts: int = 1
    #: the experts this chip holds: [first, first + held); None = all
    experts_first: int = 0
    experts_held: Optional[int] = None
    max_seq_len: int = 8192
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dtype: Any = jnp.float32
    #: the serving engine's ambient kernel policy (False = never pallas)
    use_flash: bool = True

    #: how `held_experts.route` chooses (no field: the family has one way)
    expert_choice = "noaux_tc"

    def __post_init__(self):
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers must lie within n_layers")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
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
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def latent_dim(self) -> int:
        """Values cached a token a layer: latent plus rope columns."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_row_dim(self) -> int:
        """Width of a pool row: `latent_dim` rounded up to the TPU's 128
        lanes. A 576-wide minor dimension is padded to 640 in HBM by the
        tiled layout anyway, and left at 576 XLA gives the pool a layout
        (another dimension minor) that a Mosaic kernel cannot read
        without a copy of the whole pool; the spare columns stay zero."""
        return -(-self.latent_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def pool_leaf_shapes(self, n_blocks: int, block_size: int):
        """The paged pool's leaves for this model: one latent leaf."""
        return ((self.n_layers, n_blocks, block_size, self.pool_row_dim),)

    @classmethod
    def tiny(cls, **kw) -> "MlaMoeConfig":
        """CPU-test size whose shapes still pass the kernels' gates."""
        base = dict(vocab_size=96, dim=64, n_layers=3, n_dense_layers=1,
                    n_heads=8, q_lora_rank=48, kv_lora_rank=128,
                    qk_nope_head_dim=32, qk_rope_head_dim=64, v_head_dim=32,
                    dense_hidden_dim=128, moe_hidden_dim=32,
                    n_routed_experts=16, n_experts_per_tok=4, n_group=4,
                    topk_group=2, experts_first=0, experts_held=8,
                    max_seq_len=256, rope_original_max=32)
        base.update(kw)
        return cls(**base)


# ---- YaRN RoPE ---------------------------------------------------------------


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: MlaMoeConfig):
    """The rope frequencies [d/2], static (no dependence on the length)."""
    d = cfg.qk_rope_head_dim
    base = cfg.rope_theta

    def correction(rotations: float) -> float:
        return (d * math.log(cfg.rope_original_max
                             / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return freq / cfg.rope_factor * (1.0 - keep) + freq * keep


def yarn_tables(cfg: MlaMoeConfig):
    """(cos, sin) [max_seq_len, d/2], scaled by mscale / mscale_all_dim."""
    t = jnp.arange(cfg.max_seq_len, dtype=jnp.float32)
    ang = jnp.outer(t, yarn_inv_freq(cfg))
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


# ---- blocks ------------------------------------------------------------------
#
# The expert layer (`route`, `held_dispatch`, `held_rows_bound`,
# `HeldExperts`) lives in `models/held_experts.py`, shared with
# `models/window_moe.py`; the names stay importable from here.


class MlaMoeBlock(nn.Module):
    cfg: MlaMoeConfig
    moe: bool = False

    def _swiglu(self, h, gate_up, down):
        dt = self.cfg.dtype
        gate, up = jnp.split(_mm(h, gate_up, dt), 2, axis=-1)
        return _mm(nn.silu(gate) * up, down, dt)

    def _attention(self, x, cos, sin, cache, pos, paged, layer, use_pallas):
        cfg = self.cfg
        dt = cfg.dtype
        d, h = cfg.dim, cfg.n_heads
        r, n, rope, v = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        p = self.param
        wq_a = p("wq_a", _normal(), (d, cfg.q_lora_rank))
        q_norm = p("q_norm", nn.initializers.ones, (cfg.q_lora_rank,))
        wq_b = p("wq_b", _normal(), (cfg.q_lora_rank, h, n + rope))
        wkv_a = p("wkv_a", _normal(), (d, r + rope))
        kv_norm = p("kv_norm", nn.initializers.ones, (r,))
        w_uk = p("w_uk", _normal(), (r, h, n))
        w_uv = p("w_uv", _normal(), (r, h, v))
        wo = p("wo", _normal(), (h * v, d))

        b, s = x.shape[:2]
        if cache is None:
            positions = None
        elif pos.ndim == 0:            # a chunk: token j at pos + j
            positions = jnp.broadcast_to(pos + jnp.arange(s)[None, :], (b, s))
        else:                          # decode: one token a slot
            positions = pos[:, None]
        c_q = rms_norm(_mm(x, wq_a, dt), q_norm, cfg.norm_eps)
        q = jnp.einsum("bsq,qhe->bshe", c_q, wq_b.astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        q_nope, q_rope = q[..., :n], q[..., n:]
        q_rope = apply_rope(q_rope, cos, sin, positions=positions)
        kv = _mm(x, wkv_a, dt)
        c_kv = rms_norm(kv[..., :r], kv_norm, cfg.norm_eps)
        k_r = apply_rope(kv[..., None, r:], cos, sin,
                         positions=positions)[..., 0, :]
        scale = cfg.softmax_scale
        if cache is None:
            # expanded: every head's K and V from the latent
            k_nope = jnp.einsum("bsl,lhn->bshn", c_kv, w_uk.astype(dt),
                                preferred_element_type=jnp.float32)
            val = jnp.einsum("bsl,lhv->bshv", c_kv, w_uv.astype(dt),
                             preferred_element_type=jnp.float32).astype(dt)
            score = (jnp.einsum("bqhn,bkhn->bhqk", q_nope,
                                k_nope.astype(dt),
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_r,
                                  preferred_element_type=jnp.float32)
                     ) * scale
            causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
            prob = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), -1)
            out = jnp.einsum("bhqk,bkhv->bqhv", prob.astype(dt), val,
                             preferred_element_type=jnp.float32).astype(dt)
            new_cache = None
        else:
            from ray_lightning_tpu.ops.attention import (
                PagedPrefillView, mla_decode, mla_prefill,
            )

            (pool,) = cache
            width = pool.shape[-1]
            row = jnp.concatenate(
                [c_kv, k_r, jnp.zeros((b, s, width - r - rope), dt)], -1)
            q_abs = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk.astype(dt),
                               preferred_element_type=jnp.float32).astype(dt)
            q_row = jnp.concatenate(
                [q_abs, q_rope, jnp.zeros((b, s, h, width - r - rope), dt)],
                -1)
            # write-then-attend, the paged lanes' ordering: a token's own
            # row is visible to its query
            if isinstance(paged, PagedPrefillView):
                with jax.named_scope("kv_pool"):
                    pool = pool.at[layer, paged.write_block,
                                   paged.write_offset].set(
                                       row.astype(pool.dtype))
                lat = mla_prefill(q_row, pool, paged.tables, pos, r, scale,
                                  use_pallas=use_pallas, layer=layer)
            else:
                assert s == 1, "the paged decode path takes one token a slot"
                with jax.named_scope("kv_pool"):
                    pool = pool.at[layer, paged.write_block,
                                   paged.write_offset].set(
                                       row[:, 0].astype(pool.dtype))
                lat = mla_decode(q_row[:, 0], pool, paged.tables,
                                 paged.lengths, r, scale,
                                 use_pallas=use_pallas, layer=layer)[:, None]
            out = jnp.einsum("bshl,lhv->bshv", lat, w_uv.astype(dt),
                             preferred_element_type=jnp.float32).astype(dt)
            new_cache = (pool,)
        return _mm(out.reshape(b, s, h * v), wo, dt), new_cache

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, pos=None, paged=None,
                 layer=None, stacks=None):
        cfg = self.cfg
        d = cfg.dim
        # the view's STATIC use_pallas (the serve engine's build-time
        # decision) pins the kernels; absent that, the ambient policy
        use_pallas = None if paged is None else paged.use_pallas
        if use_pallas is None and not cfg.use_flash:
            use_pallas = False
        with jax.named_scope("attn"):
            attn_norm = self.param("attn_norm", nn.initializers.ones, (d,))
            a, new_cache = self._attention(
                rms_norm(x, attn_norm, cfg.norm_eps), cos, sin, cache, pos,
                paged, layer, use_pallas)
            x = x + a
        mlp_norm = self.param("mlp_norm", nn.initializers.ones, (d,))
        h = rms_norm(x, mlp_norm, cfg.norm_eps)
        counts = jnp.zeros((2,), jnp.int32)
        if not self.moe:
            with jax.named_scope("mlp"):
                f = cfg.dense_hidden_dim
                y = self._swiglu(
                    h, self.param("w_gate_up", _normal(), (d, 2 * f)),
                    self.param("w_down", _normal(), (f, d)))
        else:
            b, s = h.shape[:2]
            y, counts = HeldExperts(cfg, name="experts")(
                h.reshape(b * s, d), stacks, layer - cfg.n_dense_layers,
                use_pallas)
            y = y.reshape(b, s, d)
            if cfg.n_shared_experts:
                with jax.named_scope("mlp"):
                    f = cfg.moe_hidden_dim * cfg.n_shared_experts
                    y = y + self._swiglu(
                        h, self.param("shared_gate_up", _normal(),
                                      (d, 2 * f)),
                        self.param("shared_down", _normal(), (f, d)))
        return x + y.astype(x.dtype), new_cache, counts


class MlaMoe(nn.Module):
    """Token ids [B, S] -> logits [B, S, V] (see the module's text)."""

    cfg: MlaMoeConfig

    #: device-side counts a paged call returns beside the pool, and how
    #: the engine joins those of a tick's two lanes
    tick_counters = (("expert_rows", "sum"), ("expert_rows_max", "max"))
    #: what the serving engine has to refuse for this decoder
    serving_unsupported = ("reference_lanes", "speculative",
                           "prefill_batch", "tensor_parallel")
    #: no sliding-window layers: one group of the pool
    kv_window = None
    #: no recurrent layers: no leaf of the pool holds a row a slot
    slot_state = False

    def serving_param_specs(self):
        """No published placement: a replica holds its share whole."""
        return {}

    def decode_tile_tokens(self, block_size: int, blocks_per_slot: int):
        """None: `rlt_mla_decode` states no tile to the engine (its
        index map reads table entry ``(length - 1) // P``, so no slot
        may be handed a length of 0)."""
        return None

    def prefill_tile_shape(self, prefill_batch: int, prefill_chunk: int,
                           block_size: int, blocks_per_slot: int):
        """None: `rlt_mla_prefill` states no tile to the engine (its grid
        is its table's; dead tiles are clamped, not left out)."""
        return None

    def paged_lanes(self, capacity: int, prefill_batch: int,
                    prefill_chunk: int, pool_block, use_pallas):
        """(decode, prefill): would the paged lanes take the kernels at
        these shapes? ``pool_block`` = (n_blocks, block_size)."""
        from ray_lightning_tpu.ops.attention import mla_uses_pallas

        cfg = self.cfg
        pool = (*pool_block, cfg.pool_row_dim)
        return (mla_uses_pallas((capacity, cfg.n_heads, cfg.pool_row_dim),
                                pool, cfg.kv_lora_rank, use_pallas),
                mla_uses_pallas((prefill_batch, prefill_chunk, cfg.n_heads,
                                 cfg.pool_row_dim),
                                pool, cfg.kv_lora_rank, use_pallas))

    @nn.compact
    def __call__(self, tokens, cache=None, pos=None, pad=None, paged=None):
        cfg = self.cfg
        if pad is not None:
            raise ValueError("MlaMoe has no left-padded (batched prefill) "
                             "cache path")
        if (cache is None) != (paged is None):
            raise ValueError("MlaMoe's cache path is the paged pool: pass "
                             "cache=(pool,) together with paged=<view>")
        embed = self.param("tok_embed", _normal(), (cfg.vocab_size, cfg.dim))
        x = embed[tokens].astype(cfg.dtype)
        cos, sin = yarn_tables(cfg)
        if cache is None:
            cos, sin = cos[: tokens.shape[1]], sin[: tokens.shape[1]]

        scan = partial(nn.scan, variable_axes={"params": 0},
                       split_rngs={"params": True},
                       metadata_params={nn.PARTITION_NAME: "layers"})

        def body(blk, carry, layer, cos, sin, pos, paged, stacks):
            h, cache = carry
            h, cache, counts = blk(h, cos, sin, cache, pos, paged, layer,
                                   stacks)
            return (h, cache), counts

        stacks = None
        if cfg.n_moe_layers:
            # every expert layer's held experts in one stack, handed to the
            # scan whole beside the layer index (see `HeldExperts`)
            f, shape = cfg.moe_hidden_dim, (cfg.n_moe_layers, cfg.held)
            stacks = (self.param("experts_gate_up", _normal(),
                                 (*shape, cfg.dim, 2 * f)),
                      self.param("experts_down", _normal(),
                                 (*shape, f, cfg.dim)))

        counts = jnp.zeros((1, 2), jnp.int32)
        carry = (x, cache)
        first = 0
        for name, n, moe in (("dense_layers", cfg.n_dense_layers, False),
                             ("moe_layers", cfg.n_moe_layers, True)):
            if not n:
                continue
            carry, got = scan(
                body, in_axes=(0,) + (nn.broadcast,) * 5, length=n,
            )(MlaMoeBlock(cfg, moe, name=name), carry,
              first + jnp.arange(n), cos, sin, pos, paged,
              stacks if moe else None)
            if moe:
                counts = got
            first += n
        x, new_cache = carry

        final_norm = self.param("final_norm", nn.initializers.ones,
                                (cfg.dim,))
        x = rms_norm(x, final_norm, cfg.norm_eps)
        head = self.param("lm_head", _normal(), (cfg.dim, cfg.vocab_size))
        with jax.named_scope("lm_head"):
            logits = jnp.dot(x.astype(cfg.dtype), head.astype(cfg.dtype),
                             preferred_element_type=jnp.float32)
        if cache is None:
            return logits
        return logits, new_cache, jnp.stack(
            [jnp.sum(counts[:, 0]), jnp.max(counts[:, 1])])
