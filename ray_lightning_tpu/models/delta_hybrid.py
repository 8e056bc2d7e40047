"""A decoder of gated-delta-rule layers with a full-attention layer a period,
for serving.

The architecture of AI2's Olmo Hybrid family as its published configuration
gives it (Olmo-Hybrid-7B is one): ``layer_types`` runs in periods of
``full_period`` layers, LINEAR-attention layers (a Gated DeltaNet mixer,
arXiv:2412.06464) with a FULL-attention layer last; every layer has a SwiGLU
MLP; the family's norm AFTER each sublayer, and an RMSNorm on the full
layers' queries and keys; untied embeddings; no positional encoding of any
kind. The layers are stacked by kind and scanned a period at a time: a
period is one module with its linear layers in a stack in front of its full
layer, and the model refuses a layer count that is no whole number of
periods.

    h  = x + RMSNorm(mixer(x))
    x' = h + RMSNorm(down(silu(gate(h)) * up(h)))

    linear mixer, on rows u [S, D] (H heads, d_k, d_v):
      [q, k, v] = u W_in,  z = u W_gate   (D -> 2 H d_k + H d_v;  H d_v)
      q, k, v = silu(conv1d_causal_depthwise([q, k, v], K))    (no bias)
      q = q / |q| * d_k^-0.5,  k = k / |k|                     (a head)
      beta  = 2 sigmoid(u W_b)        (2 x: `allow_neg_eigval`; a head)
      alpha = exp(-exp(A_log) softplus(u W_a + b_dt))          (a head)
      S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
      o_t = S_t^T q_t                                  S [d_k, d_v] a head
      out = (RMSNorm_dv(o) * silu(z)) W_out                (H d_v -> D)

    full mixer: q, k (D -> H x hd, RMSNorm over the whole width before the
    heads split), v, causal softmax attention at scale hd^-0.5 over as many
    KV heads as query heads, NO rotation, o (H x hd -> D), no biases.

Three calling conventions, one set of parameters (as `models/ssm_hybrid.py`):

  * ``model(tokens)`` -> logits: the full forward pass from a zero state,
    the tests' anchor and `generate_greedy`;
  * ``model(tokens, cache=pool, pos=.., paged=PagedPrefillView)``: a chunk
    of one slot's prompt;
  * ``model(tokens, cache=pool, pos=.., paged=PagedDecodeView)``: one token
    a slot.

The paged calls return ``(logits, pool, counts)``. **The pool has two
groups of two kinds** (`DeltaHybridConfig.pool_leaf_shapes`): the full
layers' K and V, paged by token through the view's ``tables`` as a dense
decoder's, ``[L_full, n_blocks, P, Hkv', hd]`` with the KV heads rounded UP
to a sublane tile (30 -> 32: the chip pads a second-minor 30 to 32 anyway
and Mosaic cannot slice 30 rows of the padded 32 out of HBM, so the two dead
heads are declared, hold zeros and are attended by two zero query heads:
PERF.md section 6, PR 41); and the linear layers' state, A ROW A SLOT
whatever the context: the MATRIX state two heads side by side ``[L_lin,
slots, H / 2, d_k, 2 d_v]`` float32 (the kernels' layout,
`ops/gated_delta.py`: whole tiles, so it goes in and out without a relayout)
and the convolution's tail ``[L_lin, slots, K - 1, channels / 128, 128]``
over the channels of q, k and v. All four leaves ride the scans as carry; a
tick reads and writes the rows it touches.

**A recurrence is not idempotent**: the real-rows-once rule of
`models/ssm_hybrid.py` holds here unchanged (``paged.real_rows``,
``paged.state_slot``, ``paged.state_moves``; a chunk whose first real row is
position 0 starts from zeros).

Scopes (`docs/OBSERVABILITY.md`): ``linattn`` a linear mixer whole,
``linattn_state`` inside it the reads and writes of the carried leaves,
``attn`` / ``kv_pool`` the full mixer's, ``mlp``, ``lm_head``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.held_experts import (  # noqa: F401
    _mm, _normal, generate_greedy,
)
from ray_lightning_tpu.models.ssm_hybrid import _mm32
from ray_lightning_tpu.ops.gated_delta import (
    gated_delta_rule, gated_delta_update, pair_shape,
)
from ray_lightning_tpu.ops.norms import rms_norm
from ray_lightning_tpu.ops.selective_scan import (
    causal_conv, causal_conv_update, lane_join, lane_split, state_shape,
)

#: what the chip's tiled layout rounds a pool leaf's head axis up to
_SUBLANES = 8
_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class DeltaHybridConfig:
    vocab_size: int = 100352
    dim: int = 3840
    n_layers: int = 16
    #: a period: full_period - 1 linear layers, then a full layer
    full_period: int = 4
    n_heads: int = 30
    n_kv_heads: int = 30
    head_dim: int = 128
    #: the MLP's width
    hidden_dim: int = 11008
    lin_heads: int = 30
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    d_conv: int = 4
    #: beta = 2 sigmoid(.) where True: the transition's eigenvalues reach -1
    allow_neg_eigval: bool = True
    max_seq_len: int = 8576
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    #: the serving engine's ambient kernel policy (False = never pallas)
    use_flash: bool = True

    def __post_init__(self):
        if self.n_layers % self.full_period:
            raise ValueError(
                f"n_layers {self.n_layers} must be whole periods of "
                f"{self.full_period} (the layers are stacked by kind and "
                "scanned a period at a time)")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        pair_shape(self.lin_heads, self.lin_key_dim, self.lin_value_dim)
        state_shape(self.d_conv - 1, self.conv_channels)  # channels split

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.full_period

    @property
    def n_lin_layers(self) -> int:
        return self.n_periods * (self.full_period - 1)

    @property
    def conv_channels(self) -> int:
        """q, k and v side by side."""
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    @property
    def kv_heads_held(self) -> int:
        """KV heads of the pool's leaf: rounded up to a sublane tile where
        the kernels run one query head a KV head (module text)."""
        if self.n_heads != self.n_kv_heads:
            return self.n_kv_heads
        return -(-self.n_kv_heads // _SUBLANES) * _SUBLANES

    def pool_leaf_shapes(self, n_blocks: int, block_size: int,
                         state_slots: int):
        """The pool's leaves: the full layers' K and V over the allocator's
        ``n_blocks`` (a row a token), then the linear layers' matrix state
        (float32) and convolution tail (a row a slot, ``state_slots`` of
        them)."""
        kv = (self.n_periods, n_blocks, block_size, self.kv_heads_held,
              self.head_dim)
        rows = (self.n_lin_layers, state_slots)
        return (kv, kv,
                jax.ShapeDtypeStruct(
                    (*rows, *pair_shape(self.lin_heads, self.lin_key_dim,
                                        self.lin_value_dim)), jnp.float32),
                (*rows, *state_shape(self.d_conv - 1, self.conv_channels)))

    @classmethod
    def tiny(cls, **kw) -> "DeltaHybridConfig":
        """CPU-test size whose shapes still pass the kernels' gates."""
        base = dict(vocab_size=96, dim=64, n_layers=4, full_period=4,
                    n_heads=2, n_kv_heads=2, head_dim=128, hidden_dim=96,
                    lin_heads=2, lin_key_dim=16, lin_value_dim=32, d_conv=4,
                    max_seq_len=256)
        base.update(kw)
        return cls(**base)


# ---- the leaves that decide the recurrence, as a trained model's are --------
# With leaves of one small std alone the state forgets nothing and beta is 1
# everywhere; these starts are the published Gated DeltaNet layer's.


def _a_log_init(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0))


def _dt_bias_init(key, shape, lo=1e-3, hi=1e-1):
    step = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                      math.log(lo), math.log(hi)))
    return step + jnp.log(-jnp.expm1(-step))             # softplus^-1


def _l2(x):
    """A head's row over its norm, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


class DeltaHybridBlock(nn.Module):
    """One layer: ``full`` says which kind. ``group_layer`` is its index
    among the layers of its kind (its row of that kind's leaves)."""

    cfg: DeltaHybridConfig
    full: bool = False

    def _full(self, u, cache, pos, paged, group_layer):
        cfg = self.cfg
        dt = cfg.dtype
        d, nh, nkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        p = self.param
        ones = nn.initializers.ones
        wq = p("wq", _normal(), (d, nh * hd))
        wk = p("wk", _normal(), (d, nkv * hd))
        wv = p("wv", _normal(), (d, nkv * hd))
        wo = p("wo", _normal(), (nh * hd, d))
        norm = lambda name, x: rms_norm(
            x, p(name, ones, (x.shape[-1],)), cfg.norm_eps, use_pallas=False)
        b, s = u.shape[:2]
        # the family's norm on q and k, over the whole width
        q = norm("q_norm", _mm(u, wq, dt)).reshape(b, s, nh, hd)
        k = norm("k_norm", _mm(u, wk, dt)).reshape(b, s, nkv, hd)
        v = _mm(u, wv, dt).reshape(b, s, nkv, hd)
        if cache is None:
            from ray_lightning_tpu.ops.attention import dot_product_attention

            out = dot_product_attention(q, k, v, causal=True)
            return _mm(out.reshape(b, s, nh * hd), wo, dt), None
        from ray_lightning_tpu.ops.attention import PagedPrefillView
        from ray_lightning_tpu.ops.pallas.paged_attention import (
            paged_attention_pallas, stack_as_pool,
        )
        from ray_lightning_tpu.ops.pallas.paged_prefill import (
            paged_prefill_pallas,
        )

        pk, pv = cache[:2]
        prefill = isinstance(paged, PagedPrefillView)
        assert prefill or s == 1, "the decode path takes one token a slot"
        rows = (lambda x: x) if prefill else (lambda x: x[:, 0])
        # the dead heads of the pool's leaf: zeros in, zeros attended
        dead = cfg.kv_heads_held - nkv
        held = lambda x: jnp.pad(x, ((0, 0),) * (x.ndim - 2)
                                 + ((0, dead), (0, 0)))
        # write-then-attend, the paged lanes' ordering
        with jax.named_scope("kv_pool"):
            at = (group_layer, paged.write_block, paged.write_offset)
            pk = pk.at[at].set(held(rows(k)).astype(pk.dtype))
            pv = pv.at[at].set(held(rows(v)).astype(pv.dtype))
        fk, fv, tables = stack_as_pool(pk, pv, paged.tables, group_layer)
        if prefill:
            out = paged_prefill_pallas(held(q), fk, fv, tables, pos)
        else:
            out = paged_attention_pallas(held(q[:, 0]), fk, fv, tables,
                                         paged.lengths)[:, None]
        out = out[..., :nh, :]
        return (_mm(out.reshape(b, s, nh * hd), wo, dt),
                (pk, pv, *cache[2:]))

    def _linear(self, u, cache, pos, paged, group_layer, use_pallas):
        cfg = self.cfg
        dt = cfg.dtype
        d, h, dk, dv, kc = (cfg.dim, cfg.lin_heads, cfg.lin_key_dim,
                            cfg.lin_value_dim, cfg.d_conv)
        ch = cfg.conv_channels
        p = self.param
        w_in = p("in_proj", _normal(), (d, ch))
        # the gate's projection apart from q, k and v: fused with them, the
        # product is read once in front of the delta rule and once behind
        # it, and the v5e compiler computes it twice (PERF.md section 6, PR
        # 41: 1.45 ms a layer a 2,048-row chunk)
        w_gate = p("gate_proj", _normal(), (d, h * dv))
        conv_w = p("conv_weight", _normal(0.2), (kc, ch))
        # [W_b, W_a]: beta's half wide enough to reach both sides of 1
        w_ba = p("ba_proj", _normal(0.1), (d, 2 * h))
        a = jnp.exp(p("a_log", _a_log_init, (h,)).astype(jnp.float32))
        dt_bias = p("dt_bias", _dt_bias_init, (h,)).astype(jnp.float32)
        g_out = p("out_norm", nn.initializers.ones, (dv,))
        w_out = p("out_proj", _normal(), (h * dv, d))
        no_bias = jnp.zeros((ch,), jnp.float32)

        b, s = u.shape[:2]
        x = _mm(u, w_in, dt)
        ba = _mm32(u, w_ba, dt)                           # [b, s, 2 H]
        beta = jax.nn.sigmoid(ba[..., :h])
        if cfg.allow_neg_eigval:
            beta = 2.0 * beta
        log_alpha = -a * jax.nn.softplus(ba[..., h:] + dt_bias)

        def heads(xc):
            # [.., channels] float32 -> q, k (normalised), v, by head
            xc = nn.silu(xc)
            q, k, v = jnp.split(xc, [h * dk, 2 * h * dk], axis=-1)
            by = lambda y, w: y.reshape(*y.shape[:-1], h, w)
            return ((_l2(by(q, dk)) * dk ** -0.5).astype(dt),
                    _l2(by(k, dk)).astype(dt), by(v, dv).astype(dt))

        def finish(o):
            # the per-head norm, the gate, the way out
            o = rms_norm(o, g_out, cfg.norm_eps, use_pallas=False)
            z = _mm(u, w_gate, dt).astype(jnp.float32).reshape(o.shape)
            return _mm((o * nn.silu(z)).reshape(*o.shape[:-2], h * dv),
                       w_out, dt)

        if cache is None:
            # the whole sequence from a zero state: every row is real
            tail = jnp.zeros((kc - 1, ch), dt)
            xc = jax.vmap(lambda xs: causal_conv(
                xs, tail, conv_w, no_bias, 0, s - 1)[0])(x)
            o, _ = gated_delta_rule(
                *heads(xc), log_alpha, beta,
                jnp.zeros((b, *pair_shape(h, dk, dv)), jnp.float32),
                jnp.ones((b, s), bool), use_pallas=use_pallas)
            return finish(o), None
        from ray_lightning_tpu.ops.attention import PagedPrefillView

        states, tails = cache[2:]
        if isinstance(paged, PagedPrefillView):
            # one slot's chunk: its real rows, once
            slot = paged.state_slot
            first, last = paged.real_rows[0], paged.real_rows[1]
            with jax.named_scope("linattn_state"):
                # a chunk whose first real row is position 0 starts from
                # zeros: whatever the slot held is another request's
                keep = pos + first > 0
                s0 = jnp.where(keep, states[group_layer, slot], 0.0)
                tail = lane_join(jnp.where(
                    keep, tails[group_layer, slot], 0.0).astype(tails.dtype))
            xc, tail = causal_conv(x[0], tail, conv_w, no_bias, first, last)
            idx = jnp.arange(s)
            real = (idx >= first) & (idx <= last)
            o, s1 = gated_delta_rule(
                *(y[None] for y in heads(xc)), log_alpha, beta, s0[None],
                real[None], use_pallas=use_pallas)
            with jax.named_scope("linattn_state"):
                states = states.at[group_layer, slot].set(s1[0])
                tails = tails.at[group_layer, slot].set(lane_split(tail))
        else:
            assert s == 1, "the decode path takes one token a slot"
            moves = paged.state_moves
            with jax.named_scope("linattn_state"):
                s0 = states[group_layer]          # [C, H / 2, d_k, 2 d_v]
                tail = tails[group_layer]         # [C, K - 1, ch / 128, 128]
            # a barrier between the product and the convolution: without it
            # the split of the row into the tail's lanes reaches back
            # through the product, and the v5e compiler copies the layer's
            # slice of `in_proj` to suit it (88 MB a layer a tick: PERF.md
            # section 6, PR 41); the row is a third of a megabyte
            xc, moved = causal_conv_update(
                jax.lax.optimization_barrier(x[:, 0]), tail, conv_w, no_bias)
            o, s1 = gated_delta_update(
                *heads(xc), log_alpha[:, 0], beta[:, 0], s0, moves,
                use_pallas=use_pallas)
            o = o[:, None]
            with jax.named_scope("linattn_state"):
                states = states.at[group_layer].set(s1)
                tails = tails.at[group_layer].set(jnp.where(
                    moves[:, None, None, None], moved, tail))
        return finish(o), (*cache[:2], states, tails)

    @nn.compact
    def __call__(self, x, cache=None, pos=None, paged=None,
                 group_layer=None):
        cfg = self.cfg
        d, f, dt = cfg.dim, cfg.hidden_dim, cfg.dtype
        # the view's STATIC use_pallas (the serve engine's build-time
        # decision) pins the kernels; absent that, the ambient policy
        use_pallas = None if paged is None else paged.use_pallas
        if use_pallas is None and not cfg.use_flash:
            use_pallas = False
        norm = lambda name, v: rms_norm(
            v, self.param(name, nn.initializers.ones, (d,)), cfg.norm_eps,
            use_pallas=False)
        if self.full:
            with jax.named_scope("attn"):
                mixed, new_cache = self._full(x, cache, pos, paged,
                                              group_layer)
        else:
            with jax.named_scope("linattn"):
                mixed, new_cache = self._linear(
                    x, cache, pos, paged, group_layer, use_pallas)
        h = x + norm("post_mixer_norm", mixed).astype(x.dtype)
        with jax.named_scope("mlp"):
            gate, up = jnp.split(_mm(
                h, self.param("gate_up", _normal(), (d, 2 * f)), dt), 2,
                axis=-1)
            y = _mm(nn.silu(gate) * up,
                    self.param("down", _normal(), (f, d)), dt)
        return h + norm("post_mlp_norm", y).astype(x.dtype), new_cache


class DeltaHybridPeriod(nn.Module):
    """``full_period - 1`` linear layers under one scan, then the full
    layer. ``index`` (static) is the period's place in the model."""

    cfg: DeltaHybridConfig

    @nn.compact
    def __call__(self, carry, index: int, pos, paged):
        cfg = self.cfg
        n = cfg.full_period - 1

        def body(blk, carry, layer, pos, paged):
            x, cache = carry
            return blk(x, cache, pos, paged, layer), None

        if n:
            carry, _ = nn.scan(
                body, variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(0, nn.broadcast, nn.broadcast), length=n,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(DeltaHybridBlock(cfg, False, name="linear"), carry,
              index * n + jnp.arange(n), pos, paged)
        return DeltaHybridBlock(cfg, True, name="full_layer")(
            *carry, pos, paged, index)


class DeltaHybrid(nn.Module):
    """Token ids [B, S] -> logits [B, S, V] (see the module's text)."""

    cfg: DeltaHybridConfig

    #: device-side counts a paged call returns beside the pool: the rows
    #: the prefill lane's delta rule took as real, and the slots whose state
    #: the decode lane moved (the engine sums a tick's two lanes')
    tick_counters = (("delta_rows", "sum"), ("state_slots", "sum"))
    #: what the serving engine has to refuse for this decoder, each with
    #: its reason (`serve/engine.py:why_unsupported`)
    serving_unsupported = {
        "reference_lanes": "it serves through its paged kernels only",
        "speculative": (
            "a rejected draft token has already advanced the linear "
            "layers' matrix state, which keeps no earlier row to roll back "
            "to (27 MB a slot: no snapshot either)"),
        "prefill_batch": (
            "a left-padded group would run its pad columns through the "
            "recurrence"),
        "tensor_parallel": (
            "it publishes no parameter placement, and its delta-rule and "
            "paged kernels have no manual region"),
        "prefix_cache": (
            "a shared block carries K/V and no state: a request that "
            "skipped a cached prefix would start its linear layers from "
            "zeros"),
    }
    kv_window = None
    #: its linear layers keep a row a slot in the pool
    #: (`serve/kv_cache.py` "a row a slot")
    slot_state = True

    def serving_param_specs(self):
        """No published placement: a replica holds the model whole."""
        return {}

    def decode_tile_tokens(self, block_size: int, blocks_per_slot: int):
        from ray_lightning_tpu.ops.pallas.paged_attention import (
            decode_tile_tokens,
        )

        return decode_tile_tokens(block_size, blocks_per_slot)

    def _held_heads(self):
        """(query heads, KV heads) as the paged kernels see them: the
        pool's leaf with its dead heads, a query head each."""
        cfg = self.cfg
        held = cfg.kv_heads_held
        return cfg.n_heads + held - cfg.n_kv_heads, held

    def prefill_tile_shape(self, prefill_batch: int, prefill_chunk: int,
                           block_size: int, blocks_per_slot: int):
        from ray_lightning_tpu.ops.pallas.paged_prefill import (
            prefill_tile_shape,
        )

        nh, nkv = self._held_heads()
        return prefill_tile_shape(
            (prefill_batch, prefill_chunk, nh, self.cfg.head_dim),
            (block_size, nkv, self.cfg.head_dim), blocks_per_slot)

    def paged_lanes(self, capacity: int, prefill_batch: int,
                    prefill_chunk: int, pool_block, use_pallas):
        """(decode, prefill): would the paged lanes take the kernels at
        these shapes? ``pool_block`` = (n_blocks, block_size). The delta
        rule has an XLA twin and does not decide a lane."""
        from ray_lightning_tpu.ops.attention import (
            paged_attention_uses_pallas,
            paged_prefill_uses_pallas,
        )

        nh, nkv = self._held_heads()
        hd = self.cfg.head_dim
        pool = (*pool_block, nkv, hd)
        return (paged_attention_uses_pallas((capacity, nh, hd), pool,
                                            use_pallas),
                paged_prefill_uses_pallas(
                    (prefill_batch, prefill_chunk, nh, hd), pool,
                    use_pallas))

    @nn.compact
    def __call__(self, tokens, cache=None, pos=None, pad=None, paged=None):
        cfg = self.cfg
        if pad is not None:
            raise ValueError("DeltaHybrid has no left-padded (batched "
                             "prefill) cache path")
        if (cache is None) != (paged is None):
            raise ValueError("DeltaHybrid's cache path is the paged pool: "
                             "pass cache=<its four leaves> together with "
                             "paged=<view>")
        counts = None
        if paged is not None:
            from ray_lightning_tpu.ops.attention import PagedPrefillView

            if isinstance(paged, PagedPrefillView):
                if paged.real_rows is None or paged.state_slot is None:
                    raise ValueError(
                        "DeltaHybrid's prefill view names the chunk's real "
                        "rows and its slot (real_rows, state_slot)")
                first, last = paged.real_rows[0], paged.real_rows[1]
                counts = jnp.stack([jnp.maximum(last - first + 1, 0),
                                    jnp.int32(0)])
            else:
                if paged.state_moves is None:
                    raise ValueError("DeltaHybrid's decode view says whose "
                                     "state moves (state_moves)")
                counts = jnp.stack([jnp.int32(0), jnp.sum(
                    paged.state_moves.astype(jnp.int32))])
        embed = self.param("tok_embed", _normal(), (cfg.vocab_size, cfg.dim))
        x = embed[tokens].astype(cfg.dtype)

        # the periods one after another, each with its own stacks: a stack
        # that is a parameter of the program is read in place, a slice of
        # one made for an inner loop is copied (PERF.md section 6, PR 35)
        carry = (x, cache)
        for index in range(cfg.n_periods):
            carry = DeltaHybridPeriod(cfg, name=f"period_{index}")(
                carry, index, pos, paged)
        x, new_cache = carry

        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.dim,)), cfg.norm_eps,
                     use_pallas=False)
        with jax.named_scope("lm_head"):
            logits = jnp.dot(
                x.astype(cfg.dtype),
                self.param("lm_head", _normal(),
                           (cfg.dim, cfg.vocab_size)).astype(cfg.dtype),
                preferred_element_type=jnp.float32)
        if cache is None:
            return logits
        return logits, new_cache, counts
