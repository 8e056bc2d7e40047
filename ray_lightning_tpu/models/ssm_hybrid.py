"""A decoder of state-space layers with an attention layer a period, for
serving.

The architecture of AI21's Jamba family as its published configurations
give it (Jamba2-3B is one): layer ``i`` is an ATTENTION layer where ``i %
attn_period == attn_offset`` and a STATE-SPACE layer (a Mamba-1 mixer with
the family's three inner RMSNorms) everywhere else; every layer has a SwiGLU
MLP (the family's expert layers with one expert: no router); RMSNorm in
front of both halves; tied embeddings; no positional encoding of any kind.
The layers are stacked by kind and scanned a period at a time: a period is
one module with two stacks of state-space layers around its attention
layer, and the model refuses a layer count that is no whole number of
periods.

    h  = x + mixer(RMSNorm(x))
    x' = h + down(silu(gate(u)) * up(u)),  u = RMSNorm(h)

    state-space mixer, on rows u [S, D]:
      [x, z] = u W_in                                   (D -> 2E, no bias)
      x      = silu(conv1d_causal_depthwise(x, K) + b_conv)
      [dt, B, C] = x W_x                                (E -> R + 2N)
      dt, B, C = RMSNorm_R(dt), RMSNorm_N(B), RMSNorm_N(C)
      delta  = softplus(dt W_dt + b_dt)                 (R -> E)
      h_t    = exp(delta_t A) * h_{t-1} + (delta_t B_t) x_t,  A = -exp(A_log)
      y_t    = h_t C_t + D x_t
      out    = (y * silu(z)) W_out                      (E -> D, no bias)

    attention mixer: q (D -> H x hd), k, v (D -> Hkv x hd), causal softmax
    attention at scale hd^-0.5, NO rotation, o (H x hd -> D), no biases.

Three calling conventions, one set of parameters (as `models/window_moe.py`):

  * ``model(tokens)`` -> logits: the full forward pass from a zero state,
    the tests' anchor and `generate_greedy`;
  * ``model(tokens, cache=pool, pos=.., paged=PagedPrefillView)``: a chunk
    of one slot's prompt;
  * ``model(tokens, cache=pool, pos=.., paged=PagedDecodeView)``: one token
    a slot.

The paged calls return ``(logits, pool, counts)``. **The pool has two
groups of two kinds** (`SsmHybridConfig.pool_leaf_shapes`): the attention
layers' K and V, paged by token through the view's ``tables`` as a dense
decoder's (with ONE KV head the head axis is left out of the leaf,
``[L_attn, n_blocks, P, hd]``: `ops/pallas/paged_attention.py:
headless_stack_as_pool` says why); and the state-space layers' state, A ROW
A SLOT whatever the context: the scan's state ``[L_ssm, slots, N, E / 128,
128]`` float32 (the scan kernel's layout, so it goes in and out without a
relayout) and the convolution's tail ``[L_ssm, slots, K - 1, E / 128,
128]``, the last K - 1 inputs (the same split: a slot's row and a layer's
rows are both whole tiles behind untiled leading dimensions, so neither
lane asks the compiler for another layout of the leaf). All four leaves ride the scans as carry; a tick reads and
writes the rows it touches (every slot's in the decode lane, one slot's in
the prefill lane), never a layer's leaf.

**A recurrence is not idempotent.** The prefill lane's chunk has a fixed
width and may hold rows that must not advance the state: zero padding past
the prompt's end, and, where the scheduler slid the last chunk back to keep
it inside the slot, rows it sent before. The view names the chunk's real
rows (``real_rows``); the scan leaves the state as it was on every other
row, the convolution's tail is taken after the last real row and put in
front of the first, and a chunk whose first real row is position 0 starts
from zeros (so admission, slot reuse and a preempted request's replay need
no reset). A row sent before computes garbage here (the state has moved
on), so the engine also keeps its K/V out of the attention group. The
decode lane runs every slot; a slot whose ``state_moves`` is False keeps
its state and tail.

Scopes (`docs/OBSERVABILITY.md`): ``ssm`` a state-space mixer whole,
``ssm_state`` inside it the reads and writes of the carried leaves,
``attn`` / ``kv_pool`` the attention mixer's, ``mlp``, ``lm_head``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.held_experts import (  # noqa: F401
    _mm, _normal, generate_greedy,
)
from ray_lightning_tpu.ops.norms import rms_norm
from ray_lightning_tpu.ops.selective_scan import (
    causal_conv, causal_conv_update, lane_join, lane_split, selective_scan,
    selective_update, state_shape,
)


@dataclasses.dataclass(frozen=True)
class SsmHybridConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    #: layer i is an attention layer where i % attn_period == attn_offset
    attn_period: int = 14
    attn_offset: int = 7
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    #: the MLP's width
    hidden_dim: int = 8192
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    max_seq_len: int = 8704
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    #: the serving engine's ambient kernel policy (False = never pallas)
    use_flash: bool = True

    def __post_init__(self):
        if self.n_layers % self.attn_period:
            raise ValueError(
                f"n_layers {self.n_layers} must be whole periods of "
                f"{self.attn_period} (the layers are stacked by kind and "
                "scanned a period at a time)")
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError(
                f"attn_offset {self.attn_offset} lies outside a period of "
                f"{self.attn_period}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_kv_heads must divide n_heads")
        state_shape(self.d_state, self.d_inner)   # the channels split

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.attn_period

    @property
    def n_ssm_layers(self) -> int:
        return self.n_periods * (self.attn_period - 1)

    @property
    def kv_row(self):
        """One cached token's K (or V) in an attention layer's leaf: with one
        KV head the head axis is left out."""
        return ((self.head_dim,) if self.n_kv_heads == 1
                else (self.n_kv_heads, self.head_dim))

    def pool_leaf_shapes(self, n_blocks: int, block_size: int,
                         state_slots: int):
        """The pool's leaves: the attention layers' K and V over the
        allocator's ``n_blocks`` (a row a token), then the state-space
        layers' scan state (float32) and convolution tail (a row a slot,
        ``state_slots`` of them)."""
        kv = (self.n_periods, n_blocks, block_size, *self.kv_row)
        rows = (self.n_ssm_layers, state_slots)
        return (kv, kv,
                jax.ShapeDtypeStruct(
                    (*rows, *state_shape(self.d_state, self.d_inner)),
                    jnp.float32),
                (*rows, *state_shape(self.d_conv - 1, self.d_inner)))

    @classmethod
    def tiny(cls, **kw) -> "SsmHybridConfig":
        """CPU-test size whose shapes still pass the kernels' gates."""
        base = dict(vocab_size=96, dim=64, n_layers=4, attn_period=4,
                    attn_offset=1, n_heads=4, n_kv_heads=1, head_dim=128,
                    hidden_dim=96, d_state=4, d_conv=4, expand=2, dt_rank=8,
                    max_seq_len=256)
        base.update(kw)
        return cls(**base)


def _mm32(x, w, dtype):
    """Operands at the activation dtype, the float32 accumulator kept: what
    feeds the recurrence is not rounded a second time."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


class SsmHybridBlock(nn.Module):
    """One layer: ``attention`` says which kind. ``group_layer`` is its
    index among the layers of its kind (its row of that kind's leaves)."""

    cfg: SsmHybridConfig
    attention: bool = False

    def _attention(self, u, cache, pos, paged, group_layer):
        cfg = self.cfg
        dt = cfg.dtype
        d, nh, nkv, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        p = self.param
        wq = p("wq", _normal(), (d, nh * hd))
        wk = p("wk", _normal(), (d, nkv * hd))
        wv = p("wv", _normal(), (d, nkv * hd))
        wo = p("wo", _normal(), (nh * hd, d))
        b, s = u.shape[:2]
        q = _mm(u, wq, dt).reshape(b, s, nh, hd)
        k = _mm(u, wk, dt).reshape(b, s, nkv, hd)
        v = _mm(u, wv, dt).reshape(b, s, nkv, hd)
        if cache is None:
            from ray_lightning_tpu.ops.attention import dot_product_attention

            out = dot_product_attention(q, k, v, causal=True)
            return _mm(out.reshape(b, s, nh * hd), wo, dt), None
        from ray_lightning_tpu.ops.attention import PagedPrefillView
        from ray_lightning_tpu.ops.pallas.paged_attention import (
            headless_stack_as_pool, paged_attention_pallas, stack_as_pool,
        )
        from ray_lightning_tpu.ops.pallas.paged_prefill import (
            paged_prefill_pallas,
        )

        pk, pv = cache[:2]
        prefill = isinstance(paged, PagedPrefillView)
        assert prefill or s == 1, "the decode path takes one token a slot"
        rows = (lambda x: x) if prefill else (lambda x: x[:, 0])
        # write-then-attend, the paged lanes' ordering
        with jax.named_scope("kv_pool"):
            at = (group_layer, paged.write_block, paged.write_offset)
            row = (lambda x: x.reshape(*x.shape[:-2], *cfg.kv_row))
            pk = pk.at[at].set(row(rows(k)).astype(pk.dtype))
            pv = pv.at[at].set(row(rows(v)).astype(pv.dtype))
        as_pool = headless_stack_as_pool if nkv == 1 else stack_as_pool
        fk, fv, tables = as_pool(pk, pv, paged.tables, group_layer)
        if prefill:
            out = paged_prefill_pallas(q, fk, fv, tables, pos)
        else:
            out = paged_attention_pallas(q[:, 0], fk, fv, tables,
                                         paged.lengths)[:, None]
        return (_mm(out.reshape(b, s, nh * hd), wo, dt),
                (pk, pv, *cache[2:]))

    def _state_space(self, u, cache, pos, paged, group_layer, use_pallas):
        cfg = self.cfg
        dt = cfg.dtype
        d, e, n, r, kc = (cfg.dim, cfg.d_inner, cfg.d_state, cfg.dt_rank,
                          cfg.d_conv)
        p = self.param
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        w_in = p("in_proj", _normal(), (d, 2 * e))
        conv_w = p("conv_weight", _normal(0.2), (kc, e))
        conv_b = p("conv_bias", zeros, (e,))
        w_x = p("x_proj", _normal(), (e, r + 2 * n))
        g_dt, g_b, g_c = (p("dt_norm", ones, (r,)), p("b_norm", ones, (n,)),
                          p("c_norm", ones, (n,)))
        w_dt = p("dt_proj", _normal(), (r, e))
        # what decides the recurrence stays float32, state-major [N, E]
        a = -jnp.exp(p("a_log", lambda *_: jnp.log(jnp.broadcast_to(
            jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, e))),
            (n, e)).astype(jnp.float32))
        skip = p("d", ones, (e,)).astype(jnp.float32)
        dt_bias = p("dt_bias", lambda *_: jnp.full((e,), -4.6, jnp.float32),
                    (e,)).astype(jnp.float32)
        w_out = p("out_proj", _normal(), (e, d))

        b, s = u.shape[:2]
        x, z = jnp.split(_mm(u, w_in, dt), 2, axis=-1)       # [b, s, E]

        def scan_inputs(xc):
            # [dt, B, C] and the family's inner norms, in float32
            proj = _mm32(xc, w_x, dt)
            dtr, bm, cm = jnp.split(proj, [r, r + n], axis=-1)
            norm = lambda v, g: rms_norm(v, g, cfg.norm_eps,
                                         use_pallas=False)
            return (_mm32(norm(dtr, g_dt), w_dt, dt), norm(bm, g_b),
                    norm(cm, g_c))

        if cache is None:
            # the whole sequence from a zero state: every row is real
            tail = jnp.zeros((kc - 1, e), dt)
            xc = jax.vmap(lambda xs: causal_conv(
                xs, tail, conv_w, conv_b, 0, s - 1)[0])(x)
            xc = nn.silu(xc)
            dtv, bm, cm = scan_inputs(xc)
            y, _ = selective_scan(
                xc, dtv, z, bm, cm, a, skip, dt_bias,
                jnp.zeros((b, *state_shape(n, e)), jnp.float32),
                jnp.ones((b, s), bool), use_pallas=use_pallas)
            return _mm(y, w_out, dt), None
        from ray_lightning_tpu.ops.attention import PagedPrefillView

        states, tails = cache[2:]
        if isinstance(paged, PagedPrefillView):
            # one slot's chunk: its real rows, once
            slot = paged.state_slot
            first, last = paged.real_rows[0], paged.real_rows[1]
            with jax.named_scope("ssm_state"):
                # a chunk whose first real row is position 0 starts from
                # zeros: whatever the slot held is another request's
                keep = pos + first > 0
                h0 = jnp.where(keep, states[group_layer, slot], 0.0)
                tail = lane_join(jnp.where(
                    keep, tails[group_layer, slot], 0.0).astype(tails.dtype))
            xc, tail = causal_conv(x[0], tail, conv_w, conv_b, first, last)
            xc = nn.silu(xc)
            dtv, bm, cm = scan_inputs(xc)
            idx = jnp.arange(s)
            real = (idx >= first) & (idx <= last)
            y, h = selective_scan(
                xc[None], dtv[None], z, bm[None], cm[None], a, skip, dt_bias,
                h0[None], real[None], use_pallas=use_pallas)
            with jax.named_scope("ssm_state"):
                states = states.at[group_layer, slot].set(h[0])
                tails = tails.at[group_layer, slot].set(lane_split(tail))
        else:
            assert s == 1, "the decode path takes one token a slot"
            moves = paged.state_moves
            with jax.named_scope("ssm_state"):
                h0 = states[group_layer]                 # [C, N, Es, 128]
                tail = tails[group_layer]         # [C, K - 1, Es, 128]
            xc, moved = causal_conv_update(x[:, 0], tail, conv_w, conv_b)
            xc = nn.silu(xc)
            dtv, bm, cm = scan_inputs(xc)
            y, h = selective_update(xc, dtv, z[:, 0], bm, cm, a, skip,
                                    dt_bias, h0, moves)
            y = y[:, None]
            with jax.named_scope("ssm_state"):
                states = states.at[group_layer].set(h)
                tails = tails.at[group_layer].set(jnp.where(
                    moves[:, None, None, None], moved, tail))
        return _mm(y, w_out, dt), (*cache[:2], states, tails)

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
        u = norm("input_norm", x)
        if self.attention:
            with jax.named_scope("attn"):
                mixed, new_cache = self._attention(u, cache, pos, paged,
                                                   group_layer)
        else:
            with jax.named_scope("ssm"):
                mixed, new_cache = self._state_space(
                    u, cache, pos, paged, group_layer, use_pallas)
        h = x + mixed.astype(x.dtype)
        with jax.named_scope("mlp"):
            gate, up = jnp.split(_mm(
                norm("pre_mlp_norm", h),
                self.param("gate_up", _normal(), (d, 2 * f)), dt), 2, axis=-1)
            y = _mm(nn.silu(gate) * up,
                    self.param("down", _normal(), (f, d)), dt)
        return h + y.astype(x.dtype), new_cache


class SsmHybridPeriod(nn.Module):
    """``attn_offset`` state-space layers under one scan, the attention
    layer, the period's other state-space layers under a second scan.
    ``index`` (static) is the period's place in the model."""

    cfg: SsmHybridConfig

    @nn.compact
    def __call__(self, carry, index: int, pos, paged):
        cfg = self.cfg
        before = cfg.attn_offset
        after = cfg.attn_period - 1 - before

        def body(blk, carry, layer, pos, paged):
            x, cache = carry
            return blk(x, cache, pos, paged, layer), None

        def run(name, first, n, carry):
            if not n:
                return carry
            carry, _ = nn.scan(
                body, variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(0, nn.broadcast, nn.broadcast), length=n,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(SsmHybridBlock(cfg, False, name=name), carry,
              first + jnp.arange(n), pos, paged)
            return carry

        group = index * (cfg.attn_period - 1)
        carry = run("ssm_before", group, before, carry)
        carry = SsmHybridBlock(cfg, True, name="attn_layer")(
            *carry, pos, paged, index)
        return run("ssm_after", group + before, after, carry)


class SsmHybrid(nn.Module):
    """Token ids [B, S] -> logits [B, S, V] (see the module's text)."""

    cfg: SsmHybridConfig

    #: device-side counts a paged call returns beside the pool: the rows
    #: the prefill lane's scan took as real, and the slots whose state the
    #: decode lane moved (the engine sums a tick's two lanes')
    tick_counters = (("scan_rows", "sum"), ("state_slots", "sum"))
    #: what the serving engine has to refuse for this decoder, each with
    #: its reason (`serve/engine.py:why_unsupported`)
    serving_unsupported = {
        "reference_lanes": "it serves through its paged kernels only",
        "speculative": (
            "a rejected draft token has already advanced the state-space "
            "layers' state, which keeps no earlier row to roll back to"),
        "prefill_batch": (
            "a left-padded group would run its pad columns through the "
            "recurrence"),
        "tensor_parallel": (
            "it publishes no parameter placement, and its scan and paged "
            "kernels have no manual region"),
        "prefix_cache": (
            "a shared block carries K/V and no state: a request that "
            "skipped a cached prefix would start its state-space layers "
            "from zeros"),
    }
    kv_window = None
    #: its state-space layers keep a row a slot in the pool
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

    def prefill_tile_shape(self, prefill_batch: int, prefill_chunk: int,
                           block_size: int, blocks_per_slot: int):
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
        these shapes? ``pool_block`` = (n_blocks, block_size). The scan
        has an XLA twin and does not decide a lane."""
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
            raise ValueError("SsmHybrid has no left-padded (batched "
                             "prefill) cache path")
        if (cache is None) != (paged is None):
            raise ValueError("SsmHybrid's cache path is the paged pool: "
                             "pass cache=<its four leaves> together with "
                             "paged=<view>")
        counts = None
        if paged is not None:
            from ray_lightning_tpu.ops.attention import PagedPrefillView

            if isinstance(paged, PagedPrefillView):
                if paged.real_rows is None or paged.state_slot is None:
                    raise ValueError(
                        "SsmHybrid's prefill view names the chunk's real "
                        "rows and its slot (real_rows, state_slot)")
                first, last = paged.real_rows[0], paged.real_rows[1]
                counts = jnp.stack([jnp.maximum(last - first + 1, 0),
                                    jnp.int32(0)])
            else:
                if paged.state_moves is None:
                    raise ValueError("SsmHybrid's decode view says whose "
                                     "state moves (state_moves)")
                counts = jnp.stack([jnp.int32(0), jnp.sum(
                    paged.state_moves.astype(jnp.int32))])
        embed = self.param("tok_embed", _normal(), (cfg.vocab_size, cfg.dim))
        x = embed[tokens].astype(cfg.dtype)

        # the periods one after another, each with its own stacks: a scan
        # over periods around the scans over layers would hand the inner
        # loops a SLICE of the weights, which XLA makes by copying it (every
        # weight once a lane a tick: a third of the step, PERF.md section 6,
        # PR 35); a stack that is a parameter of the program is read in place
        carry = (x, cache)
        for index in range(cfg.n_periods):
            carry = SsmHybridPeriod(cfg, name=f"period_{index}")(
                carry, index, pos, paged)
        x, new_cache = carry

        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.dim,)), cfg.norm_eps,
                     use_pallas=False)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum(
                "bsd,vd->bsv", x.astype(cfg.dtype), embed.astype(cfg.dtype),
                preferred_element_type=jnp.float32)
        if cache is None:
            return logits
        return logits, new_cache, counts
