"""Llama-3-style decoder-only transformer, TPU-first.

The flagship model (BASELINE.json config 4: Llama-3-8B FSDP on a v5p-64).
The reference has no transformer at all (its models are MLPs, reference
tests/utils.py:96-120) — this is net-new capability designed for the MXU:

  * bf16 activations, f32 RMSNorm reductions and softmax;
  * GQA attention through the pallas flash kernel (ops/pallas/flash.py);
  * SwiGLU MLP — two fused [D, 2F] projections keep matmuls large;
  * `lax.scan` over layers (one compiled layer body, L-step scan: compile
    time and HBM program size O(1) in depth) with optional
    `jax.checkpoint` rematerialization per layer;
  * sharding by annotation: `param_specs()` returns Megatron-style
    PartitionSpecs (column-split QKV/gate, row-split O/down) on the
    `tensor` axis, token-embedding sharded on `tensor`, everything
    FSDP-shardable on its largest free axis — the strategies compose
    these over the mesh;
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.ops.attention import (
    dot_product_attention,
    flash_attention_on_mesh,
)
from ray_lightning_tpu.ops.fused_ce import fused_cross_entropy
from ray_lightning_tpu.ops.ring_attention import ring_attention
from ray_lightning_tpu.ops.ulysses import ulysses_attention
from ray_lightning_tpu.ops.norms import rms_norm
from ray_lightning_tpu.ops.rope import apply_rope, rope_frequencies


# f32-accumulating dense dots (numcheck RLT801's sanctioned
# single-rounding shape; see ops/precision.py for the full contract)
from ray_lightning_tpu.ops.precision import (
    f32_acc_dot_general as _f32_acc_dot_general,
    f32_out_dot_general as _f32_out_dot_general,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True
    #: what the per-layer checkpoint saves: "nothing" (max memory savings,
    #: full recompute in backward), "dots" (save matmul outputs, recompute
    #: only elementwise — the usual best speed/memory point when HBM
    #: allows), "attn_out" (save the named attention residuals — q/k/v,
    #: the kernel output, and its logsumexp — so the backward skips the
    #: QKV projections, RoPE, and the flash forward: the attention share
    #: of the recompute tax, for ~200 MB/layer at B=8 S=2048; everything
    #: else still recomputes). Ignored when remat=False.
    remat_policy: str = "nothing"
    scan_layers: bool = True
    use_flash: bool = True
    #: shard attention over the mesh's `seq` axis — long-context training
    #: where one device cannot hold the full sequence's KV. Takes effect
    #: when the strategy's mesh has seq > 1.
    seq_parallel: bool = False
    #: "ring" (ppermute KV ring, ops/ring_attention.py — O(S/n) memory,
    #: any head count) or "ulysses" (head/sequence all_to_all,
    #: ops/ulysses.py — two collectives, needs heads % seq == 0).
    seq_parallel_mode: str = "ring"
    #: fused chunked cross-entropy (ops/fused_ce.py): training/eval loss
    #: never materializes the [B, S, V] logits — the dominant activation
    #: at V=128256. predict/generate still produce real logits.
    #: None = auto: fused for large vocabularies (>= 64k, where the
    #: materialized logits dominate HBM and may not compile at all),
    #: materialized otherwise (marginally faster, bit-identical to the
    #: historical loss path). Set True/False to force.
    fused_ce: Optional[bool] = None
    #: logits tile height for the fused CE scan (C×V live logits memory)
    ce_chunk_tokens: int = 1024
    #: compute the fused CE's gradients inline in the forward scan
    #: (ops/fused_ce.py _ce_inline) instead of rematerializing each
    #: logits tile in backward — removes the lm_head recompute tax
    #: (~one [C, D]×[D, V] pass per step) for ~D×V f32 extra residual
    #: memory. Only meaningful when the fused path is active.
    ce_inline_bwd: bool = False
    #: >0 enables the GPipe decoder path (ops/pipeline.py) when the mesh
    #: has pipe > 1: the scanned layer stack is stage-split over `pipe`
    #: and this many microbatches flow through per step. Requires
    #: scan_layers (the stacked param layout IS the pipeline's) and
    #: composes with data/fsdp; tensor/seq stay off the pipeline path.
    pipeline_microbatches: int = 0

    def __post_init__(self):
        if self.seq_parallel_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_parallel_mode must be 'ring' or 'ulysses', got "
                f"{self.seq_parallel_mode!r}"
            )
        if self.remat_policy not in ("nothing", "dots", "attn_out"):
            raise ValueError(
                f"remat_policy must be 'nothing', 'dots' or 'attn_out', "
                f"got {self.remat_policy!r}"
            )
        if self.ce_inline_bwd and not (
                self.fused_ce is True
                or (self.fused_ce is None and self.vocab_size >= 2**16)):
            # a silent no-op flag would let a user believe they measured
            # the inline path (and the planner charge for residuals that
            # never exist) — refuse the combination instead
            raise ValueError(
                "ce_inline_bwd requires the fused CE path: set "
                "fused_ce=True (or leave it auto with vocab >= 64k)"
            )
        if self.pipeline_microbatches > 0 and not self.scan_layers:
            raise ValueError(
                "pipeline_microbatches requires scan_layers=True (the "
                "stacked layer layout is what the pipeline stage-splits)"
            )
        if self.pipeline_microbatches > 0 and self.seq_parallel:
            raise ValueError(
                "pipeline_microbatches and seq_parallel are mutually "
                "exclusive (the pipeline path runs attention per stage)"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def pool_leaf_shapes(self, n_blocks: int, block_size: int):
        """The serving engine's paged pool for this model: a K and a V
        leaf (`serve/kv_cache.py:init_pool`)."""
        leaf = (self.n_layers, n_blocks, block_size, self.n_kv_heads,
                self.head_dim)
        return (leaf, leaf)

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**{**dict(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, hidden_dim=14336), **kw})

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test/debug config: same code path, laptop-sized."""
        return cls(**{**dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            hidden_dim=128, max_seq_len=256, remat=False), **kw})


def _is_prefill_view(paged) -> bool:
    """Is this paged view the PREFILL lane's (chunk-wide queries) or
    the decode lane's (one token per slot)? Import-deferred so the
    training path never pays the serve-op import."""
    from ray_lightning_tpu.ops.attention import PagedPrefillView

    return isinstance(paged, PagedPrefillView)


def _is_joined_view(paged) -> bool:
    """Is this paged view a tick's two lanes joined (the decode rows,
    then the prefill chunk, in one call)?"""
    from ray_lightning_tpu.ops.attention import PagedJoinedView

    return isinstance(paged, PagedJoinedView)


def _view_dispatch(view, cfg) -> Optional[bool]:
    """``use_pallas`` for a paged lane's attention: the view's STATIC
    ``use_pallas`` (the serve engine's build-time decision) pins the
    dispatch; absent that, the flash-style ambient policy."""
    if view.use_pallas is not None:
        return view.use_pallas
    return None if cfg.use_flash else False


def _is_flash_remat_opt(params) -> bool:
    """Is this `remat_opt` equation the flash kernel's hoisted fwd rule?

    `optimize_remat=True` rewrites EVERY such custom_vjp into a
    `remat_opt` call, so a policy keyed on the primitive name alone
    would save the residuals of any future optimized-remat custom_vjp
    in the model, not specifically attention's. The flash fwd rule tags
    its residual tuple with checkpoint_name("flash_residuals")
    (ops/pallas/flash.py _flash_fwd_rule) — those `name` equations are
    visible in the hoisted fwd jaxpr carried in the eqn params, which is
    the precise fingerprint."""
    fwd = params.get("fwd_jaxpr")
    jaxpr = getattr(fwd, "jaxpr", None)
    if jaxpr is None:
        return False
    return any(
        eqn.primitive.name == "name"
        and eqn.params.get("name") == "flash_residuals"
        for eqn in jaxpr.eqns)


def _attn_residuals_saveable(prim, *avals, **params) -> bool:
    """Checkpoint policy for remat_policy="attn_out": save the flash
    kernel's VJP residuals (q/k/v/o/lse) plus the block-level attention
    output, recompute everything else.

    Mechanism: the flash custom_vjp is defined with optimize_remat=True
    (ops/pallas/flash.py), which hoists its fwd rule into a `remat_opt`
    call whose outputs ARE the residual tuple — a custom_vjp is
    otherwise opaque to checkpoint policies (its residuals never appear
    in the primal trace; a named-saveable policy alone verifiably saved
    nothing, tests/test_ops.py). Saving the FLASH kernel's remat_opt
    outputs (scoped via `_is_flash_remat_opt` — any other
    optimize_remat custom_vjp keeps its own remat policy) is therefore
    exactly "save the attention residuals". The `name` check covers the
    XLA-reference attention path, whose output is tagged "attn_out" in
    LlamaBlock; the pallas branch deliberately does NOT tag (the kernel
    residuals already include o — tagging would double-save it)."""
    if prim.name == "remat_opt":
        return _is_flash_remat_opt(params)
    return prim.name == "name" and params.get("name") == "attn_out"


def _remat_policy(name: str):
    """Shared checkpoint-policy lookup for the scan and pipeline paths.

    "attn_out" is the point between "nothing" (recompute all) and
    "dots" (save all matmul outputs): it drops the attention share of
    the backward recompute tax — QKV projections, RoPE, and the flash
    forward never re-run — for ~200 MB/layer of saved residuals at
    B=8 S=2048 (the block input is saved by the remat boundary itself
    under every policy)."""
    return {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "attn_out": _attn_residuals_saveable,
    }[name]


def _activation_pin(cfg: "LlamaConfig", mesh, batch: int, seq: int):
    """``pin(a, *tail)`` for the training branch of a block: batch over the
    mesh's data-parallel axes (`parallel.mesh.dp_axis_names`), the sequence
    over ``seq`` where the sequence-parallel island already puts it, and the
    dimensions behind them as ``tail`` says (``"tensor"`` where
    `_PER_LAYER_SPECS` splits the weight that makes them, `None` for the
    residual stream's features): the layout `flash_attention_on_mesh` and
    `seq_island` give q, k and v, stated for every activation of the layer.

    Why: a strategy that overlays `fsdp` on the weights says nothing of the
    activations, and GSPMD is then free to let a weight's split leak into a
    product's output and reshard the ACTIVATIONS (a whole batch's residual
    stream gathered at `wqkv`, six all-to-alls of the MLP hidden in the
    backward: 1.3 GB a layer a chip at the fsdp4 cell's shapes, against
    0.38 GB of weights and gradients; PERF.md section 6, PR 42). Pinned,
    the one freedom left is to gather a layer's weights where a product
    needs them and scatter their gradients. `with_sharding_constraint`
    transposes to itself, so the cotangents are pinned too.

    The identity (no constraint in the traced program) with no mesh, with
    every data-parallel axis of size 1, or with a batch they do not divide
    (the rule `flash_attention_on_mesh` has for a one-prompt call)."""
    from ray_lightning_tpu.parallel.mesh import (
        batch_size_divisor,
        dp_axis_names,
    )

    n = 1 if mesh is None else batch_size_divisor(mesh)
    if n == 1 or batch % n:
        return lambda a, *tail: a
    from jax.sharding import NamedSharding

    s = mesh.shape.get("seq", 1)
    lead = (dp_axis_names(mesh),
            "seq" if cfg.seq_parallel and s > 1 and seq % s == 0 else None)

    def pin(a, *tail):
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(*lead, *tail)))

    return pin


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    mesh: Optional[Any] = None  # jax.sharding.Mesh (static, hashable)

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, pos=None, pad=None,
                 paged=None, layer=None):
        """Training/prefill-from-zero when cache is None; with a
        ``cache=(k_cache, v_cache)`` ([B, S_max, Hkv, hd] each) and a
        (traced) ``pos``, runs the KV-cache decode path and returns the
        updated cache as the scan output. ``pad`` ([B] int32, cache path
        only) is the per-row LEFT padding of a ragged batch: RoPE
        positions shift down by ``pad[b]`` (clamped at 0 for the pad
        rows themselves, whose outputs are discarded) and attention
        masks out the pad columns — a left-padded row decodes exactly
        like its unpadded prompt (test-pinned).

        ``paged`` (an `ops.attention.PagedDecodeView`, serving engine
        only) switches the cache path to the block-paged pool: ``cache``
        is then the WHOLE stacked pool ``([L, n_blocks, P, Hkv, hd])``
        pair and ``layer`` this block's index into it (traced under the
        layer scan, a python int otherwise) — a layer's pool is never
        taken out of the stack: the write carries the layer in its
        index, the kernel in its block ids, and the updated stack is
        returned. S must
        be 1 (one decode token per slot), ``pos`` is a
        per-slot [B] vector of cache positions, the new K/V token is
        scattered straight into the pool at the view's (already
        scratch-redirected) write index, and attention consumes the
        pool through the per-slot block tables — fused on the pallas
        path, dense-gathered on the XLA reference path
        (ops.attention.paged_attention). A `PagedPrefillView` instead
        selects the chunked PREFILL twin: S is the chunk width, ``pos``
        the group's shared scalar write offset, the whole chunk's K/V
        is scattered through ``write_block/write_offset`` and
        `ops.attention.paged_prefill` attends causally through the
        tables. A `PagedJoinedView` carries one of each: B is 1, the S
        rows are the C slots' decode tokens and then the chunk's CH,
        ``pos`` is each row's cache position ``[C + CH]``, and the rows
        part only for their K/V writes and their attention (each lane's
        as its own branch has them), so a tick that carries a chunk reads
        this block's weights once. ``paged=None`` lowers the identical
        historical program."""
        cfg = self.cfg
        d, hd = cfg.dim, cfg.head_dim
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=jnp.float32,
                        dot_general=_f32_acc_dot_general)

        B, S = x.shape[0], x.shape[1]
        n_q, n_kv = cfg.n_heads, cfg.n_kv_heads
        # training only: every activation of the layer stays on the batch
        # axes, so under FSDP the weights travel (`_activation_pin`; the
        # identity off a data-parallel mesh). `wide`: the axis of a
        # dimension a column-parallel weight makes
        pin = _activation_pin(cfg, self.mesh if cache is None else None,
                              B, S)
        wide = ("tensor" if self.mesh is not None
                and self.mesh.shape.get("tensor", 1) > 1 else None)
        x = pin(x, None)

        # `attn` / `mlp`: the block's two halves in a profiler trace (flax
        # scopes only the Dense calls; norms, RoPE, the kernel and the
        # gate fall outside them)
        with jax.named_scope("attn"):
            attn_norm_w = self.param("attn_norm", nn.initializers.ones, (d,))
            h = pin(rms_norm(x, attn_norm_w, cfg.norm_eps), None)
            # fused QKV projection: one [D, (H + 2*Hkv) * hd] matmul
            qkv = pin(dense((n_q + 2 * n_kv) * hd, name="wqkv")(h), wide)
            q, k, v = jnp.split(
                qkv, [n_q * hd, (n_q + n_kv) * hd], axis=-1)
            q = pin(q.reshape(B, S, n_q, hd), wide, None)
            k = pin(k.reshape(B, S, n_kv, hd), wide, None)
            v = pin(v.reshape(B, S, n_kv, hd), wide, None)
            if cache is None:
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
                pallas_path = False
                if (cfg.seq_parallel and self.mesh is not None
                        and self.mesh.shape.get("seq", 1) > 1):
                    # manual island: sequence sharded over `seq`; everything
                    # else stays compiler-sharded.
                    if cfg.seq_parallel_mode == "ulysses":
                        attn = ulysses_attention(
                            q, k, v, self.mesh, causal=True,
                            use_pallas=None if cfg.use_flash else False)
                    else:
                        attn = ring_attention(q, k, v, self.mesh, causal=True)
                else:
                    # use_flash=True -> auto (pallas on TPU, XLA fallback
                    # elsewhere); False -> always the XLA reference path.
                    # On a multi-device mesh the kernel runs in a manual
                    # region (XLA cannot partition a Mosaic call).
                    from ray_lightning_tpu.ops.attention import flash_uses_pallas

                    pallas_path = flash_uses_pallas(
                        q.shape, k.shape, None if cfg.use_flash else False)
                    attn = flash_attention_on_mesh(
                        q, k, v, self.mesh, causal=True,
                        use_pallas=None if cfg.use_flash else False)
                # name the attention output for remat_policy="attn_out" —
                # the save point the XLA-reference (and seq-parallel island)
                # paths offer. The pallas branch is deliberately NOT named:
                # its full VJP residual set (incl. o) is already saved
                # through the kernel's own remat_opt hoist, and naming the
                # output again would keep a second [B, S, H·hd] residual per
                # layer beyond what parallel/plan.py accounts. Under other
                # policies the name is inert. flash_uses_pallas is the SAME
                # predicate the dispatch uses, so the annotation cannot
                # drift from the path actually taken.
                if not pallas_path:
                    from jax.ad_checkpoint import checkpoint_name

                    attn = checkpoint_name(attn, "attn_out")
                new_cache = None
            elif paged is not None and _is_joined_view(paged):
                # both paged lanes in ONE pass (serve/engine.py, a tick that
                # carries a chunk): B == 1 and the S rows are the C slots'
                # decode tokens, then the CH rows of the chunk, so `wqkv`
                # above and `wo` and the MLP below read their weights once a
                # tick. ``pos`` is each row's cache position ``[C + CH]``.
                # The rows part here only, for attention: each lane's
                # kernel call is that of its own branch below, over the
                # same pool. The slot that takes the chunk is not decoding:
                # its decode row writes to scratch and is discarded, and the
                # chunk writes only blocks its slot owns, so neither lane
                # sees a row the other wrote this tick.
                assert B == 1 and pad is None, "one unpadded chunk a tick"
                dec, chunk = paged.decode, paged.prefill
                C = dec.tables.shape[0]
                q = apply_rope(q, cos, sin, positions=pos[None, :])
                k = apply_rope(k, cos, sin, positions=pos[None, :])
                pk, pv = cache  # [L, n_blocks, P, Hkv, hd] — the stack
                # write-then-attend, as either lane alone: one scatter a
                # leaf, the decode rows at their (scratch-redirected) write
                # index and the chunk's through its own
                with jax.named_scope("kv_pool"):
                    block = jnp.concatenate(
                        [dec.write_block, chunk.write_block[0]])
                    offset = jnp.concatenate(
                        [dec.write_offset, chunk.write_offset[0]])
                    pk = pk.at[layer, block, offset].set(
                        k[0].astype(pk.dtype))
                    pv = pv.at[layer, block, offset].set(
                        v[0].astype(pv.dtype))
                from ray_lightning_tpu.ops.attention import (
                    paged_attention, paged_prefill,
                )

                attn = jnp.concatenate([
                    paged_attention(
                        q[0, :C], pk, pv, dec.tables, dec.lengths,
                        use_pallas=_view_dispatch(dec, cfg),
                        layer=layer)[None],
                    paged_prefill(
                        q[:, C:], pk, pv, chunk.tables, pos[C],
                        use_pallas=_view_dispatch(chunk, cfg),
                        layer=layer)], axis=1)
                new_cache = (pk, pv)
            elif paged is not None and _is_prefill_view(paged):
                # paged PREFILL (serve/engine.py fused prefill lane): a
                # CH-token chunk per head-group row against the SHARED
                # block pool — the per-group dense cache copy never exists
                # on this path. ``pos`` is the group's shared scalar write
                # offset (chunk token j sits at cache position pos + j);
                # ``pad`` is the per-row left pad of the right-aligned
                # group (None on the single-slot lane).
                positions = jnp.broadcast_to(
                    (pos + jnp.arange(S))[None, :], (B, S))
                if pad is not None:
                    positions = jnp.maximum(positions - pad[:, None], 0)
                q = apply_rope(q, cos, sin, positions=positions)
                k = apply_rope(k, cos, sin, positions=positions)
                pk, pv = cache  # [L, n_blocks, P, Hkv, hd] — the stack
                # write-then-attend, the decode fused lane's ordering: the
                # whole chunk's K/V is scattered into OWNED pool blocks
                # (vacant group rows arrive scratch-redirected — block 0 is
                # masked garbage by contract) BEFORE attention, so each
                # query's causal window covers the in-chunk prefix too.
                # `kv_pool`: all the pool handling the paged path has
                with jax.named_scope("kv_pool"):
                    pk = pk.at[layer, paged.write_block,
                               paged.write_offset].set(k.astype(pk.dtype))
                    pv = pv.at[layer, paged.write_block,
                               paged.write_offset].set(v.astype(pv.dtype))
                from ray_lightning_tpu.ops.attention import paged_prefill

                attn = paged_prefill(q, pk, pv, paged.tables, pos, pad=pad,
                                     use_pallas=_view_dispatch(paged, cfg),
                                     layer=layer)
                new_cache = (pk, pv)
            elif paged is not None:
                # paged decode (serve/engine.py fused lane): one token per
                # slot against the SHARED block pool — no per-slot dense
                # cache copy exists on the kernel path. ``pos`` is a [B]
                # vector (per-slot cache position); its RoPE position is
                # pos - pad for a left-pad-prefilled slot.
                assert S == 1, "the paged cache path decodes one token/slot"
                positions = pos[:, None] + jnp.arange(S)[None, :]
                if pad is not None:
                    positions = jnp.maximum(positions - pad[:, None], 0)
                q = apply_rope(q, cos, sin, positions=positions)
                k = apply_rope(k, cos, sin, positions=positions)
                pk, pv = cache  # [L, n_blocks, P, Hkv, hd] — the stack
                # write-then-attend, exactly the dense cache path's
                # dynamic_update_slice ordering: the token's own K/V is
                # visible to its query. Idle/prefilling slots arrive
                # scratch-redirected (write_block 0) — duplicate scratch
                # writes race, but scratch is masked garbage by contract.
                with jax.named_scope("kv_pool"):
                    pk = pk.at[layer, paged.write_block,
                               paged.write_offset].set(
                                   k[:, 0].astype(pk.dtype))
                    pv = pv.at[layer, paged.write_block,
                               paged.write_offset].set(
                                   v[:, 0].astype(pv.dtype))
                from ray_lightning_tpu.ops.attention import paged_attention

                attn = paged_attention(
                    q[:, 0], pk, pv, paged.tables, paged.lengths, pad=pad,
                    use_pallas=_view_dispatch(paged, cfg),
                    layer=layer)[:, None]
                new_cache = (pk, pv)
            else:
                positions = pos + jnp.arange(S)
                if pad is not None:
                    # left-padded ragged batch: row b's first real token
                    # sits at column pad[b] but is RoPE position 0; clamp
                    # keeps the (discarded) pad rows' table reads in range
                    positions = jnp.maximum(
                        positions[None, :] - pad[:, None], 0)
                q = apply_rope(q, cos, sin, positions=positions)
                k = apply_rope(k, cos, sin, positions=positions)
                ck, cv = cache  # [B, S_max, Hkv, hd]
                ck = jax.lax.dynamic_update_slice_in_dim(
                    ck, k.astype(ck.dtype), pos, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cv, v.astype(cv.dtype), pos, axis=1)
                if (S > 1 and isinstance(pos, int) and pos == 0
                        and pad is None):
                    # prefill from empty context: plain causal attention over
                    # the chunk itself (flash path — never materialize the
                    # [S, S_max] masked score matrix against the zero tail).
                    attn = flash_attention_on_mesh(
                        q, k, v, self.mesh, causal=True,
                        use_pallas=None if cfg.use_flash else False)
                else:
                    # single-token decode (or mid-sequence chunk, or a
                    # left-padded prefill): masked reference SDPA over the
                    # cache — S is tiny here.
                    kv_pos = jnp.arange(ck.shape[1])[None, None, None, :]
                    q_pos = (pos + jnp.arange(S))[None, None, :, None]
                    mask = kv_pos <= q_pos
                    if pad is not None:
                        # pad columns are not context for anyone
                        mask = mask & (kv_pos >= pad[:, None, None, None])
                    attn = dot_product_attention(
                        q, ck, cv, causal=False, mask=mask)
                new_cache = (ck, cv)
            attn = pin(attn.reshape(B, S, n_q * hd), wide)
            x = pin(x + dense(d, name="wo")(attn), None)

        with jax.named_scope("mlp"):
            mlp_norm_w = self.param("mlp_norm", nn.initializers.ones, (d,))
            h = pin(rms_norm(x, mlp_norm_w, cfg.norm_eps), None)
            # fused gate+up: one [D, 2F] matmul
            gate_up = pin(dense(2 * cfg.hidden_dim, name="w_gate_up")(h),
                          wide)
            gate, up = jnp.split(gate_up, 2, axis=-1)
            hidden = pin(nn.silu(gate) * up, wide)
            x = pin(x + dense(d, name="w_down")(hidden), None)
        return x, new_cache  # (carry, ys) pair so nn.scan drives the block


class Llama(nn.Module):
    """Flax core model: token ids [B, S] -> logits [B, S, V]."""

    cfg: LlamaConfig
    mesh: Optional[Any] = None  # set by the strategy for seq/tensor islands

    # ---- what the serving engine asks of a decoder (serve/engine.py) ----
    #: device-side counts a paged call returns beside the pool: none
    tick_counters = ()
    #: serving modes the engine has to refuse for this decoder: none
    serving_unsupported = ()
    #: no sliding-window layers: one group of the pool
    kv_window = None
    #: no recurrent layers: no leaf of the pool holds a row a slot
    slot_state = False
    #: a `PagedJoinedView` is served: a tick's decode rows and its prefill
    #: chunk go through the layers in one call, the weights read once
    joins_lanes = True

    def serving_param_specs(self):
        return llama_param_specs(self.cfg)

    def decode_tile_tokens(self, block_size: int, blocks_per_slot: int):
        """Tokens of the paged decode kernel's KV tile, by the kernel's
        own rule. A decoder that states one has a kernel whose time is
        its live tiles': a slot handed a length of 0 costs none and
        reads zeros, so the engine hands that to every slot that is not
        decoding (`serve/engine.py:_decode_fused`)."""
        from ray_lightning_tpu.ops.pallas.paged_attention import (
            decode_tile_tokens,
        )

        return decode_tile_tokens(block_size, blocks_per_slot)

    def prefill_tile_shape(self, prefill_batch: int, prefill_chunk: int,
                           block_size: int, blocks_per_slot: int):
        """(query tile rows, KV tile tokens) of the paged prefill kernel
        for the engine's chunk, by the kernel's own rule: its time is that
        of the KV tiles each query tile can see (`serve/engine.py:
        _step_work` counts them as ``prefill_tiles``)."""
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
        these shapes? The predicates the ops' own dispatch uses;
        ``pool_block`` = (n_blocks, block_size)."""
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
    def __call__(self, tokens: jnp.ndarray, cache=None, pos=None,
                 pad=None, paged=None, last_only: bool = False,
                 return_hidden: bool = False):
        """Training/eval: ``model(tokens) -> logits``. Decoding:
        ``model(tokens, cache=(k, v), pos=p) -> (logits, new_cache)``
        with cache leaves stacked over layers ([L, B, S_max, Hkv, hd];
        see `init_cache`) and ``p`` the write offset (python 0 for a
        fresh prefill, traced thereafter). ``last_only`` projects only
        the final position through the lm_head (prefill wants one row of
        logits, not [S, vocab]). ``return_hidden`` skips the lm_head and
        returns the final-norm'd [B, S, D] states — the fused-CE loss
        path projects them chunk-wise (ops/fused_ce.py). ``paged``
        (serving engine) switches the cache path to the block-paged
        pool — cache leaves are then [L, n_blocks, P, Hkv, hd] and
        ``pos`` is a per-slot vector; see `LlamaBlock.__call__`. The
        stacked pool is then the layer scan's CARRY (the layer index
        its xs): every block writes into and reads from the one stack
        at its own layer, so the returned pool is the donated buffer
        updated in place, and no layer's pool is ever sliced out or
        copied. Under a `PagedJoinedView` (`joins_lanes`: a tick's decode
        rows and its prefill chunk in one call, ``tokens [1, C + CH]``)
        the head reads the C decode rows and the one chunk row the view's
        ``last_row`` keeps: logits ``[1, C + 1, V]``. The dense cache
        (``paged=None``) rides the scan as xs in / ys out, as it always
        has."""
        cfg = self.cfg
        # take from the f32 table and round the (token-sized) result,
        # rather than dtype=cfg.dtype (which rounds the TABLE before the
        # take): gather commutes with rounding so the forward is
        # bitwise identical, but the backward now upcasts per-token
        # cotangents BEFORE the vocab-sized scatter-add, so the
        # embedding grad accumulates — and reduce-scatters — in f32
        # (numcheck RLT804) instead of bf16
        embed = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=jnp.float32,
            param_dtype=jnp.float32, name="tok_embed",
        )
        x = embed(tokens).astype(cfg.dtype)
        cos, sin = rope_frequencies(
            cfg.head_dim, cfg.max_seq_len, cfg.rope_theta, dtype=jnp.float32
        )
        if cache is None:
            cos, sin = cos[: tokens.shape[1]], sin[: tokens.shape[1]]

        block = LlamaBlock
        if cfg.remat and cache is None:
            block = nn.remat(block, policy=_remat_policy(cfg.remat_policy))
        new_cache = None
        if cfg.scan_layers:
            # one compiled block, scanned over a stacked-params layer axis
            scan = partial(
                nn.scan,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            if cache is None:
                x, _ = scan(block, in_axes=nn.broadcast)(
                    cfg, self.mesh, name="layers")(x, cos, sin)
            elif paged is not None:
                # the stacked pool is the scan's CARRY beside x, and the
                # layer index its only xs: xs in / ys out are different
                # buffers, so a pool that rode them was sliced out of the
                # stack, written back into a second stack and copied over
                # the donated one, every layer of every tick. Carried, it
                # stays where it is: each block scatters its tokens at
                # [layer, block, offset] and the kernels read the layer's
                # blocks out of the stack through their block tables
                # (ops/pallas/paged_attention.py:stack_as_pool). The
                # paged view (block tables / lengths / write indices) is
                # layer-invariant and broadcasts like pos/pad.
                def layer_body(blk, carry, layer, cos, sin, pos, pad,
                               paged):
                    h, pk, pv = carry
                    h, (pk, pv) = blk(h, cos, sin, (pk, pv), pos, pad,
                                      paged, layer)
                    return (h, pk, pv), None

                (x, *new_cache), _ = scan(
                    layer_body, in_axes=(0,) + (nn.broadcast,) * 5,
                )(block(cfg, self.mesh, name="layers"), (x, *cache),
                  jnp.arange(cfg.n_layers), cos, sin, pos, pad, paged)
                new_cache = tuple(new_cache)
            else:
                # the dense cache rides the scan: in over the layer axis,
                # updated cache collected as the scan output (out_axes=0)
                x, new_cache = scan(
                    block,
                    in_axes=(nn.broadcast, nn.broadcast, 0,
                             nn.broadcast, nn.broadcast, nn.broadcast),
                    out_axes=0,
                )(cfg, self.mesh, name="layers")(x, cos, sin, cache,
                                                 pos, pad, paged)
        elif paged is not None:
            # the same rule unrolled: every block writes into and reads
            # from the one stack at its static layer index
            new_cache = cache
            for i in range(cfg.n_layers):
                x, new_cache = block(cfg, self.mesh, name=f"layer_{i}")(
                    x, cos, sin, new_cache, pos, pad, paged, i)
        else:
            caches = []
            for i in range(cfg.n_layers):
                layer_cache = None if cache is None else jax.tree.map(
                    lambda c, i=i: c[i], cache)
                x, c = block(cfg, self.mesh, name=f"layer_{i}")(
                    x, cos, sin, layer_cache, pos, pad, paged)
                caches.append(c)
            if cache is not None:
                new_cache = jax.tree.map(
                    lambda *cs: jnp.stack(cs, axis=0), *caches)

        final_w = self.param("final_norm", nn.initializers.ones, (cfg.dim,))
        if last_only:
            x = x[:, -1:, :]
        if paged is not None and _is_joined_view(paged):
            # the head reads the C decode rows and the ONE row of the chunk
            # the step keeps, taken before the product: [C + 1, V] logits,
            # not [C + CH, V]
            C = paged.decode.tables.shape[0]
            x = jnp.concatenate([
                x[:, :C], jax.lax.dynamic_slice_in_dim(
                    x, C + paged.last_row, 1, axis=1)], axis=1)
        x = rms_norm(x, final_w, cfg.norm_eps)
        if return_hidden:
            # lm_head params still exist (init traces the default path);
            # the loss projects these states tile-by-tile instead.
            return x
        if cfg.tie_embeddings:
            with jax.named_scope("lm_head"):
                logits = embed.attend(x.astype(jnp.float32))
        else:
            # vocab projection at activation dtype (bf16 operands hit
            # the MXU at full rate; ~3% step-time win) with an f32
            # accumulator the logits keep — loss/sampling math runs on
            # the unrounded sum (_f32_out_dot_general).
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=jnp.float32, name="lm_head",
                dot_general=_f32_out_dot_general,
            )(x).astype(jnp.float32)
        if cache is None:
            return logits
        return logits, new_cache


def _stacked(spec: P, stacked: bool) -> P:
    """Prepend the scan layer axis to a per-layer spec. The layer axis
    carries `pipe` — on meshes without pipeline parallelism the strategy
    drops the size-1 axis (Strategy._adapt_spec) and it is replicated as
    before; with pipe > 1 each stage group owns its contiguous block."""
    return P("pipe", *spec) if stacked else spec


#: per-layer tensor-parallel placement (no fsdp, no layer axis), which
#: `llama_param_specs` stacks and the strategy overlays with fsdp.
_PER_LAYER_SPECS: Dict[str, P] = {
    "wqkv/kernel": P(None, "tensor"),
    "wo/kernel": P("tensor", None),
    "w_gate_up/kernel": P(None, "tensor"),
    "w_down/kernel": P("tensor", None),
    "attn_norm": P(),
    "mlp_norm": P(),
}


def llama_param_specs(cfg: LlamaConfig) -> Dict[str, P]:
    """Megatron-style tensor-parallel placement for every weight.

    Keys are `/`-joined param paths as produced by utils.pytree._path_str.
    Column-parallel (output dim on `tensor`): wqkv, w_gate_up.
    Row-parallel (input dim on `tensor`): wo, w_down.
    Embedding: vocab on `tensor`. Norm gains: replicated (spec P()).
    The strategies overlay `fsdp` on whatever axis is still free.
    """
    st = cfg.scan_layers
    specs: Dict[str, P] = {
        "tok_embed/embedding": P("tensor", None),
        "final_norm": P(),
    }
    if not cfg.tie_embeddings:
        specs["lm_head/kernel"] = P(None, "tensor")
    per_layer = _PER_LAYER_SPECS
    if st:
        for k, v in per_layer.items():
            specs[f"layers/{k}"] = _stacked(v, True)
    else:
        for i in range(cfg.n_layers):
            for k, v in per_layer.items():
                specs[f"layer_{i}/{k}"] = v
    return specs


def cross_entropy_loss(
    logits: jnp.ndarray, targets: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Token-level CE in f32; `mask` (0/1) excludes padding."""
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets
    )
    if mask is not None:
        return (losses * mask).sum() / jnp.maximum(mask.sum(), 1)
    return losses.mean()


def init_cache(cfg: LlamaConfig, batch: int, max_len: int):
    """Zeroed KV cache, leaves [n_layers, B, max_len, Hkv, head_dim]
    (layer axis matches the scan's in/out axes)."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


@functools.lru_cache(maxsize=32)
def _compiled_generate(model: Llama, B: int, S0: int, max_new_tokens: int,
                       temperature: float, top_k: Optional[int],
                       cache_len: int, padded: bool):
    """Build-and-jit once per (model, shape, sampling) key so repeated
    generate() calls hit XLA's compile cache instead of retracing a
    fresh closure every time. The KV cache is an ARGUMENT, donated:
    the caller's `init_cache` buffer is consumed in place, so the
    decode holds one cache in HBM, never an input copy next to the
    updated one (the second-full-cache failure mode this signature
    retires)."""
    cfg = model.cfg

    def sample(logits, rng):
        if temperature == 0.0:
            return logits.argmax(-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][:, -1][:, None]
            logits = jnp.where(logits >= kth, logits, -jnp.inf)
        return jax.random.categorical(rng, logits).astype(jnp.int32)

    def run(params, prompt, rng, cache, pad):
        logits, cache = model.apply({"params": params}, prompt,
                                    cache=cache, pos=0, pad=pad,
                                    last_only=True)
        last = logits[:, -1, :]
        out = jnp.zeros((B, max_new_tokens), jnp.int32)

        def body(t, carry):
            last, cache, out, rng = carry
            rng, sub = jax.random.split(rng)
            tok = sample(last, sub)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, tok[:, None], t, axis=1)
            logits, cache = model.apply({"params": params}, tok[:, None],
                                        cache=cache, pos=S0 + t, pad=pad)
            return (logits[:, 0, :], cache, out, rng)

        _, cache, out, _ = jax.lax.fori_loop(
            0, max_new_tokens, body, (last, cache, out, rng))
        # the final cache is RETURNED so the donated input has an
        # output to alias — donation with no matching output is a
        # silent no-op (plus a UserWarning per compile); the caller
        # drops it, the buffer is simply reused in place
        return out, cache

    if not padded:
        # the pad argument must not appear in the unpadded program at
        # all (bitwise pin vs the historical path)
        def run_nopad(params, prompt, rng, cache):
            return run(params, prompt, rng, cache, None)

        return jax.jit(run_nopad, donate_argnums=(3,))
    return jax.jit(run, donate_argnums=(3,))


def generate(
    model: Llama,
    params,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    seed: int = 0,
    cache_len: Optional[int] = None,
    prompt_lengths=None,
) -> jnp.ndarray:
    """Autoregressive decoding with a KV cache, one compiled program:
    flash-attention prefill over the prompt (one row of lm_head logits),
    then a `lax.fori_loop` of single-token steps (each an in-place
    `dynamic_update_slice` into the DONATED cache — static shapes
    throughout, no per-token recompilation, one cache's HBM; repeated
    calls reuse the compiled program).

    Greedy when temperature == 0; otherwise temperature (+ optional
    top-k) sampling. ``cache_len`` sizes the KV cache explicitly (any
    length >= prompt + max_new_tokens — no rounding is imposed);
    default is exactly prompt + max_new_tokens. ``prompt_lengths``
    ([B] ints) declares a LEFT-padded ragged batch: row b's real prompt
    is its last ``prompt_lengths[b]`` columns, and each row decodes
    exactly as its unpadded prompt would (test-pinned). Returns
    [B, max_new_tokens] int32.
    """
    B, S0 = prompt.shape
    explicit_cache_len = cache_len is not None
    if cache_len is None:
        cache_len = S0 + max_new_tokens
    if cache_len < S0 + max_new_tokens:
        raise ValueError(
            f"cache_len ({cache_len}) is smaller than prompt ({S0}) + "
            f"max_new_tokens ({max_new_tokens})"
        )
    if cache_len > model.cfg.max_seq_len:
        what = (f"cache_len ({cache_len})" if explicit_cache_len else
                f"prompt ({S0}) + max_new_tokens ({max_new_tokens})")
        raise ValueError(
            f"{what} exceeds max_seq_len ({model.cfg.max_seq_len})"
        )
    pad = None
    if prompt_lengths is not None:
        lengths = np.asarray(prompt_lengths, np.int32)
        if lengths.shape != (B,):
            raise ValueError(
                f"prompt_lengths must have shape ({B},), got "
                f"{lengths.shape}")
        if (lengths < 1).any() or (lengths > S0).any():
            # a length beyond the prompt width would produce a NEGATIVE
            # pad — RoPE positions silently shift up and every decode
            # is wrong with no error
            raise ValueError(
                f"prompt_lengths must be within [1, {S0}] (the padded "
                f"prompt width), got {lengths.tolist()}")
        pad = jnp.asarray(S0 - lengths)
    run = _compiled_generate(model, B, S0, max_new_tokens,
                             float(temperature), top_k, int(cache_len),
                             pad is not None)
    cache = init_cache(model.cfg, B, cache_len)
    if pad is None:
        out, _ = run(params, prompt, jax.random.key(seed), cache)
    else:
        out, _ = run(params, prompt, jax.random.key(seed), cache, pad)
    return out


class LlamaModule(TpuModule):
    """TpuModule wrapper: next-token prediction on {"tokens": [B, S+1]}
    (or {"inputs","targets"} pairs)."""

    def __init__(self, cfg: Optional[LlamaConfig] = None,
                 lr: float = 3e-4, weight_decay: float = 0.1,
                 warmup_steps: int = 100, total_steps: int = 10000,
                 mu_dtype: Optional[Any] = None,
                 **cfg_overrides):
        """``mu_dtype``: storage dtype for Adam's first moment (e.g.
        ``jnp.bfloat16``; default None = the params' f32). Halves the
        mu buffer — ~1/4 of optimizer HBM — which on a memory-capped
        chip buys batch instead; the variance (nu) always stays f32.
        The planner charges the real dtype automatically (it eval_shapes
        this optimizer), as do checkpoints (orbax saves the tree as-is)."""
        super().__init__()
        if cfg is None:
            cfg = LlamaConfig(**cfg_overrides)
        elif cfg_overrides:
            cfg = dataclasses.replace(cfg, **cfg_overrides)
        self.cfg = cfg
        self.lr = lr
        self.weight_decay = weight_decay
        self.warmup_steps = warmup_steps
        self.total_steps = total_steps
        self.mu_dtype = mu_dtype
        self.save_hyperparameters(
            cfg=cfg, lr=lr, weight_decay=weight_decay,
            warmup_steps=warmup_steps, total_steps=total_steps,
            mu_dtype=mu_dtype,
        )

    def configure_model(self):
        # `self.mesh` is bound by Strategy.setup before the model builds,
        # so seq/tensor manual islands (ring attention) see the live mesh.
        return Llama(self.cfg, mesh=self.mesh)

    def configure_optimizers(self):
        sched = optax.warmup_cosine_decay_schedule(
            0.0, self.lr, self.warmup_steps, max(self.total_steps, 2),
            end_value=self.lr * 0.1,
        )
        return optax.adamw(sched, b1=0.9, b2=0.95,
                           weight_decay=self.weight_decay,
                           mu_dtype=self.mu_dtype)

    def param_specs(self, params) -> Dict[str, P]:
        return llama_param_specs(self.cfg)

    def _split(self, batch):
        if "tokens" in batch:
            toks = batch["tokens"]
            return toks[:, :-1], toks[:, 1:], batch.get("mask")
        return batch["inputs"], batch["targets"], batch.get("mask")

    def _use_fused_ce(self) -> bool:
        if self.cfg.fused_ce is not None:
            return self.cfg.fused_ce
        return self.cfg.vocab_size >= 2**16

    def _use_pipeline(self) -> bool:
        return (self.cfg.pipeline_microbatches > 0
                and self.mesh is not None
                and self.mesh.shape.get("pipe", 1) > 1)

    def _pipelined_hidden(self, params, tokens):
        """GPipe decoder path: the SAME stacked `layers` params the scan
        path trains, stage-split over the mesh's `pipe` axis
        (ops/pipeline.py) — embedding / final norm / lm_head run outside
        the pipeline, numerics identical to the scan path."""
        from ray_lightning_tpu.ops.pipeline import gpipe_apply

        cfg = self.cfg
        if any(self.mesh.shape.get(ax, 1) > 1 for ax in ("tensor", "seq")):
            raise ValueError(
                "the pipeline path composes with data/fsdp only; drop "
                "tensor/seq from the mesh or disable "
                "pipeline_microbatches"
            )
        emb = params["tok_embed"]["embedding"]
        x = jnp.take(emb, tokens, axis=0).astype(cfg.dtype)
        cos, sin = rope_frequencies(
            cfg.head_dim, cfg.max_seq_len, cfg.rope_theta, dtype=jnp.float32
        )
        cos, sin = cos[: tokens.shape[1]], sin[: tokens.shape[1]]
        block = LlamaBlock(cfg, None)

        def stage_fn(lp, h, cos, sin):
            return block.apply({"params": lp}, h, cos, sin)[0]

        policy = _remat_policy(cfg.remat_policy)
        h = gpipe_apply(
            stage_fn, params["layers"], x, self.mesh,
            microbatches=cfg.pipeline_microbatches,
            remat=cfg.remat, remat_policy=policy, extra=(cos, sin),
        )
        return rms_norm(h, params["final_norm"], cfg.norm_eps)

    def _loss(self, params, inputs, targets, mask):
        cfg = self.cfg
        use_pipe = self._use_pipeline()
        use_fused = self._use_fused_ce()
        if not (use_pipe or use_fused):
            return cross_entropy_loss(
                self.apply(params, inputs), targets, mask)
        hidden = (self._pipelined_hidden(params, inputs) if use_pipe
                  else self.apply(params, inputs, return_hidden=True))
        if use_fused:
            if cfg.tie_embeddings:
                w = params["tok_embed"]["embedding"].T
            else:
                w = params["lm_head"]["kernel"]
            return fused_cross_entropy(
                hidden, w, targets, mask,
                chunk_tokens=cfg.ce_chunk_tokens,
                compute_dtype=cfg.dtype,
                inline_backward=cfg.ce_inline_bwd,
            )
        # materialized logits from the pipelined hidden states — the same
        # math the flax head performs: cfg.dtype operands with the f32
        # accumulator kept for the loss (_f32_out_dot_general's
        # contract; a plain cfg.dtype @ here is numcheck's RLT801)
        if cfg.tie_embeddings:
            w = params["tok_embed"]["embedding"].T
        else:
            w = params["lm_head"]["kernel"]
        logits = _f32_out_dot_general(
            hidden.astype(cfg.dtype), w.astype(cfg.dtype),
            (((hidden.ndim - 1,), (0,)), ((), ())))
        return cross_entropy_loss(logits, targets, mask)

    def training_step(self, params, batch, rng):
        inputs, targets, mask = self._split(batch)
        loss = self._loss(params, inputs, targets, mask)
        self.log("train_loss", loss)
        return loss

    def validation_step(self, params, batch):
        inputs, targets, mask = self._split(batch)
        return {"val_loss": self._loss(params, inputs, targets, mask)}

    def predict_step(self, params, batch):
        inputs, _, _ = self._split(batch)
        return self.apply(params, inputs).argmax(-1)

    def init_params(self, rng, batch):
        inputs, _, _ = self._split(batch)
        return self.model.init(rng, inputs)["params"]

    def generate(self, prompt, max_new_tokens: int, **kw) -> jnp.ndarray:
        """KV-cache autoregressive decoding with the trained params."""
        assert self.params is not None, "fit or load a checkpoint first"
        self.setup()
        return generate(self.model, self.params, jnp.asarray(prompt),
                        max_new_tokens, **kw)

