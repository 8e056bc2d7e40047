"""Fused (chunked) cross-entropy: lm_head projection + CE without ever
materializing the full [B, S, V] logits tensor.

The HBM hazard: at Llama-3-8B scale (V=128256) full-sequence f32 logits
are ~2 GB per 4k-token microbatch — they dominate activation memory and
stall the matmul pipeline on writeback. (The reference has no LM path at
all — its models are MLPs, reference tests/utils.py:96-120 — so this is
net-new capability, built TPU-first.)

Design (XLA-idiomatic, no hand-scheduling):
  * flatten tokens, `lax.scan` over chunks of C tokens: each step computes
    a [C, V] logits tile (bf16 matmul on the MXU, f32 accumulation via
    ``preferred_element_type``), reduces it to per-token loss, and
    discards it — live logits memory is O(C·V) instead of O(B·S·V);
  * `jax.checkpoint` on the chunk body: backward RECOMPUTES the tile
    instead of saving it, so the residual set stays O(C·V) there too
    (the classic Liger-style fused-CE memory shape, expressed as remat
    + scan rather than a hand-written kernel — XLA fuses the matmul,
    logsumexp and subtraction into the tile);
  * grad w.r.t. the lm_head weight accumulates across scan steps
    automatically (scan's backward carries the cotangent sum).

Matches `cross_entropy_loss` (models/llama.py) bit-for-bit in f32 up to
reduction order.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _inline_unroll_max() -> int:
    """Chunk-count ceiling for unrolling the inline-CE forward (above it,
    fall back to lax.scan). Parse-or-default on the env override — a
    malformed value must degrade (with a warning, so a mistyped override
    is debuggable), not fail the training step at trace time — the same
    policy as the flash block-size knobs (ops/pallas/flash.py
    _env_block)."""
    raw = os.environ.get("RLT_CE_INLINE_UNROLL_MAX")
    if raw is None:
        return 16
    try:
        return int(raw)
    except ValueError:
        import warnings

        warnings.warn(
            f"RLT_CE_INLINE_UNROLL_MAX={raw!r} is not an int; "
            "using default 16", stacklevel=2)
        return 16


# the scope every op of this module carries in a profiler trace: both
# backward passes (autodiff's and `_ce_inline_bwd`) inherit it
@jax.named_scope("fused_ce")
def fused_cross_entropy(
    hidden: jnp.ndarray,
    lm_head: jnp.ndarray,
    targets: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    chunk_tokens: int = 1024,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    inline_backward: bool = False,
) -> jnp.ndarray:
    """Mean token CE of ``normalize(hidden) @ lm_head`` vs ``targets``.

    hidden:  [B, S, D] final-norm'd activations (any float dtype).
    lm_head: [D, V] projection weight (the `lm_head/kernel` param, or the
             transposed embedding for tied-embedding models).
    targets: [B, S] int labels.
    mask:    optional [B, S] 0/1 validity mask.
    chunk_tokens: logits tile height C; live logits memory is C×V.
    inline_backward: compute the CE gradients DURING the forward pass
             (see ``_ce_inline``) instead of rematerializing each logits
             tile in the backward; trades a D×V residual (the lm_head's
             dtype) for one fewer [C, D]×[D, V] matmul pass per step.
             Exact for hidden/lm_head gradients at any cotangent scale.
             Caveat: the MASK cotangent is zero on this path (the default
             path differentiates through the mean's weighting) — do not
             use it with a learnable mask.

    Returns the scalar mean loss (f32), masked-token weighted.
    """
    if inline_backward:
        # dtype travels as its NAME: custom_vjp static args must be
        # plain hashable non-array values (a np.dtype is rejected)
        return _ce_inline(chunk_tokens, jnp.dtype(compute_dtype).name,
                          hidden, lm_head, targets,
                          jnp.ones(targets.shape, jnp.float32)
                          if mask is None
                          else mask.astype(jnp.float32))
    x, t, m, n_chunks, C = _prep_chunks(
        hidden, targets, mask, chunk_tokens, compute_dtype)
    D = hidden.shape[-1]
    w = lm_head.astype(compute_dtype)

    @jax.checkpoint
    def chunk_loss(x_c, t_c):
        # [C, V] tile: bf16 MXU matmul, f32 accumulation
        logits = jnp.dot(x_c, w, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
        return lse - tgt  # [C] f32

    def body(carry, inp):
        loss_sum, weight_sum = carry
        x_c, t_c, m_c = inp
        losses = chunk_loss(x_c, t_c)
        return (loss_sum + (losses * m_c).sum(),
                weight_sum + m_c.sum()), None

    (loss_sum, weight_sum), _ = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (x.reshape(n_chunks, C, D), t.reshape(n_chunks, C),
         m.reshape(n_chunks, C)),
    )
    return loss_sum / jnp.maximum(weight_sum, 1.0)


def _prep_chunks(hidden, targets, mask, chunk_tokens, compute_dtype):
    """Shared flatten/cast/pad tiling for both CE paths.

    Static tiling: pad T up to a multiple of the tile height with
    zero-masked rows (never fall back to one giant tile — an awkward
    prime T must not silently materialize the [T, V] logits this module
    exists to avoid). Returns flat (x [T+pad, D], t, m, n_chunks, C).
    """
    B, S, D = hidden.shape
    T = B * S
    x = hidden.reshape(T, D).astype(compute_dtype)
    t = targets.reshape(T)
    m = (jnp.ones((T,), jnp.float32) if mask is None
         else mask.reshape(T).astype(jnp.float32))
    C = min(max(1, chunk_tokens), T)
    pad = (-T) % C
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, D), x.dtype)])
        t = jnp.concatenate([t, jnp.zeros((pad,), t.dtype)])
        m = jnp.concatenate([m, jnp.zeros((pad,), m.dtype)])
    return x, t, m, (T + pad) // C, C


# ---- inline-backward variant ---------------------------------------------
#
# The chunked-remat path above pays a pure recompute tax in backward: each
# [C, V] logits tile is materialized a SECOND time (jax.checkpoint) just to
# rebuild the softmax, then two more matmuls produce dx and dW — 4 tile
# matmul passes per step where 3 carry useful FLOPs. At the flagship bench
# shape (D=2048, V=128256) that recompute is ~10% of the whole training
# step's executed FLOPs.
#
# The fix (the Liger-kernel idea, expressed as XLA-level scan + custom_vjp
# rather than a hand-written kernel): CE is the ROOT of the loss graph, and
# its gradient is LINEAR in the upstream cotangent g — so compute
# (dx, dW) for g=1 during the forward scan, store them as residuals, and
# have the backward just scale by g. Exact for any g (grad-accumulation
# scans, loss weighting); no logits tile is ever built twice. Bonus: dW
# accumulates in f32 across chunks (the autodiff path accumulates the
# bf16-cast weight's cotangent chunk-by-chunk in bf16).
#
# Cost: residual memory dx [T, D] (activation-sized) + dW [D, V] stored in
# the lm_head's dtype (f32 for this framework's f32-param models) — the
# same footprint as the weight-grad buffer backward allocates anyway, just
# live earlier. At 8B/128k-vocab scale that is ~2 GB/chip under fsdp=8,
# acceptable against the recompute saving; it is NOT the default because
# tiny-memory configs may prefer the remat path.


def _ce_inline_fwd(chunk_tokens, dtype_name, hidden, lm_head, targets, m):
    compute_dtype = jnp.dtype(dtype_name)
    B, S, D = hidden.shape
    T = B * S
    V = lm_head.shape[1]
    x, t, mm, n_chunks, C = _prep_chunks(
        hidden, targets, m, chunk_tokens, compute_dtype)
    pad = n_chunks * C - T
    w = lm_head.astype(compute_dtype)
    # Σm is known BEFORE the scan, so per-chunk dlogits can carry the
    # final 1/Σm normalization and dW is a plain sum across chunks.
    weight_sum = mm.sum()
    inv = 1.0 / jnp.maximum(weight_sum, 1.0)

    def body(dw_acc, inp):
        x_c, t_c, m_c = inp
        logits = jnp.dot(x_c, w, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
        loss_c = ((lse - tgt) * m_c).sum()
        # d(mean CE)/d(logits) = (softmax - onehot) * m/Σm — computed
        # here, once, from the tile that is already live. The onehot is a
        # broadcasted-iota compare, NOT a scatter: elementwise, so XLA
        # fuses exp + subtract + scale + cast into one pass over the tile
        # and the only materialized [C, V] intermediates are the f32
        # logits and the bf16 dlogits (a scatter would force a second
        # f32 [C, V] buffer — the peak-memory cliff that kept the larger
        # inline batches from compiling on a 16 GB chip).
        coeff = m_c * inv
        onehot = (
            jax.lax.broadcasted_iota(t_c.dtype, logits.shape, 1)
            == t_c[:, None]
        )
        dlogits = (
            (jnp.exp(logits - lse[:, None]) - onehot) * coeff[:, None]
        ).astype(compute_dtype)
        dx_c = jnp.dot(dlogits, w.T, preferred_element_type=jnp.float32)
        dw_acc = dw_acc + jnp.dot(x_c.T, dlogits,
                                  preferred_element_type=jnp.float32)
        return dw_acc, (loss_c, dx_c.astype(hidden.dtype))

    xs = (x.reshape(n_chunks, C, D), t.reshape(n_chunks, C),
          mm.reshape(n_chunks, C))
    if n_chunks <= _inline_unroll_max():
        # Straight-line chunk chain instead of a `while` loop: n_chunks is
        # static, and a lax.scan whose CARRY is the [D, V] f32 dW
        # accumulator (~1 GB at Llama-3 vocab) is the program shape the
        # TPU compile path handled worst in the July 2026 v5e sweeps
        # (minutes-long or failing compiles at n_chunks >= 2; not
        # re-measured on the current toolchain); unrolling removes the
        # while-loop + giant-carry structure entirely. The
        # optimization_barrier threads each chunk's inputs through the
        # previous chunk's dW so the bodies form a data-dependence CHAIN:
        # without it only the dw adds are ordered and the scheduler may
        # overlap several [C, V] logits tiles, silently breaking the
        # O(C·V) live-logits bound this module exists to provide (and
        # that parallel/plan.py charges for exactly once).
        dw = jnp.zeros((D, V), jnp.float32)
        loss_parts, dx_parts = [], []
        for i in range(n_chunks):
            inp = jax.tree.map(lambda a: a[i], xs)
            if i:
                # ALL of the previous chunk's outputs go through the
                # barrier, not just dw: dx_c consumes the dlogits tile,
                # and leaving it outside the chain would let the
                # scheduler defer every dx matmul to the end — n_chunks
                # dlogits tiles live at once, the exact blow-up the
                # barrier exists to forbid.
                inp, dw, loss_parts[-1], dx_parts[-1] = (
                    jax.lax.optimization_barrier(
                        (inp, dw, loss_parts[-1], dx_parts[-1])))
            dw, (loss_c, dx_c) = body(dw, inp)
            loss_parts.append(loss_c)
            dx_parts.append(dx_c)
        loss_chunks = jnp.stack(loss_parts)
        dx = jnp.stack(dx_parts)
    else:
        dw, (loss_chunks, dx) = jax.lax.scan(
            body, jnp.zeros((D, V), jnp.float32), xs)
    loss = loss_chunks.sum() * inv
    dx_full = dx.reshape(T + pad, D)[:T].reshape(B, S, D)
    # residuals must be arrays only (shapes/dtypes are recovered from dx
    # in bwd; the mask was normalized to f32 at the entry point)
    return loss, (dx_full, dw.astype(lm_head.dtype))


def _ce_inline_bwd(chunk_tokens, dtype_name, res, g):
    dx, dw = res
    t_shape = dx.shape[:2]  # targets/mask are [B, S]
    # integer targets take a float0 cotangent; the mask's true gradient is
    # unused by every caller (it is a data-validity indicator) — zeros.
    return (dx * g.astype(dx.dtype), dw * g.astype(dw.dtype),
            np.zeros(t_shape, jax.dtypes.float0),
            jnp.zeros(t_shape, jnp.float32))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ce_inline(chunk_tokens, dtype_name, hidden, lm_head, targets, m):
    # primal-only call (no differentiation): plain chunked loss, zero
    # gradient work — the fwd rule below runs only under grad
    return fused_cross_entropy(hidden, lm_head, targets, m,
                               chunk_tokens=chunk_tokens,
                               compute_dtype=jnp.dtype(dtype_name))


_ce_inline.defvjp(_ce_inline_fwd, _ce_inline_bwd)
