"""Flash attention (forward + backward) as pallas TPU kernels.

Online-softmax tiling (Flash-Attention-2 schedule): the S×S score matrix is
never materialized in HBM; each grid step streams one KV tile through VMEM
against a resident Q tile, keeping running (max, sum, acc) statistics in
f32 scratch. Causal blocks that are fully masked are skipped (predicated
body). Backward recomputes P from the saved logsumexp, in two passes:
one gridded over KV tiles (dK, dV) and one over Q tiles (dQ) — no atomics,
which TPUs don't have.

Layout: kernels work on [B, H, S, D]; the public wrapper takes the
framework-standard [B, S, H, D] and transposes (XLA folds the transpose
into neighboring ops). GQA is handled by an index_map trick: KV tiles are
indexed with h // n_rep, so KV heads are read in place — no repeat, no
extra HBM traffic.

Sliding window (``window``, static): a query at ``t`` sees the keys in
``(t - window, t]``. The grid's inner axis then runs over the blocks a
band can reach and no further, counted from the band's first block
(`_band_first`), so the blocks wholly behind the band are neither fetched
nor visited; the blocks on the band's two edges are masked. With
``window=None`` the kernels lower as they did before the window existed.

Tiling constraints: block sizes start from the tuned defaults (512 Q /
1024 KV) and halve until they divide S (`_fit_block`), so any S that is
a multiple of a small power of two tiles; D should be a multiple of 128
(MXU lane width) — callers check `shapes_supported` and fall back to the
XLA path otherwise.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tuned on v5e (B=4, S=2048, H=16, D=128, fwd+bwd sweep 2026-07): larger
# KV tiles amortize the HBM streaming against the resident Q tile;
# (512, 1024) ran 1.49x faster than (256, 256), and 2048-wide tiles blow
# the VMEM budget. Still clamped to S when S is smaller.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30  # avoid true -inf: exp(-inf - -inf) = nan on fully-masked rows


from ray_lightning_tpu.ops.dispatch import interpret_mode as _interpret


def _fit_block(block: int, s: int) -> int:
    """Largest block <= `block` that divides s (halving search)."""
    b = min(block, s)
    while b > 8 and s % b != 0:
        b //= 2
    return b


def _band_first(outer, block_outer, block_inner, shift):
    """First inner block that the band of outer block ``outer`` reaches:
    the block that holds position ``outer * block_outer + shift``, or
    block 0 where that lies before the sequence's start."""
    return jnp.maximum(outer * block_outer + shift, 0) // block_inner


def _band_blocks(block_outer: int, block_inner: int, window: int,
                 n_inner: int) -> int:
    """Inner blocks a band can reach from one outer block: the
    ``block_outer + window - 1`` positions of its span, wherever the span
    starts in an inner block, and never more than there are."""
    return min(n_inner, (block_outer + window - 2) // block_inner + 2)


def _in_band(run, q_start, kv_start, block_k: int, window: int):
    """``run`` and: the KV block's last key is inside the window of the Q
    block's first row (else the whole block lies behind the band)."""
    return run & (kv_start + block_k - 1 > q_start - window)


def shapes_supported(q_shape, k_shape) -> bool:
    """[B, S, H, D]: blocks must tile S; D must be lane-aligned."""
    b, sq, hq, d = q_shape
    _, sk, hk, _ = k_shape
    if d % 128 != 0 and d not in (64,):  # 64 still tiles acceptably
        return False
    if hq % hk != 0:
        return False
    if sq % 8 != 0 or sk % 8 != 0:  # sublane alignment
        return False
    # blocks below 128 starve the MXU (8-wide tiles on S=8*odd would
    # "fit" but run far slower than the fused XLA path) — fall back.
    bq, bk = _fit_block(DEFAULT_BLOCK_Q, sq), _fit_block(DEFAULT_BLOCK_K, sk)
    return (sq % bq == 0 and bq >= min(sq, 128)
            and sk % bk == 0 and bk >= min(sk, 128))


# ----------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                *, scale, causal, q_offset, block_q, block_k, num_kv_blocks,
                window=None, kv_blocks=None):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block (innermost: scratch persists across it)
    step = j              # the inner axis's step; under a window j moves on
    if window is not None:
        j = step + _band_first(i, block_q, block_k, q_offset - window + 1)

    @pl.when(step == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # causal skip: block fully masked iff smallest q pos < smallest kv pos
    q_start = i * block_q + q_offset
    kv_start = j * block_k
    run = (not causal) or (q_start + block_q - 1 >= kv_start)
    if window is not None:
        run = _in_band(run, q_start, kv_start, block_k, window) & (
            j < kv_blocks)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            visible = q_pos >= kv_pos
            if window is not None:
                visible = visible & (q_pos - kv_pos < window)
            s = jnp.where(visible, s, _NEG_INF)
        m_prev = m_scr[:, 0]  # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = corr * l_scr[:, 0] + jnp.sum(p, axis=1)
        acc[:] = corr[:, None] * acc[:] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0] = m_new

    @pl.when(step == num_kv_blocks - 1)
    def _finish():
        l = l_scr[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / safe_l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :, 0] = m_scr[:, 0] + jnp.log(safe_l)


def _kv_specs(bq, bk, d, n_rep, nk, q_offset, window):
    """The K and V block specs of a grid (b, h, q block, kv step). Under a
    window the step counts from the band's first block, and a step past the
    sequence's end re-reads the last block (no fetch; the kernel skips it)."""
    if window is None:
        at = lambda b_, h_, i, j, n_rep=n_rep: (b_, h_ // n_rep, j, 0)
    else:
        def at(b_, h_, i, j):
            first = _band_first(i, bq, bk, q_offset - window + 1)
            return (b_, h_ // n_rep, jnp.minimum(first + j, nk - 1), 0)
    return [pl.BlockSpec((1, 1, bk, d), at), pl.BlockSpec((1, 1, bk, d), at)]


def _fwd(q, k, v, scale, causal, q_offset, block_q, block_k, window=None):
    """q,k,v: [B, H, S, D] (kv may have fewer heads). Returns (o, lse)."""
    b, h, sq, d = q.shape
    hk = k.shape[1]
    n_rep = h // hk
    bq = _fit_block(block_q, sq)
    bk = _fit_block(block_k, k.shape[2])
    nq, nk = sq // bq, k.shape[2] // bk
    steps = nk if window is None else _band_blocks(bq, bk, window, nk)
    grid = (b, h, nq, steps)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, q_offset=q_offset,
        block_q=bq, block_k=bk, num_kv_blocks=steps, window=window,
        kv_blocks=nk,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            *_kv_specs(bq, bk, d, n_rep, nk, q_offset, window),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="rlt_flash_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, q_offset, block_q, block_k,
                    num_q_blocks, window=None, q_blocks=None):
    j = pl.program_id(2)  # kv block (outer)
    i = pl.program_id(3)  # q block (inner: accumulators persist)
    step = i
    if window is not None:    # the first Q block that sees this KV block
        i = step + _band_first(j, block_k, block_q, -q_offset)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = i * block_q + q_offset
    kv_start = j * block_k
    run = (not causal) or (q_start + block_q - 1 >= kv_start)
    if window is not None:
        run = _in_band(run, q_start, kv_start, block_k, window) & (
            i < q_blocks)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]      # [bq]
        delta = delta_ref[0, 0, :, 0]  # [bq] = rowsum(dO * O)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            visible = q_pos >= kv_pos
            if window is not None:
                visible = visible & (q_pos - kv_pos < window)
            s = jnp.where(visible, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])  # [bq, bk]
        # dV += P^T dO
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - delta[:, None]) * scale
        # dK += dS^T Q
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc,
                   *, scale, causal, q_offset, block_q, block_k,
                   num_kv_blocks, window=None, kv_blocks=None):
    i = pl.program_id(2)  # q block (outer)
    j = pl.program_id(3)  # kv block (inner)
    step = j
    if window is not None:
        j = step + _band_first(i, block_q, block_k, q_offset - window + 1)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = i * block_q + q_offset
    kv_start = j * block_k
    run = (not causal) or (q_start + block_q - 1 >= kv_start)
    if window is not None:
        run = _in_band(run, q_start, kv_start, block_k, window) & (
            j < kv_blocks)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            visible = q_pos >= kv_pos
            if window is not None:
                visible = visible & (q_pos - kv_pos < window)
            s = jnp.where(visible, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(step == num_kv_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(scale, causal, q_offset, block_q, block_k, res, do, window=None):
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    hk = k.shape[1]
    n_rep = h // hk
    sk = k.shape[2]
    bq = _fit_block(block_q, sq)
    bk = _fit_block(block_k, sk)
    nq, nk = sq // bq, sk // bk

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B,H,Sq,1]

    # pass 1: dK, dV — grid over kv blocks, accumulate over q blocks.
    # GQA: compute per-Q-head dk/dv at [B, H, Sk, D], then segment-sum the
    # rep groups down to [B, Hk, Sk, D] outside the kernel (one reshape-sum).
    if window is None:
        q_steps, kv_steps = nq, nk
        q_at = lambda b_, h_, j, i: (b_, h_, i, 0)
    else:
        q_steps = _band_blocks(bk, bq, window, nq)
        kv_steps = _band_blocks(bq, bk, window, nk)

        def q_at(b_, h_, j, i):
            first = _band_first(j, bk, bq, -q_offset)
            return (b_, h_, jnp.minimum(first + i, nq - 1), 0)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, q_offset=q_offset,
        block_q=bq, block_k=bk, num_q_blocks=q_steps, window=window,
        q_blocks=nq,
    )
    dk_full, dv_full = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, nk, q_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_at),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, j, i, n_rep=n_rep: (b_, h_ // n_rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, j, i, n_rep=n_rep: (b_, h_ // n_rep, j, 0)),
            pl.BlockSpec((1, 1, bq, d), q_at),
            pl.BlockSpec((1, 1, bq, 1), q_at),
            pl.BlockSpec((1, 1, bq, 1), q_at),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name="rlt_flash_bwd_dkdv",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    if n_rep > 1:
        dk = dk_full.reshape(b, hk, n_rep, sk, d).sum(axis=2)
        dv = dv_full.reshape(b, hk, n_rep, sk, d).sum(axis=2)
    else:
        dk, dv = dk_full, dv_full

    # pass 2: dQ — grid over q blocks, accumulate over kv blocks.
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, q_offset=q_offset,
        block_q=bq, block_k=bk, num_kv_blocks=kv_steps, window=window,
        kv_blocks=nk,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            *_kv_specs(bq, bk, d, n_rep, nk, q_offset, window),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="rlt_flash_bwd_dq",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhsd(q, k, v, scale, causal, q_offset, block_q, block_k,
                window=None):
    o, _ = _fwd(q, k, v, scale, causal, q_offset, block_q, block_k, window)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, q_offset, block_q, block_k,
                    window=None):
    o, lse = _fwd(q, k, v, scale, causal, q_offset, block_q, block_k,
                  window)
    # Label the VJP residuals for jaxpr readability. NOTE these names
    # alone cannot make a remat policy save the residuals — a custom_vjp
    # fwd rule is not part of the primal trace, so a named-saveable
    # policy sees nothing (verified in tests/test_ops.py). The working
    # mechanism for remat_policy="attn_out" is optimize_remat=True below,
    # which hoists this rule into a `remat_opt` call whose outputs the
    # policy saves (models/llama.py _attn_residuals_saveable).
    from jax.ad_checkpoint import checkpoint_name

    res = tuple(checkpoint_name(t, "flash_residuals")
                for t in (q, k, v, o, lse))
    return o, res


def _flash_bwd_rule(scale, causal, q_offset, block_q, block_k, window, res,
                    do):
    return _bwd(scale, causal, q_offset, block_q, block_k, res, do, window)


# optimize_remat: without it a custom_vjp is OPAQUE to remat policies —
# the residuals live only in the fwd rule, which is not part of the
# primal trace, so save_only_these_names("flash_residuals") had nothing
# to save and the kernel forward re-ran in every remat backward (counted
# via pallas_call occurrences in the jaxpr, tests/test_ops.py). With it,
# JAX rewrites the call so the fwd rule's residual outputs are visible
# to the surrounding checkpoint and the policy decides their fate.
_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule, optimize_remat=True)


def _env_block(name: str, default: int) -> int:
    """Tuning-knob env parse: a malformed value falls back to the tuned
    default with a warning instead of failing the whole training step at
    trace time (same policy as the bench watchdog's env parse)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        value = int(raw)
        if value <= 0:
            # 0 would divide-by-zero in the grid math, a negative value
            # would yield a negative block — both kill the step at trace
            # time, the exact failure this fallback exists to prevent
            raise ValueError(raw)
    except ValueError:
        import warnings

        warnings.warn(
            f"ignoring malformed {name}={raw!r}; using {default}",
            stacklevel=2,
        )
        return default
    return value


def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    window: int | None = None,
) -> jnp.ndarray:
    """Flash attention on [B, S, H, D] tensors (framework layout).

    ``window`` (static): a query at ``t`` sees the keys in ``(t - window,
    t]``; it needs ``causal``. None is no window.

    ``block_q``/``block_k`` default to the tuned module constants,
    overridable per-process via ``RLT_FLASH_BLOCK_Q``/``RLT_FLASH_BLOCK_K``
    (read at trace time — the sweep harness's tuning knob)."""
    if block_q is None:
        block_q = _env_block("RLT_FLASH_BLOCK_Q", DEFAULT_BLOCK_Q)
    if block_k is None:
        block_k = _env_block("RLT_FLASH_BLOCK_K", DEFAULT_BLOCK_K)
    if window is not None and (not causal or window < 1):
        raise ValueError("a sliding window is a band under the diagonal: it "
                         f"needs causal=True and window >= 1, got {window}")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    qt = q.transpose(0, 2, 1, 3)  # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash_bhsd(qt, kt, vt, scale, causal, q_offset, block_q, block_k,
                    window)
    return o.transpose(0, 2, 1, 3)
