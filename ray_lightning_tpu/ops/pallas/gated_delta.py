"""The gated delta rule (`ops/gated_delta.py` has the equations) as pallas
TPU kernels: `rlt_delta_chunk`, a sequence's chunks with the state resident,
and `rlt_delta_step`, one row a slot in one pass over the state.

**Layout.** A grid step works on a PAIR of heads, because the carried state
is kept two heads side by side, ``[d_k, 2 d_v]`` float32 (at ``d_v`` 192 a
pair's 384 lanes are whole tiles, one head's 192 are not). Everything with a
``d_v`` axis is in that layout (``v`` and the output are ``[T, H d_v]`` as
the projections leave them: a pair is 2 ``d_v`` consecutive columns), and a
head of the pair is picked by a LANE MASK, never by a slice at lane 192:
each head's product is taken over all 2 ``d_v`` lanes and the half that is
its own is kept. The matrix unit does twice the work on those products and
no relayout exists anywhere. ``q`` and ``k`` come head-major, ``[H, T,
d_k]``, so that a head of the pair is an index on a leading dimension.

**`rlt_delta_chunk`**: grid = (sequences, pairs, chunks), the chunks
innermost and sequential: the state's output block keeps its index across
them, stays in VMEM and is the carry; the first chunk copies the state in.
A chunk of C = 64 rows, a head (``g`` the running sum of ``log alpha`` in
the chunk, ``T = (I + A)^-1`` the WY form's triangular system inverted,
which `ops.gated_delta.chunk_inverse` makes for all chunks at once: it does
not depend on the state):

    U   = T (beta * (V - exp(g) * K S))            [C, C] x [C, 2 d_v]
    O   = exp(g) * Q S + (tril(Q K^T) * exp(g_i - g_j)) U
    S'  = exp(g_C) S + (K * exp(g_C - g))^T U

float32 state, accumulation and ``T U``; the other products take operands in
the rows' type (bfloat16 when served). ``g`` and ``beta`` arrive as ROWS
``[4, C]`` a pair (a column ``[C, 1]`` in HBM is a tile a row of eight); the
kernel makes the columns it needs from the diagonal of a broadcast.

A row with ``alpha`` 1 and ``beta`` 0 is the identity on the state, exactly:
its row of ``U`` is zero. Its output is garbage and the caller discards it.

**`rlt_delta_step`**: grid = (slots, pair blocks). One row a slot: ``S^T k``
and ``S^T q`` from one read of the state (one product ``[8, d_k] x [d_k, 2
d_v]`` with the pair's four rows), the rank-one correction from one more
(``[8, d_k]^T x [8, 2 d_v]``), the state written once, aliased onto its
input.

Inference only (no VJP). The `jax.numpy` twins are `ops.gated_delta.
gated_delta_chunked` and `gated_delta_update_reference`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.dispatch import interpret_mode as _interpret
from ray_lightning_tpu.ops.gated_delta import CHUNK

LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
#: pairs of heads one grid step of `rlt_delta_step` walks
_STEP_PAIRS = 5


def delta_shapes_supported(rows: int, heads: int, d_k: int, d_v: int) -> bool:
    """Would the kernels take these shapes (`rlt_delta_step`: one row)? On
    a TPU a pair's state must be whole tiles; interpreted elsewhere, any
    even number of heads (the tests' tiny widths walk the same kernels)."""
    if rows < 1 or heads % 2:
        return False
    return _interpret() or (d_k % 8 == 0 and (2 * d_v) % LANES == 0)


def _nt(x, y, **kw):
    """``x y^T``."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32, **kw)


def _tn(x, y, **kw):
    """``x^T y``."""
    return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32, **kw)


def _dot(x, y, **kw):
    return jnp.dot(x, y, preferred_element_type=jnp.float32, **kw)


def _chunk_kernel(q_ref, k_ref, v_ref, rows_ref, t_ref, s0_ref, o_ref, s_ref,
                  *, d_v):
    """One (sequence, pair, chunk)."""
    @pl.when(pl.program_id(2) == 0)
    def _state_in():
        s_ref[...] = s0_ref[...]

    f32 = jnp.float32
    mx = q_ref.dtype
    exact = dict(precision=_HIGHEST) if mx == f32 else {}
    s = s_ref[0, 0]                                      # [d_k, 2 d_v]
    c = v_ref.shape[1]
    first = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * d_v), 1) < d_v
    first_s = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < d_v
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # a row [1, C] as a column [C, 1]: the diagonal of its broadcast
    column = lambda row: jnp.sum(jnp.where(ii == jj, row, 0.0), axis=1,
                                 keepdims=True)
    v2 = v_ref[0].astype(f32)
    s_mx = s.astype(mx)
    rows = rows_ref[0, 0, 0]                             # [4, C]
    out = new = None
    for h in range(2):
        qh, kh = q_ref[0, h], k_ref[0, h]                # [C, d_k]
        g_row, b_row = rows[2 * h:2 * h + 1], rows[2 * h + 1:2 * h + 2]
        g_col, b_col = column(g_row), column(b_row)
        g_last = g_col[c - 1:c]                          # [1, 1]
        gamma = jnp.exp(g_col)
        rhs = b_col * (v2 - gamma * _dot(kh, s_mx, **exact))
        u = _dot(t_ref[0, h, 0], rhs, precision=_HIGHEST)
        u_mx = u.astype(mx)
        seen = ii >= jj
        within = jnp.where(seen, jnp.exp(jnp.where(
            seen, g_col - g_row, 0.0)) * _nt(qh, kh, **exact), 0.0)
        oh = gamma * _dot(qh, s_mx, **exact) + _dot(
            within.astype(mx), u_mx, **exact)
        kd = (kh.astype(f32) * jnp.exp(g_last - g_col)).astype(mx)
        sh = jnp.exp(g_last) * s + _tn(kd, u_mx, **exact)
        if h == 0:
            out, new = oh, sh
        else:
            out, new = jnp.where(first, out, oh), jnp.where(first_s, new, sh)
    o_ref[0] = out.astype(o_ref.dtype)
    s_ref[0, 0] = new


def delta_chunk_pallas(q, k, v, rows, tinv, state):
    """q, k ``[B, H, T, d_k]``; v ``[B, T, H d_v]``; rows ``[B, H / 2, T /
    C, 4, C]`` float32 (a pair's ``g``, ``beta`` of its first head, then of
    its second); tinv ``[B, H, T / C, C, C]`` float32; state ``[B, H / 2,
    d_k, 2 d_v]`` float32. T a multiple of C = `CHUNK`. Returns (out ``[B,
    T, H d_v]`` float32, the state after the last row)."""
    b, h, t, dk = q.shape
    dv = v.shape[-1] // h
    c = CHUNK
    if t % c:
        raise ValueError(f"{t} rows are no whole number of chunks of {c}")
    grid = (b, h // 2, t // c)
    heads = pl.BlockSpec((1, 2, c, dk), lambda bi, pi, ci: (bi, pi, ci, 0))
    wide = pl.BlockSpec((1, c, 2 * dv), lambda bi, pi, ci: (bi, ci, pi))
    held = pl.BlockSpec((1, 1, dk, 2 * dv), lambda bi, pi, ci: (bi, pi, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, d_v=dv),
        grid=grid,
        in_specs=[heads, heads, wide,
                  pl.BlockSpec((1, 1, 1, 4, c),
                               lambda bi, pi, ci: (bi, pi, ci, 0, 0)),
                  pl.BlockSpec((1, 2, 1, c, c),
                               lambda bi, pi, ci: (bi, pi, ci, 0, 0)),
                  held],
        out_specs=[wide, held],
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="rlt_delta_chunk",
        interpret=_interpret(),
    )(q, k, v, rows, tinv, state)


def _step_kernel(qk_ref, lanes_ref, s_ref, o_ref, new_ref, *, d_v, pairs):
    """One (slot, block of pairs): a row of every pair in the block."""
    first = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * d_v), 1) < d_v
    for p in range(pairs):
        s = s_ref[0, p]                                  # [d_k, 2 d_v]
        qk8 = qk_ref[0, p]           # [8, d_k]: q, q', k, k', four of zeros
        r = _dot(qk8, s, precision=_HIGHEST)             # [8, 2 d_v]
        sq = jnp.where(first, r[0:1], r[1:2])
        sk = jnp.where(first, r[2:3], r[3:4])
        lanes = lanes_ref[0, p]               # v, alpha, beta, q . k
        v, alpha, beta, qk = (lanes[i:i + 1] for i in range(4))
        u = beta * (v - alpha * sk)
        o_ref[0, p] = alpha * sq + qk * u
        zero = jnp.zeros_like(u)
        # the rows of u against the rows of k in qk8: zeros meet q
        u8 = jnp.concatenate(
            [zero, zero, jnp.where(first, u, 0.0), jnp.where(first, 0.0, u),
             zero, zero, zero, zero], axis=0)
        new_ref[0, p] = alpha * s + _tn(qk8, u8, precision=_HIGHEST)


def delta_step_pallas(q, k, lanes, state):
    """q, k ``[S, H / 2, 2, d_k]`` float32 (a pair's two heads); lanes ``[S,
    H / 2, 4, 2 d_v]`` float32 (v, alpha, beta and ``q . k`` in the state's
    lanes); state ``[S, H / 2, d_k, 2 d_v]`` float32. Returns (out ``[S, H /
    2, 2 d_v]``, the states)."""
    s, hp, _, dk = q.shape
    dv2 = state.shape[-1]
    pb = _STEP_PAIRS if hp % _STEP_PAIRS == 0 else 1
    qk8 = jnp.concatenate([q, k, jnp.zeros((s, hp, 4, dk), jnp.float32)], 2)
    block = lambda *tail: pl.BlockSpec((1, pb, *tail),
                                       lambda si, pi: (si, pi, 0, 0))
    out, new = pl.pallas_call(
        functools.partial(_step_kernel, d_v=dv2 // 2, pairs=pb),
        grid=(s, hp // pb),
        in_specs=[block(8, dk), block(4, dv2), block(dk, dv2)],
        out_specs=[block(1, dv2), block(dk, dv2)],
        out_shape=[jax.ShapeDtypeStruct((s, hp, 1, dv2), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="rlt_delta_step",
        interpret=_interpret(),
    )(qk8, lanes, state)
    return out[:, :, 0], new
