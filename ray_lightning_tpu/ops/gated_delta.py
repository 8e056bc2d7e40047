"""The gated delta rule: the recurrence of a Gated DeltaNet linear-attention
layer (arXiv:2412.06464; the delta rule's chunked form, arXiv:2406.06484),
with its state carried in and out.

One head keeps a MATRIX ``S [d_k, d_v]``; a row ``t`` decays it, corrects it
by a rank-one term that depends on the state itself, and reads it:

    S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

``alpha_t`` in (0, 1] arrives as its logarithm, ``beta_t`` in [0, 2] (2 x a
sigmoid where the layer allows negative eigenvalues); ``q`` and ``k`` arrive
normalised and scaled (the layer does that beside its convolution).

**The state's layout** is the kernels' wherever it is kept: two heads side
by side, ``[.., H / 2, d_k, 2 d_v]`` float32. At ``d_v`` 192 one head's
``[96, 192]`` would pad its lanes to 256 (a third more bytes and a relayout
at the kernel); a pair's ``[96, 384]`` is whole tiles. `heads_to_pairs` /
`pairs_to_heads` convert.

**Rows that are not real.** ``real [B, T]`` marks the rows that advance the
recurrence. A row that does not (padding past a prompt's end; a row a
serving engine sent before and sends again) is the identity on the state,
exactly: its ``alpha`` is 1 and its ``beta`` 0, so its correction is a
product with zero. Its output is garbage to be discarded.

Three forms of one function:

  * `gated_delta_recurrence`: a `lax.scan` over rows, the tests' anchor;
  * `gated_delta_chunked`: chunks of `CHUNK` rows. Inside a chunk the
    rows' corrections solve one unit lower-triangular system (the WY form):
    with ``g`` the running sum of ``log alpha`` from the chunk's first row,

        (I + A) U = beta * (V - exp(g) * K S_0),
        A_ij = beta_i exp(g_i - g_j) (k_i . k_j)  for j < i,
        O   = exp(g) * Q S_0 + (tril(Q K^T) * exp(g_i - g_j)) U,
        S_C = exp(g_C) S_0 + (K * exp(g_C - g))^T U,

    all matrix products, the state handed from chunk to chunk. The system's
    inverse does not depend on the state, so `chunk_inverse` computes it for
    every chunk at once (forward substitution inside 16-row diagonal blocks,
    which is the recurrence itself and so as stable; the blocks joined by
    the block-inverse formula);
  * the pallas kernel `ops/pallas/gated_delta.py:rlt_delta_chunk`, which
    takes that inverse and walks a sequence's chunks with the state resident.

`gated_delta_rule` dispatches (the flash discipline, `ops/dispatch.py`): the
kernel on TPU, or forced and interpreted elsewhere, where the shapes tile;
else `gated_delta_chunked`. `gated_delta_update` is the one-row form the
decode lane runs for every slot at once, in ONE pass over the state: the
kernel `rlt_delta_step` where `gated_delta_uses_pallas` takes one row, else
plain `jax.numpy` (PERF.md section 6, PR 41, has both times).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: rows a chunk of the chunked form holds
CHUNK = 64
#: rows of a diagonal block that forward substitution inverts
_SOLVE_BLOCK = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def pair_shape(heads: int, d_k: int, d_v: int):
    """One sequence's state in the kernels' layout."""
    if heads % 2:
        raise ValueError(f"{heads} heads do not pair")
    return (heads // 2, d_k, 2 * d_v)


def heads_to_pairs(s):
    """``[.., H, d_k, d_v]`` -> ``[.., H / 2, d_k, 2 d_v]``."""
    *lead, h, dk, dv = s.shape
    s = s.reshape(*lead, h // 2, 2, dk, dv)
    return jnp.moveaxis(s, -3, -2).reshape(*lead, h // 2, dk, 2 * dv)


def pairs_to_heads(s):
    """``[.., H / 2, d_k, 2 d_v]`` -> ``[.., H, d_k, d_v]``."""
    *lead, hp, dk, dv2 = s.shape
    s = s.reshape(*lead, hp, dk, 2, dv2 // 2)
    return jnp.moveaxis(s, -2, -3).reshape(*lead, 2 * hp, dk, dv2 // 2)


def _masked(log_alpha, beta, real):
    """A row that is not real: ``alpha`` 1 and ``beta`` 0."""
    real = real[..., None]
    f32 = lambda x: x.astype(jnp.float32)
    return (jnp.where(real, f32(log_alpha), 0.0),
            jnp.where(real, f32(beta), 0.0))


def gated_delta_recurrence(q, k, v, log_alpha, beta, state, real):
    """The recurrence row by row, float32: a `lax.scan`. Shapes as
    `gated_delta_rule`."""
    f32 = lambda x: x.astype(jnp.float32)
    rows = lambda x: jnp.swapaxes(f32(x), 0, 1)            # time in front
    la, be = _masked(log_alpha, beta, real)

    def step(s, row):
        qt, kt, vt, lat, bet = row                   # [B, H, ..]
        s = s * jnp.exp(lat)[..., None, None]
        u = bet[..., None] * (vt - jnp.einsum(
            "bhkv,bhk->bhv", s, kt, precision=_HIGHEST))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=_HIGHEST)

    s, o = jax.lax.scan(step, pairs_to_heads(f32(state)),
                        (rows(q), rows(k), rows(v), rows(la), rows(be)))
    return jnp.swapaxes(o, 0, 1), heads_to_pairs(s)


# ---- the chunked form -------------------------------------------------------


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a [.., C, C]`` strictly lower triangular, C a
    power-of-two multiple of `_SOLVE_BLOCK` (or less than it)."""
    c = a.shape[-1]
    b = min(c, _SOLVE_BLOCK)
    n = c // b
    lead = a.shape[:-2]
    blocks = a.reshape(*lead, n, b, n, b)
    # the diagonal blocks, all at once: row i of the inverse is e_i less
    # the rows above it weighted by a's row i (forward substitution)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], -3)
    t = jnp.broadcast_to(jnp.eye(b, dtype=a.dtype), diag.shape)
    for i in range(1, b):
        row = -jnp.sum(diag[..., i, :, None] * t, axis=-2)
        t = t.at[..., i, :].add(row)
    # [[T11, 0], [A21, T22]]^-1 = [[T11, 0], [-T22 A21 T11, T22]], joined
    # two blocks at a time
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)
    size = b
    while n > 1:
        pairs = a.reshape(*lead, n // 2, 2, size, n // 2, 2, size)
        low = jnp.stack([pairs[..., i, 1, :, i, 0, :]
                         for i in range(n // 2)], -3)
        t11, t22 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        t21 = -mm(mm(t22, low), t11)
        top = jnp.concatenate([t11, jnp.zeros_like(t11)], -1)
        t = jnp.concatenate([top, jnp.concatenate([t21, t22], -1)], -2)
        n, size = n // 2, 2 * size
    return t[..., 0, :, :]


def chunk_rows(x, chunk: int):
    """``[B, T, H, ..]`` (T a multiple of ``chunk``) -> ``[B, H, T / chunk,
    chunk, ..]``."""
    b, t, h = x.shape[:3]
    x = x.reshape(b, t // chunk, chunk, h, *x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


def chunk_inverse(k, g, beta):
    """The WY form's triangular system a chunk, inverted: k ``[.., C,
    d_k]``, g (the running sum of ``log alpha`` inside the chunk) and beta
    ``[.., C]`` -> ``(I + A)^-1 [.., C, C]`` float32."""
    k = k.astype(jnp.float32)
    c = k.shape[-2]
    kk = jnp.einsum("...ik,...jk->...ij", k, k, precision=_HIGHEST)
    below = jnp.tril(jnp.ones((c, c), bool), -1)
    # exp of a difference that is <= 0 below the diagonal: no overflow
    decay = jnp.exp(jnp.where(below, g[..., :, None] - g[..., None, :], 0.0))
    a = jnp.where(below, beta[..., :, None] * decay * kk, 0.0)
    return _unit_lower_inverse(a)


def _pad_rows(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def gated_delta_chunked(q, k, v, log_alpha, beta, state, real,
                        chunk: int = CHUNK):
    """The chunked form in `jax.numpy`, float32: the kernel's twin. Shapes
    as `gated_delta_rule`."""
    f32 = lambda x: x.astype(jnp.float32)
    t = q.shape[1]
    chunk = min(chunk, max(_SOLVE_BLOCK, 1 << (t - 1).bit_length()))
    pad = -t % chunk
    la, be = _masked(log_alpha, beta, real)
    split = lambda x: chunk_rows(_pad_rows(f32(x), pad), chunk)
    qc, kc, vc, lac, bec = map(split, (q, k, v, la, be))
    gc = jnp.cumsum(lac, axis=-1)                        # [B, H, NC, C]
    tinv = chunk_inverse(kc, gc, bec)
    mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=_HIGHEST)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(s, args):
        qi, ki, vi, gi, bi, ti = args                    # [B, H, C, ..]
        gamma = jnp.exp(gi)[..., None]
        rhs = bi[..., None] * (vi - gamma * mm("bhck,bhkv->bhcv", ki, s))
        u = mm("bhij,bhjv->bhiv", ti, rhs)
        within = jnp.where(seen, jnp.exp(jnp.where(
            seen, gi[..., :, None] - gi[..., None, :], 0.0)) * mm(
                "bhik,bhjk->bhij", qi, ki), 0.0)
        o = gamma * mm("bhck,bhkv->bhcv", qi, s) + mm(
            "bhij,bhjv->bhiv", within, u)
        last = gi[..., -1:]
        s = jnp.exp(last)[..., None] * s + mm(
            "bhck,bhcv->bhkv", ki * jnp.exp(last - gi)[..., None], u)
        return s, o

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)
    s, o = jax.lax.scan(one, pairs_to_heads(f32(state)), tuple(
        map(chunks_first, (qc, kc, vc, gc, bec, tinv))))
    # [NC, B, H, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(o, 0, 2)
    b, h, nc, c, dv = o.shape
    o = jnp.moveaxis(o.reshape(b, h, nc * c, dv), 1, 2)
    return o[:, :t], heads_to_pairs(s)


def gated_delta_uses_pallas(rows: int, heads: int, d_k: int, d_v: int,
                            use_pallas: bool | None = None) -> bool:
    """Would `gated_delta_rule` (or, at one row, `gated_delta_update`) take
    its kernel for these shapes? The one predicate the dispatches share."""
    from ray_lightning_tpu.ops import dispatch

    if not dispatch.use_pallas(use_pallas):
        return False
    from ray_lightning_tpu.ops.pallas.gated_delta import (
        delta_shapes_supported,
    )

    return delta_shapes_supported(rows, heads, d_k, d_v)


def gated_delta_rule(q, k, v, log_alpha, beta, state, real,
                     use_pallas: bool | None = None):
    """q, k ``[B, T, H, d_k]`` (normalised, q scaled); v ``[B, T, H, d_v]``;
    log_alpha (<= 0), beta ``[B, T, H]``; state ``[B, H / 2, d_k, 2 d_v]``
    float32; real ``[B, T]`` bool. Returns (out ``[B, T, H, d_v]`` float32,
    the state after the last real row)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if not gated_delta_uses_pallas(t, h, dk, dv, use_pallas):
        return gated_delta_chunked(q, k, v, log_alpha, beta, state, real)
    from ray_lightning_tpu.ops.pallas.gated_delta import delta_chunk_pallas

    pad = -t % CHUNK
    la, be = _masked(log_alpha, beta, real)
    padded = lambda x: _pad_rows(x, pad)
    gc = jnp.cumsum(chunk_rows(padded(la), CHUNK), axis=-1)
    bec = chunk_rows(padded(be), CHUNK)
    kc = chunk_rows(padded(k), CHUNK)
    with jax.named_scope("delta_solve"):
        tinv = chunk_inverse(kc, gc, bec)
    # a pair's two heads' g and beta as rows: [B, H / 2, NC, 4, C]
    nc = gc.shape[2]
    rows4 = jnp.stack([gc, bec], 3).reshape(b, h // 2, 2, nc, 2, CHUNK)
    rows4 = jnp.moveaxis(rows4, 2, 3).reshape(b, h // 2, nc, 4, CHUNK)
    heads_first = lambda x: jnp.moveaxis(padded(x), 2, 1)  # [B, H, T, dk]
    out, state = delta_chunk_pallas(
        heads_first(q), heads_first(k),
        padded(v).reshape(b, t + pad, h * dv), rows4, tinv,
        state.astype(jnp.float32))
    return out.reshape(b, t + pad, h, dv)[:, :t], state


# ---- one row a sequence -----------------------------------------------------


def _by_lane(x, d_v):
    """A value a head ``[S, H]`` -> a value a lane of the pair layout ``[S,
    H / 2, 2 d_v]``: head ``2p`` on the first ``d_v`` lanes of pair ``p``."""
    s, h = x.shape
    return jnp.repeat(x.reshape(s, h // 2, 2), d_v, axis=-1)


def _rows_by_lane(x, d_v):
    """A row a head ``[S, H, d_k]`` -> ``[S, H / 2, d_k, 2 d_v]``: row ``k``
    of head ``2p`` on the first ``d_v`` lanes of pair ``p``."""
    s, h, dk = x.shape
    x = jnp.swapaxes(x.reshape(s, h // 2, 2, dk), -1, -2)
    return jnp.repeat(x, d_v, axis=-1)


def gated_delta_update_reference(q, k, v, log_alpha, beta, state, moves):
    """`gated_delta_update` in plain `jax.numpy`: the state is read and
    written in its own layout, only the rows change theirs."""
    f32 = lambda x: x.astype(jnp.float32)
    s, h, dv = v.shape
    la, be = _masked(log_alpha, beta, moves)
    alpha, be = _by_lane(jnp.exp(la), dv), _by_lane(be, dv)
    kl, ql = _rows_by_lane(f32(k), dv), _rows_by_lane(f32(q), dv)
    state = f32(state)
    sk = jnp.sum(state * kl, axis=-2)                    # S^T k
    sq = jnp.sum(state * ql, axis=-2)                    # S^T q
    u = be * (f32(v).reshape(s, h // 2, 2 * dv) - alpha * sk)
    qk = _by_lane(jnp.sum(f32(q) * f32(k), axis=-1), dv)
    new = alpha[..., None, :] * state + kl * u[..., None, :]
    return (alpha * sq + qk * u).reshape(s, h, dv), new


def gated_delta_update(q, k, v, log_alpha, beta, state, moves,
                       use_pallas: bool | None = None):
    """One row a sequence, every sequence at once: q, k ``[S, H, d_k]``; v
    ``[S, H, d_v]``; log_alpha, beta ``[S, H]``; state ``[S, H / 2, d_k, 2
    d_v]`` float32; moves ``[S]`` bool, False = this sequence's state stays
    as it is. Returns (out ``[S, H, d_v]`` float32, the states)."""
    s, h, dk = q.shape
    dv = v.shape[-1]
    if not gated_delta_uses_pallas(1, h, dk, dv, use_pallas):
        return gated_delta_update_reference(q, k, v, log_alpha, beta, state,
                                            moves)
    from ray_lightning_tpu.ops.pallas.gated_delta import delta_step_pallas

    f32 = lambda x: x.astype(jnp.float32)
    la, be = _masked(log_alpha, beta, moves)
    # a pair's rows: q and k of both heads [S, H / 2, 2, d_k]; v as the
    # state's lanes; alpha, beta and q . k a lane
    pair = lambda x: f32(x).reshape(s, h // 2, 2, dk)
    qk = jnp.sum(f32(q) * f32(k), axis=-1)
    lanes = jnp.stack([f32(v).reshape(s, h // 2, 2 * dv),
                       _by_lane(jnp.exp(la), dv), _by_lane(be, dv),
                       _by_lane(qk, dv)], 2)             # [S, H / 2, 4, 2 dv]
    out, state = delta_step_pallas(pair(q), pair(k), lanes, f32(state))
    return out.reshape(s, h, dv), state
