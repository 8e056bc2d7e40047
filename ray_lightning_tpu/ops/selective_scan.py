"""Selective scan: the recurrence of a Mamba-1 state-space mixer, with its
state carried in and out, and the causal convolution in front of it with
its tail carried likewise.

    delta_t = softplus(dt_t + dt_bias) * real_t
    h_t     = exp(delta_t A) * h_{t-1} + (delta_t B_t) x_t
    out_t   = (sum_n h_t[n] C_t[n] + D x_t) * silu(z_t)

One sequence is ``T`` rows of ``E`` channels; the state ``h`` is ``[N, E]``
float32. **The state's layout** is the kernel's wherever it is kept:
``[.., N, E / 128, 128]``, the channels split into lanes, so that a serving
engine's carried leaf goes into the kernel and comes back without a
relayout (`ops/pallas/selective_scan.py` says why that layout). `lane_split`
/ `lane_join` convert a channel axis.

**Rows that are not real.** ``real`` [S, T] marks the rows that advance
the recurrence. A row that does not (padding past a prompt's end; a row a
serving engine sent before and sends again) is the identity on the state,
exactly (its ``delta`` is 0), and its output is garbage to be discarded. A
recurrence is not idempotent the way a K/V write is: this mask is what lets
a fixed-width chunk hold fewer real rows than its width.

`selective_scan` dispatches (the flash discipline, `ops/dispatch.py`): the
pallas kernel on TPU, or forced and interpreted elsewhere, where the
channels tile; else `selective_scan_reference`, a `lax.scan` over rows with
the same semantics. `selective_update` is the one-row form the decode lane
runs for every slot at once: plain `jax.numpy`, because one row a slot is
bound by reading and writing the state once, which XLA's fusion already
does at the memory's speed (PERF.md section 6, PR 35).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def lane_split(x):
    """``[.., E]`` -> ``[.., E / 128, 128]``."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // LANES, LANES)


def lane_join(x):
    """``[.., Es, 128]`` -> ``[.., Es * 128]``."""
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


def state_shape(n_state: int, channels: int):
    """One sequence's state in the kernel's layout."""
    if channels % LANES:
        raise ValueError(f"{channels} channels do not split into lanes of "
                         f"{LANES}")
    return (n_state, channels // LANES, LANES)


def _advance(h, xt, dtt, bt, ct, rt, a, d, dt_bias):
    """One row of every sequence, float32, the channels in the state's own
    layout (so that a state never changes layout on its way through): h
    [S, N, Es, 128]; xt, dtt [S, Es, 128]; bt, ct [S, N]; rt [S]; a [N, Es,
    128]; d, dt_bias [Es, 128] -> (h', y [S, Es, 128])."""
    per_state = lambda v: v[:, :, None, None]
    delta = jax.nn.softplus(dtt + dt_bias) * rt[:, None, None]
    h = (jnp.exp(delta[:, None] * a) * h
         + (delta * xt)[:, None] * per_state(bt))
    return h, jnp.sum(h * per_state(ct), axis=1) + d * xt


def _gate(y, z):
    return y * (z * jax.nn.sigmoid(z))


def _f32_split(v):
    return lane_split(v.astype(jnp.float32))


def selective_scan_reference(x, dt, z, b, c, a, d, dt_bias, state, real):
    """The `jax.numpy` twin of the kernel: a `lax.scan` over rows. Shapes
    as `selective_scan`."""
    f32 = lambda v: v.astype(jnp.float32)
    rows = lambda v: jnp.swapaxes(v, 0, 1)            # time in front
    consts = (_f32_split(a), _f32_split(d), _f32_split(dt_bias))

    def step(h, row):
        return _advance(h, *row, *consts)

    h, y = jax.lax.scan(step, f32(state), (
        rows(_f32_split(x)), rows(_f32_split(dt)), rows(f32(b)),
        rows(f32(c)), rows(f32(real))))
    return _gate(lane_join(rows(y)), f32(z)), h


def selective_scan_uses_pallas(rows: int, channels: int, n_state: int,
                               use_pallas: bool | None = None) -> bool:
    """Would `selective_scan` take the kernel for these shapes? The one
    predicate the dispatch and a decoder's lane decision share."""
    from ray_lightning_tpu.ops import dispatch

    if not dispatch.use_pallas(use_pallas):
        return False
    from ray_lightning_tpu.ops.pallas.selective_scan import (
        scan_shapes_supported,
    )

    return scan_shapes_supported(rows, channels, n_state)


def selective_scan(x, dt, z, b, c, a, d, dt_bias, state, real,
                   use_pallas: bool | None = None):
    """x, dt, z ``[S, T, E]``; b, c ``[S, T, N]``; a ``[N, E]`` (negative:
    ``-exp(A_log)``, state-major); d, dt_bias ``[E]``; state ``[S, N, E /
    128, 128]`` float32; real ``[S, T]`` bool. Returns (out ``[S, T, E]``
    float32, the state after the last real row)."""
    s, t, e = x.shape
    n = b.shape[-1]
    if not selective_scan_uses_pallas(t, e, n, use_pallas):
        return selective_scan_reference(x, dt, z, b, c, a, d, dt_bias,
                                        state, real)
    from ray_lightning_tpu.ops.pallas.selective_scan import (
        row_block, selective_scan_pallas,
    )

    f32 = lambda v: v.astype(jnp.float32)
    pad = -t % row_block(t)
    rows = lambda v: jnp.pad(f32(v), ((0, 0), (0, pad), (0, 0)))
    out, state = selective_scan_pallas(
        lane_split(rows(x)), lane_split(rows(dt)), lane_split(rows(z)),
        rows(b), rows(c), lane_split(f32(a)), lane_split(f32(d)),
        lane_split(f32(dt_bias)), f32(state),
        jnp.pad(real.astype(jnp.int32), ((0, 0), (0, pad))))
    return lane_join(out)[:, :t], state


def selective_update(x, dt, z, b, c, a, d, dt_bias, state, moves):
    """One row a sequence, every sequence at once: x, dt, z ``[S, E]``; b,
    c ``[S, N]``; state ``[S, N, E / 128, 128]`` float32; moves ``[S]``
    bool, False = this sequence's state stays as it is. A one-row
    `selective_scan` without the loop; the state is read and written in
    its own layout, only the rows change theirs."""
    f32 = lambda v: v.astype(jnp.float32)
    h, y = _advance(f32(state), _f32_split(x), _f32_split(dt), f32(b),
                    f32(c), f32(moves), _f32_split(a), _f32_split(d),
                    _f32_split(dt_bias))
    return _gate(lane_join(y), f32(z)), h


# ---- the causal convolution in front of the scan ---------------------------


def causal_conv(x, tail, weight, bias, first, last):
    """Depthwise causal convolution of one sequence's chunk with the tail
    of what came before. x ``[T, E]``; tail ``[K - 1, E]``: the K - 1 inputs
    before the chunk's first REAL row; weight ``[K, E]`` (tap K - 1 on the
    row itself); bias ``[E]``; ``first`` / ``last``: the chunk's real rows
    (``last = first - 1``: none). Returns (``[T, E]``, the K - 1 inputs up
    to and including row ``last``). Rows before ``first`` read garbage, as
    the scan's mask expects of them; row ``first`` reads the tail and never
    the rows in front of it, which a slid-back chunk sends a second time."""
    k = weight.shape[0]
    t = x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    # xp[j + k - 1] = x[j]: the tail sits where rows first - k + 1 .. first
    # - 1 would
    xp = jax.lax.dynamic_update_slice_in_dim(xp, tail.astype(x.dtype), first,
                                             axis=0)
    y = bias.astype(jnp.float32)
    for j in range(k):
        y = y + (xp[j:j + t].astype(jnp.float32)
                 * weight[j].astype(jnp.float32))
    new_tail = jax.lax.dynamic_slice_in_dim(xp, last + 1, k - 1, axis=0)
    return y, new_tail.astype(tail.dtype)


def causal_conv_update(x, tail, weight, bias):
    """One row a sequence: x ``[S, E]``, tail ``[S, K - 1, E / 128, 128]``
    (a carried leaf's rows, in their own layout) -> (``[S, E]`` float32,
    the tail moved on by the row, in the tail's layout and type)."""
    xp = jnp.concatenate([tail, lane_split(x.astype(tail.dtype))[:, None]],
                         1)                                # [S, K, Es, 128]
    y = jnp.sum(xp.astype(jnp.float32) * _f32_split(weight)[None], 1)
    return lane_join(y) + bias.astype(jnp.float32), xp[:, 1:]
