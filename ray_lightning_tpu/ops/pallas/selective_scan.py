"""Selective scan (the Mamba-1 recurrence) as a pallas TPU kernel.

    h_t = exp(delta_t A) * h_{t-1} + (delta_t B_t) x_t        h [N, E] float32
    y_t = sum_n h_t[n] C_t[n] + D x_t
    out_t = y_t * silu(z_t)
    delta_t = softplus(dt_t + dt_bias) * real_t

``A`` is diagonal a (state, channel) pair and ``delta`` is a (row, channel)
pair, so the recurrence has no matrix form: as XLA an associative scan
materialises ``[rows, E, N]`` float32 several times over (335 MB a layer a
1,024-row chunk at E 5120, N 16). This kernel walks time inside VMEM and
keeps nothing of that size anywhere.

Layout: channels split into lanes, ``E = Es x 128``, and every operand that
has a channel axis carries it as its last two dimensions ``[.., Es, 128]``,
so that one row of one channel tile (8 x 128 = 1,024 channels) is ONE vector
register and indexing a row is address arithmetic on an untiled leading
dimension. The state is ``[N, Es, 128]``: one register a state index. ``B_t``
and ``C_t`` (``[rows, N]``, shared by all channels) are SCALARS to a channel
tile, read from SMEM and splatted; with channels on lanes and states on
sublanes instead they would be a lane-broadcast of a column a row.

grid = (sequences, channel tiles, row blocks), the row blocks innermost and
sequential: the state's output block keeps its index across them, stays in
VMEM and is the carry; the first row block copies the state in. Per row and
state index: one exp, five multiplies and two adds on one register; sixteen
independent chains a row give the scheduler its parallelism.

A row with ``real == 0`` is the identity on the state, exactly: its
``delta`` is 0, so ``exp(0) h + 0 = h``. Its output is garbage and the
caller discards it.

Inference only (no VJP). The `jax.numpy` twin with the same semantics is
`ops.selective_scan.selective_scan_reference`; dispatch follows the flash
discipline (`ops.selective_scan.selective_scan_uses_pallas`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.dispatch import interpret_mode as _interpret

LANES = 128
#: sublanes of a channel tile: 8 x 128 channels, one float32 register a row
_TILE_SUBLANES = 8
#: rows a grid step walks (a block of x, dt, z and y is rows x 4 KiB)
_ROW_BLOCK = 256


def scan_shapes_supported(rows: int, channels: int, n_state: int) -> bool:
    """Would the kernel take these shapes? On a TPU the channels must split
    into whole tiles of 8 x 128 (one register a row); interpreted elsewhere,
    into lanes (the tests' tiny widths walk the same kernel)."""
    whole = LANES if _interpret() else _TILE_SUBLANES * LANES
    return rows >= 1 and n_state >= 1 and channels % whole == 0


def row_block(rows: int) -> int:
    """Rows a grid step walks: `_ROW_BLOCK`, or all of fewer. The caller
    pads the rows to a multiple with rows that are not real."""
    return min(rows, _ROW_BLOCK)


def _softplus(v):
    # log(1 + e^v) without overflow, from exp and log alone
    return jnp.maximum(v, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(v)))


def _scan_kernel(b_ref, c_ref, real_ref, x_ref, dt_ref, z_ref, a_ref, d_ref,
                 bias_ref, h0_ref, y_ref, h_ref, *, n_state, rows):
    """One (sequence, channel tile, row block): ``rows`` rows walked in
    order, the state a tuple of ``n_state`` registers carried through the
    loop and kept in ``h_ref`` between row blocks."""
    @pl.when(pl.program_id(2) == 0)
    def _state_in():
        h_ref[...] = h0_ref[...]

    d, bias = d_ref[...], bias_ref[...]

    def row(t, h):
        xt = x_ref[0, t]
        dt = _softplus(dt_ref[0, t] + bias) * real_ref[0, 0, t].astype(
            jnp.float32)
        dx = dt * xt
        y = d * xt
        out = []
        for n in range(n_state):
            hn = jnp.exp(dt * a_ref[n]) * h[n] + dx * b_ref[
                0, 0, t * n_state + n]
            y = y + hn * c_ref[0, 0, t * n_state + n]
            out.append(hn)
        zt = z_ref[0, t]
        y_ref[0, t] = y * zt / (1.0 + jnp.exp(-zt))
        return tuple(out)

    h = jax.lax.fori_loop(
        0, rows, row, tuple(h_ref[0, n] for n in range(n_state)))
    for n in range(n_state):
        h_ref[0, n] = h[n]


def selective_scan_pallas(x, dt, z, b, c, a, d, dt_bias, state, real):
    """x, dt, z ``[S, T, Es, 128]`` float32; b, c ``[S, T, N]`` float32; a
    ``[N, Es, 128]``, d, dt_bias ``[Es, 128]`` float32; state ``[S, N, Es,
    128]`` float32; real ``[S, T]`` int32. T a multiple of `row_block(T)`.
    Returns (out ``[S, T, Es, 128]`` float32, the state after the last
    row)."""
    s, t, es, _ = x.shape
    n = b.shape[-1]
    tb = row_block(t)
    ts = _TILE_SUBLANES if es % _TILE_SUBLANES == 0 else es
    if t % tb:
        raise ValueError(f"{t} rows are no whole number of blocks of {tb}")
    grid = (s, es // ts, t // tb)
    rows = pl.BlockSpec((1, tb, ts, LANES), lambda si, ei, ti: (si, ti, ei, 0))
    # a row block's scalars: one row of [S * blocks, 1, width], so that the
    # block's last two dimensions are the array's own
    nt = t // tb
    scalars = lambda width: pl.BlockSpec(
        (1, 1, width), lambda si, ei, ti: (si * nt + ti, 0, 0),
        memory_space=pltpu.SMEM)
    blocks = lambda v: v.reshape(s * nt, 1, -1)
    chan = pl.BlockSpec((ts, LANES), lambda si, ei, ti: (ei, 0))
    held = pl.BlockSpec((1, n, ts, LANES), lambda si, ei, ti: (si, 0, ei, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n, rows=tb),
        grid=grid,
        in_specs=[scalars(tb * n), scalars(tb * n), scalars(tb),
                  rows, rows, rows,
                  pl.BlockSpec((n, ts, LANES), lambda si, ei, ti: (0, ei, 0)),
                  chan, chan, held],
        out_specs=[rows, held],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="rlt_ssm_scan",
        interpret=_interpret(),
    )(blocks(b), blocks(c), blocks(real), x, dt, z, a, d, dt_bias, state)
