"""Grouped matrix product: rows sorted by group, one weight a group.

    lhs         [R, K]      rows of group 0 first, then group 1, ...
    rhs         [G, K, N]   one matrix a group, or a stack of layers
                            [L, G, K, N] read at ``layer``
    group_sizes [G] int32   rows of each group; rows past their sum
                            belong to no group
    -> [R, N], rows past ``sum(group_sizes)`` zero

A layer of a stack is never sliced out: XLA cannot hand a Mosaic call a
dynamic slice in place, and copied a layer's 1.4 GB of expert weights in
front of every product (42% of the serving step, my chip run, PR 27). The
stack is relabelled ``[L * G, K, N]`` (leading dimensions merge, nothing
moves) and the layer folded into the groups: ``group_sizes`` becomes a
vector over all ``L * G`` with the layer's sizes in its own stretch and
zeros elsewhere (the `stack_as_pool` rule of the paged kernels).

The expert layer's product (`models/mla_moe.py`): the rows routed to the
experts a chip holds, sorted by expert. On TPU (or forced, interpreted
elsewhere) it is the Pallas grouped matmul jax ships
(`jax.experimental.pallas.ops.tpu.megablox.gmm`): its grid runs over the
row tiles that hold a row, so the work follows the rows that are there
and an expert's weights are read once a row tile it owns; elsewhere
`lax.ragged_dot`, the `jax.numpy` twin with the same semantics.

``trained=True`` is the product a backward pass can cross. `gmm` has no
transpose rule, so the Pallas branch then runs under a `jax.custom_vjp`
(`_gmm_trained`): the rows' cotangent is the same grouped product against
the transposed weights (``gmm(.., transpose_rhs=True)``), the weights' is
`megablox.gmm.tgmm` (``lhs[rows of g].T @ grad[rows of g]`` a group; an
empty group's is zero), and the rows of no group stay zero in both
directions. Off the TPU `lax.ragged_dot` differentiates itself. The
serving steps leave it False and trace the bare call they always have.

The trained expert layer (`models/held_experts.py:_held_rows`) never calls
this over its whole bound of rows: it cuts the bound into equal stretches
and calls it once a stretch that holds a row, inside a loop, with the
layer's group sizes clipped to the stretch (a group that a stretch's end
cuts has its rows in two calls, and a group outside the stretch is an
empty one there). The backward trip differentiates the two products of its
own stretch (`jax.vjp` inside the loop's body) and adds the weights'
cotangents, one `tgmm` a stretch, into a float32 carry. ``lhs`` is then a
stretch's rows, a multiple of `TRAINED_ROW_TILE` where the bound is.

`grouped_row_sums` is the same `tgmm` read as a sum: the rows of a group
added up by a slot each carries, ``out[g, j] += sum of scale[r] * rows[r]
over the rows r of g with slot[r] == j``, as the product of a one-hot of
the slots with the rows, accumulated in float32 into what it is given
(`tgmm`'s ``existing_out``, in place). The trained expert layer adds a
stretch's rows into their tokens with it, the token tiles as groups.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: rows a tile of the Pallas product holds; callers pad R to a multiple
ROW_TILE = 128
#: rows a tile of the trained product holds where the rows divide by it:
#: a tile's rows share one read of an expert's weights, and at 128 rows
#: that read bounds the product (110 FLOPs a byte at 640 x 768 tiles
#: against the v5e's ridge of 240)
TRAINED_ROW_TILE = 512


def row_tile(rows: int) -> int:
    """The row tile for ``rows`` rows: `ROW_TILE`, or the rows rounded up
    to a sublane tile where there are fewer."""
    return ROW_TILE if rows >= ROW_TILE else -(-rows // 8) * 8


def _tile(n: int) -> int:
    """The largest multiple of 128 up to 1024 that divides ``n``; ``n``
    capped at 1024 where none does."""
    return next((t for t in range(1024, 0, -128) if n % t == 0),
                min(n, 1024))


def _trained_tiling(rows: int, k: int, n: int):
    tm = TRAINED_ROW_TILE if rows % TRAINED_ROW_TILE == 0 else row_tile(rows)
    return tm, _tile(k), _tile(n)


def _gmm(lhs, rhs, group_sizes, out_dtype: str, transpose_rhs: bool = False):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from ray_lightning_tpu.ops import dispatch

    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return gmm(lhs, rhs, group_sizes,
               preferred_element_type=jnp.dtype(out_dtype),
               tiling=_trained_tiling(*lhs.shape, n),
               transpose_rhs=transpose_rhs,
               interpret=dispatch.interpret_mode())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_trained(lhs, rhs, group_sizes, out_dtype: str):
    """The Pallas grouped product [R, K] x [G, K, N] with a backward pass;
    rows past ``sum(group_sizes)`` are left unwritten, as `gmm` leaves
    them (the caller zeroes them)."""
    return _gmm(lhs, rhs, group_sizes, out_dtype)


def _gmm_trained_fwd(lhs, rhs, group_sizes, out_dtype):
    return _gmm(lhs, rhs, group_sizes, out_dtype), (lhs, rhs, group_sizes)


def _gmm_trained_bwd(out_dtype, res, grad):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    from ray_lightning_tpu.ops import dispatch

    lhs, rhs, group_sizes = res
    rows, k = lhs.shape
    valid = (jnp.arange(rows) < jnp.sum(group_sizes))[:, None]
    # a row of no group has no product: its cotangent is nobody's
    grad = jnp.where(valid, grad, jnp.zeros((), grad.dtype))
    d_lhs = _gmm(grad, rhs, group_sizes, lhs.dtype.name, transpose_rhs=True)
    d_lhs = jnp.where(valid, d_lhs, jnp.zeros((), d_lhs.dtype))
    d_rhs = tgmm(lhs.swapaxes(0, 1), grad, group_sizes,
                 preferred_element_type=rhs.dtype,
                 tiling=_trained_tiling(rows, k, rhs.shape[2]),
                 interpret=dispatch.interpret_mode())
    return d_lhs, d_rhs, None


_gmm_trained.defvjp(_gmm_trained_fwd, _gmm_trained_bwd)


def grouped_matmul(lhs, rhs, group_sizes, use_pallas: bool | None = None,
                   out_dtype=None, layer=0, trained: bool = False):
    from ray_lightning_tpu.ops import dispatch

    out_dtype = out_dtype or lhs.dtype
    rows, k = lhs.shape
    n = rhs.shape[-1]
    group_sizes = group_sizes.astype(jnp.int32)
    valid = (jnp.arange(rows) < jnp.sum(group_sizes))[:, None]
    tm = row_tile(rows)
    pallas = dispatch.use_pallas(use_pallas) and rows % tm == 0
    if rhs.ndim == 4 and pallas:
        layers, groups = rhs.shape[:2]
        rhs = rhs.reshape(layers * groups, k, n)
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((layers * groups,), jnp.int32), group_sizes,
            (jnp.asarray(layer, jnp.int32) * groups,))
    elif rhs.ndim == 4:
        rhs = jax.lax.dynamic_index_in_dim(rhs, layer, keepdims=False)
    if pallas and trained:
        out = _gmm_trained(lhs, rhs, group_sizes, jnp.dtype(out_dtype).name)
    elif pallas:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        out = gmm(lhs, rhs, group_sizes,
                      preferred_element_type=jnp.dtype(out_dtype),
                      tiling=(tm, min(k, 1024), min(n, 1024)),
                      interpret=dispatch.interpret_mode())
    else:
        out = jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=jnp.float32).astype(out_dtype)
    # the Pallas product leaves the rows of no group unwritten
    return jnp.where(valid, out, jnp.zeros((), out.dtype))


def grouped_row_sums(rows, slot, group_sizes, into, scale=None,
                     use_pallas: bool | None = None):
    """rows [R, N] sorted by group, slot [R] int32 in [0, W), group_sizes
    [G], into [G, W, N] float32, scale [R] float32 or None (1) -> ``into``
    plus, at ``[g, j]``, the sum of ``scale[r] * rows[r]`` over the rows r
    of group g whose slot is j; rows past ``sum(group_sizes)`` are in no
    sum. Each product and each sum is float32's.

    On TPU `tgmm` over a one-hot ``[W, R]`` of the slots at the rows' type,
    whose grid visits only the row tiles that hold a row and which adds into
    ``into`` in place. The MXU multiplies two bfloat16 exactly, so a float32
    ``scale`` goes in as the three bfloat16 pieces it is the sum of, one
    `tgmm` a piece: no product is rounded before it is added. Elsewhere a
    scatter-add by ``group * W + slot``."""
    from ray_lightning_tpu.ops import dispatch

    r, n = rows.shape
    groups, width, _ = into.shape
    group_sizes = group_sizes.astype(jnp.int32)
    if (dispatch.use_pallas(use_pallas) and width % 128 == 0
            and r % ROW_TILE == 0):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

        hot = slot[None, :] == jnp.arange(width)[:, None]
        if scale is None:
            pieces = [hot.astype(rows.dtype)]
        else:
            pieces, rest = [], scale.astype(jnp.float32)
            for _ in range(-(-24 // (jnp.finfo(rows.dtype).nmant + 1))):
                part = rest.astype(rows.dtype)
                pieces.append(jnp.where(hot, part[None, :],
                                        jnp.zeros((), rows.dtype)))
                rest = rest - part.astype(jnp.float32)
        for lhs in pieces:
            into = tgmm(lhs, rows, group_sizes,
                        preferred_element_type=jnp.float32,
                        tiling=_trained_tiling(r, width, n),
                        existing_out=into,
                        interpret=dispatch.interpret_mode())
        return into
    ends = jnp.cumsum(group_sizes)
    at = jnp.arange(r)
    to = jnp.where(
        at < ends[-1],
        jnp.searchsorted(ends, at, side="right") * width + slot,
        groups * width)
    rows = rows.astype(jnp.float32)
    if scale is not None:
        rows = rows * scale[:, None]
    return into.reshape(groups * width, n).at[to].add(
        rows, mode="drop").reshape(into.shape)
