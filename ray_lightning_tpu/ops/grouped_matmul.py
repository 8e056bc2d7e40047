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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: rows a tile of the Pallas product holds; callers pad R to a multiple
ROW_TILE = 128


def row_tile(rows: int) -> int:
    """The row tile for ``rows`` rows: `ROW_TILE`, or the rows rounded up
    to a sublane tile where there are fewer."""
    return ROW_TILE if rows >= ROW_TILE else -(-rows // 8) * 8


def grouped_matmul(lhs, rhs, group_sizes, use_pallas: bool | None = None,
                   out_dtype=None, layer=0):
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
    if pallas:
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
