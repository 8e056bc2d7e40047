"""Pipeline parallelism: a GPipe-style microbatch pipeline over the mesh's
`pipe` axis.

SURVEY §2.3 lists pipeline parallelism as absent from the reference and
out of its scope; this is a beyond-parity building block, designed the
TPU way: no schedulers, no per-stage processes — ONE compiled SPMD
program in which every `pipe`-axis device holds a contiguous block of
layers and microbatch activations flow stage→stage over ICI
`ppermute`s inside a `lax.scan` (the "pipelined scan" pattern).

Schedule (GPipe, fill-and-drain): with P stages and M microbatches the
scan runs T = M + P - 1 steps; at step t stage p computes microbatch
t - p (when in range), so utilization is M / (M + P - 1) — choose
M >> P. Backward is ordinary jax AD through the scan: ppermute
transposes to the reverse permute, reproducing the reverse-order
pipeline without any hand-written schedule. Per-stage activation
stash is the usual GPipe O(M) — wrap ``stage_fn`` cost down with
``remat=True``.

Composes with the other axes: batch stays sharded on data/fsdp axes,
tensor/seq manual islands keep working inside ``stage_fn`` — the
shard_map here is manual over every mesh axis (like ops/ring_attention's
islands), with batch dims passed through per-shard.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_lightning_tpu.parallel.mesh import dp_axis_names


def pipeline_perm(pipe: int) -> list[tuple[int, int]]:
    """The GPipe stage-to-stage schedule: an OPEN chain (stage i sends to
    i+1, no wrap-around hop — stage 0 never reads its recv, so the
    longest link would carry dead payload; ppermute zero-fills unlisted
    destinations). Schedule metadata for tracecheck (RLT303): a partial
    permutation is legal precisely when, like this one, it has no
    duplicate sources or destinations."""
    return [(i, i + 1) for i in range(pipe - 1)]


def gpipe_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    *,
    microbatches: int,
    axis_name: str = "pipe",
    remat: bool = False,
    remat_policy: Optional[Callable] = None,
    extra: tuple = (),
) -> jnp.ndarray:
    """Apply L stacked layers to ``x``, stage-split over ``axis_name``.

    stage_fn(layer_params, h, *extra) -> h : ONE layer's forward; its
        ``layer_params`` is one leading-axis slice of ``stacked_params``.
    stacked_params : pytree whose leaves have leading dim L (the scanned
        layer stack — the same layout `nn.scan` produces), L % P == 0.
        Each stage owns a contiguous [L/P] block (sharded on `pipe`).
    x : [B, ...] global activations; B % microbatches == 0 per shard.
    extra : broadcast operands passed to every stage_fn call (e.g. rope
        tables) — replicated over the pipe axis.

    Returns ``x`` after all L layers (same shape/sharding as input).
    With pipe size 1 this degrades to a plain layer scan.

    Composition caveat: "composes with data/fsdp" means the BATCH axis —
    activations stay dp/fsdp-sharded. Parameters do NOT: each stage's
    in_spec shards only the layer axis on `pipe` and replicates every
    other param dim, so combining pipe>1 with fsdp>1 all-gathers each
    stage's full layer block inside the shard_map for the duration of
    the step (GPipe owns whole layers by design). For memory-bound
    models prefer fsdp WITHOUT pipe, or accept per-stage unsharded
    weights as the pipeline's cost.
    """
    pipe = mesh.shape.get(axis_name, 1)
    body = (jax.checkpoint(stage_fn, policy=remat_policy) if remat
            else stage_fn)

    if pipe <= 1:
        def seq_body(h, lp):
            return body(lp, h, *extra), None

        return jax.lax.scan(seq_body, x, stacked_params)[0]

    leaves = jax.tree.leaves(stacked_params)
    L = leaves[0].shape[0]
    if L % pipe:
        raise ValueError(f"{L} layers not divisible by pipe={pipe}")
    M = microbatches

    # same batch-axis vocabulary as the Trainer's batch sharding — ONE
    # source of truth for which axes carry the batch
    x_spec = P(dp_axis_names(mesh), *([None] * (x.ndim - 1)))
    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    extra_specs = tuple(jax.tree.map(lambda _: P(), e) for e in extra)

    def local(params_local, x_local, *extra_local):
        # params_local leaves: [L/P, ...] — this stage's layer block
        p_idx = jax.lax.axis_index(axis_name)
        B = x_local.shape[0]
        if B % M:
            raise ValueError(
                f"per-shard batch {B} not divisible by microbatches={M}"
            )
        mbs = x_local.reshape((M, B // M) + x_local.shape[1:])

        def stage(h):
            def layer(h, lp):
                return body(lp, h, *extra_local), None

            return jax.lax.scan(layer, h, params_local)[0]

        def step(carry, t):
            recv, out = carry
            # stage 0 feeds from the microbatch queue; later stages from
            # the activation received last step (clamped index: steps
            # past the queue re-feed the last microbatch, results unused)
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            h = jnp.where(p_idx == 0, feed, recv)
            y = stage(h)
            # open chain, not a ring: stage 0 never reads its recv, so the
            # wrap-around hop (the longest link) would carry dead payload;
            # ppermute zero-fills unlisted destinations
            recv_next = jax.lax.ppermute(y, axis_name, pipeline_perm(pipe))
            # the LAST stage emits microbatch t-(P-1)'s final activation
            out_idx = t - (pipe - 1)
            idx = jnp.clip(out_idx, 0, M - 1)
            valid = (p_idx == pipe - 1) & (out_idx >= 0)
            cur = jax.lax.dynamic_index_in_dim(out, idx, 0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(valid, y, cur), idx, 0
            )
            return (recv_next, out), None

        out0 = jnp.zeros_like(mbs)
        (_, out), _ = jax.lax.scan(
            step, (jnp.zeros_like(mbs[0]), out0), jnp.arange(M + pipe - 1)
        )
        # only the last stage holds real outputs; replicate over the pipe
        out = jax.lax.psum(
            jnp.where(p_idx == pipe - 1, out, jnp.zeros_like(out)),
            axis_name,
        )
        return out.reshape(x_local.shape)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, x_spec) + extra_specs,
        out_specs=x_spec,
        check_vma=False,  # mixes pipe-varying and replicated
    )(stacked_params, x, *extra)


def pipeline_param_spec(inner: Optional[P] = None,
                        axis_name: str = "pipe") -> P:
    """PartitionSpec for a layer-stacked parameter under pipeline
    parallelism: leading (layer) axis on `pipe`, then the given per-layer
    spec. Modules put this in param_specs() for their stacked blocks."""
    inner = inner or P()
    return P(axis_name, *inner)
