"""The parts of the trained expert decoder's step alone, on the chip, at the
`train.SmallThinker-21BA3B-Instruct.ctx16k` cell's widths: the flash kernels
forward and backward on a full and on a window layer (by KV block), the
trained expert layer forward and backward (by the grouped product's row
tile at a seeded router's routing, and at routings told to the router that
leave 1, 2 and all 6 stretches of the bound with a row: a tenth of the
bound, even routing's quarter, every pair held), and one index gather of
the layer's static bound of rows.

    chiprun --chips 1 -- env PYTHONPATH=. python3 scripts/swa_moe_alone.py [flash] [experts] [stretches] [gather]

Prints one JSON line a timing (median of 5 after 2 warm-ups) with the work's
least time beside it; about 3 min held for all four parts (the default). It
refuses off the chip. ``PYTHONPATH=_parent`` times another checkout's layer
(the parent commit unpacked by `git archive`) with this script.
"""
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.held_experts import HeldExperts
from ray_lightning_tpu.models.swa_moe import SwaMoeConfig
from ray_lightning_tpu.ops import grouped_matmul as gm
from ray_lightning_tpu.ops.pallas.flash import flash_attention_pallas

S, D, H, KV, HD, W = 16384, 2560, 28, 4, 128, 4096
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def timed(fn, *args):
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    out = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(out)


def say(**row):
    print(json.dumps(row), flush=True)


def flash(window, block_k):
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (1, S, H, HD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, KV, HD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, KV, HD), jnp.bfloat16)
    do = jax.random.normal(ks[3], (1, S, H, HD), jnp.bfloat16)
    step = jax.jit(jax.grad(lambda q, k, v: (flash_attention_pallas(
        q, k, v, window=window, block_k=block_k).astype(jnp.float32)
        * do).sum(), (0, 1, 2)))
    w = S if window is None else window
    pairs = w * (w + 1) // 2 + (S - w) * w
    say(part="flash_fwd_bwd", window=window, block_k=block_k,
        ms=timed(step, q, k, v),
        least_ms=1e3 * 3.5 * 4 * H * HD * pairs / PEAK_FLOPS)


def told(rows):
    """(the rows the router reads, its matrix) that send ``rows`` of the
    16,384 x 6 pairs to the 16 held experts, spread evenly over them: the
    rows carry the logits and the matrix is the identity on 64 columns."""
    t, j = jnp.arange(S)[:, None], jnp.arange(6)[None, :]
    held = j < rows // S + (t < rows % S)
    chosen = jnp.where(held, (t + j) % 16, 16 + (t + j) % 48)
    logits = jax.random.normal(jax.random.key(5), (S, 64))
    logits = logits.at[t, chosen].add(8.0)
    x = jnp.zeros((S, D), jnp.bfloat16).at[:, :64].set(
        logits.astype(jnp.bfloat16))
    return x, jnp.eye(D, 64)


def experts(row_tile, rows=None):
    gm.TRAINED_ROW_TILE = row_tile
    cfg = SwaMoeConfig(dim=D, dtype=jnp.bfloat16, n_layers=4,
                       experts_held=16, vocab_size=1024)
    ks = jax.random.split(jax.random.key(1), 5)
    h = jax.random.normal(ks[0], (S, D), jnp.bfloat16)
    x = jax.random.normal(ks[1], (S, D), jnp.bfloat16)
    stacks = (0.02 * jax.random.normal(ks[2], (16, D, 2 * 768)),
              0.02 * jax.random.normal(ks[3], (16, 768, D)))
    params = {"router": jax.random.normal(ks[4], (D, 64))}
    if rows is not None:
        x, params["router"] = told(rows)
    layer = HeldExperts(cfg, trained=True)

    def loss(params, h, x, stacks):
        y, counts = layer.apply({"params": params}, h, stacks, route_from=x)
        return jnp.square(y).sum(), counts

    step = jax.jit(jax.grad(loss, (0, 1, 3), has_aux=True))
    _, counts = step(params, h, x, stacks)
    rows = int(counts[0])
    # a checkout from before the stretches has two counts
    say(part="expert_layer_fwd_bwd", row_tile=row_tile, rows=rows,
        rows_max=int(counts[1]), stretches=[int(c) for c in counts[2:]],
        ms=timed(step, params, h, x, stacks),
        least_ms=1e3 * 3 * 2 * rows * 3 * D * 768 / PEAK_FLOPS)


def gather():
    h = jax.random.normal(jax.random.key(2), (S, D), jnp.bfloat16)
    index = jax.random.randint(jax.random.key(3), (S * 6,), 0, S)
    take = jax.jit(lambda h, i: jnp.take(h, i, axis=0))
    say(part="gather_bound_rows", rows=S * 6, ms=timed(take, h, index),
        least_ms=1e3 * 2 * S * 6 * D * 2 / PEAK_BYTES)


def main() -> int:
    if jax.default_backend() != "tpu":
        print("scripts/swa_moe_alone.py times the chip: no TPU here",
              file=sys.stderr)
        return 2
    parts = sys.argv[1:] or ["flash", "experts", "stretches", "gather"]
    if "flash" in parts:
        for window in (None, W):
            for block_k in (1024, 512):
                flash(window, block_k)
    if "experts" in parts:
        for row_tile in (512, 256, 128):
            experts(row_tile)
    if "stretches" in parts:
        for rows in (9_600, 24_576, S * 6):
            experts(512, rows)
    if "gather" in parts:
        gather()
    return 0


if __name__ == "__main__":
    sys.exit(main())
