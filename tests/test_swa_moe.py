"""The second decoder `Trainer.fit` trains (`models/swa_moe.py`) and what
training it needed of the shared parts: a sliding window in the flash
kernels, a grouped product with a backward pass, an expert layer that
works over the rows that are there, a stretch of its bound at a time. On the
CPU at tiny widths with the real
structure: four layers of the two kinds, a window shorter than the sequence,
8 experts of which a chip holds 2-4, 3 a token."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu import FSDP, DataLoader, SingleDevice, Trainer
from ray_lightning_tpu.models.held_experts import (
    HeldExperts, held_rows_bound, route, stretch_rows, stretch_sizes,
)
from ray_lightning_tpu.models.swa_moe import (
    SwaMoe, SwaMoeBlock, SwaMoeConfig, SwaMoeModule, swa_moe_param_specs,
)
from ray_lightning_tpu.ops import dispatch
from ray_lightning_tpu.ops.attention import (
    dot_product_attention, flash_attention,
)
from ray_lightning_tpu.ops.grouped_matmul import (
    grouped_matmul, grouped_row_sums,
)
from ray_lightning_tpu.ops.pallas.flash import flash_attention_pallas
from ray_lightning_tpu.ops.rope import rope_frequencies

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- the window in the flash kernels ----------------------------------------


def _qkv(s=256, h=4, kv=2, d=128, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (1, s, h, d)),
            jax.random.normal(ks[1], (1, s, kv, d)),
            jax.random.normal(ks[2], (1, s, kv, d)),
            jax.random.normal(ks[3], (1, s, h, d)))


# a window that cuts whole blocks, one that cuts through a block, one row,
# and one wider than a block of either kind
@pytest.mark.parametrize("window", [128, 72, 1, 200])
@pytest.mark.parametrize("blocks", [(64, 64), (32, 128), (128, 32)])
def test_window_flash_matches_masked_attention(window, blocks):
    q, k, v, do = _qkv()
    bq, bk = blocks

    def kernel(q, k, v):
        return flash_attention_pallas(q, k, v, window=window, block_q=bq,
                                      block_k=bk)

    def plain(q, k, v):
        return dot_product_attention(q, k, v, window=window)

    with dispatch.force_pallas():
        out = kernel(q, k, v)
        grads = jax.grad(lambda *a: (kernel(*a) * do).sum(), (0, 1, 2))(
            q, k, v)
    want = plain(q, k, v)
    want_grads = jax.grad(lambda *a: (plain(*a) * do).sum(), (0, 1, 2))(
        q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-6)
    for got, ref, name in zip(grads, want_grads, "qkv"):
        np.testing.assert_allclose(got, ref, atol=2e-5, err_msg=f"d{name}")


def test_a_band_that_covers_everything_is_no_window_bit_for_bit():
    """`window=None` lowers as it did before the window existed
    (`scripts/step_lowered_same.py` reads both training cells' steps SAME);
    a window as long as the sequence walks the same blocks in the same
    order and has to give the same bits, forward and backward."""
    q, k, v, do = _qkv()
    with dispatch.force_pallas():
        def run(window):
            f = lambda *a: flash_attention_pallas(
                *a, window=window, block_q=64, block_k=64)
            return (f(q, k, v), *jax.grad(
                lambda *a: (f(*a) * do).sum(), (0, 1, 2))(q, k, v))

        for a, b in zip(run(None), run(256)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_window_needs_causal_and_the_fallback_masks_the_same_band():
    q, k, v, _ = _qkv(s=64)
    with pytest.raises(ValueError, match="causal"):
        flash_attention_pallas(q, k, v, causal=False, window=8)
    # off the TPU `flash_attention` is the jax.numpy path: the same band
    t = jnp.arange(64)
    band = (t[:, None] - t[None, :] < 24)[None, None]
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, window=24)),
        np.asarray(dot_product_attention(q, k, v, mask=band)))


def test_blocks_behind_the_band_are_not_in_the_grid():
    """At S = 2048, W = 256 with (128, 256) blocks a Q block's band reaches
    3 KV blocks of 8: the forward's grid has 3 steps on its inner axis."""
    q, k, v, _ = _qkv(s=2048, h=1, kv=1)
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    for window in (None, 256):
        grids.clear()
        with dispatch.force_pallas():
            walk(jax.make_jaxpr(lambda *a: flash_attention_pallas(
                *a, window=window, block_q=128, block_k=256))(q, k, v).jaxpr)
        assert grids == [(1, 1, 16, 8 if window is None else 3)], grids


# ---- the grouped product's backward pass --------------------------------------


@pytest.mark.parametrize("sizes", [[40, 0, 56, 16], [0, 0, 128, 0],
                                   [128, 0, 0, 0], [0, 0, 0, 0]])
def test_grouped_matmul_vjp_matches_ragged_dots_own(sizes):
    """Empty groups, and rows of no group (the sizes sum to at most 128 of
    256 rows): both cotangents against `lax.ragged_dot`'s, and the rows of
    no group zero in both directions."""
    ks = jax.random.split(jax.random.key(1), 3)
    lhs = jax.random.normal(ks[0], (256, 128), jnp.float32)
    rhs = jax.random.normal(ks[1], (4, 128, 256), jnp.float32)
    ct = jax.random.normal(ks[2], (256, 256), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)

    def loss(lhs, rhs, use_pallas):
        return (grouped_matmul(lhs, rhs, sizes, use_pallas,
                               trained=True) * ct).sum()

    out = grouped_matmul(lhs, rhs, sizes, True, trained=True)
    want = grouped_matmul(lhs, rhs, sizes, False)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)
    got = jax.grad(loss, (0, 1))(lhs, rhs, True)
    ref = jax.grad(loss, (0, 1))(lhs, rhs, False)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-4)
    n = int(sizes.sum())
    assert not np.asarray(out[n:]).any() and not np.asarray(got[0][n:]).any()


def test_grouped_matmul_vjp_over_a_stack_of_layers():
    """The [L, G, K, N] stack form keeps working, and differentiates: the
    layer read gets the gradient, the others zeros."""
    ks = jax.random.split(jax.random.key(2), 2)
    lhs = jax.random.normal(ks[0], (128, 128), jnp.float32)
    rhs = jax.random.normal(ks[1], (3, 2, 128, 128), jnp.float32)
    sizes = jnp.asarray([72, 40], jnp.int32)

    def loss(rhs, use_pallas):
        return grouped_matmul(lhs, rhs, sizes, use_pallas, layer=1,
                              trained=True).sum()

    got, ref = jax.grad(loss)(rhs, True), jax.grad(loss)(rhs, False)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)
    assert not np.asarray(got[0]).any() and np.asarray(got[1]).any()


def test_the_bare_product_is_the_call_it_was():
    """`trained=False` traces no `custom_vjp`: the serving steps' jaxprs do
    not change (`scripts/step_jaxpr_same.py`)."""
    lhs, rhs = jnp.zeros((128, 128)), jnp.zeros((2, 128, 128))
    sizes = jnp.asarray([64, 64], jnp.int32)
    text = lambda **kw: str(jax.make_jaxpr(lambda a, b: grouped_matmul(
        a, b, sizes, True, **kw))(lhs, rhs))
    assert "custom_vjp" not in text()
    assert "custom_vjp" in text(trained=True)


@pytest.mark.parametrize("scaled", [False, True])
@pytest.mark.parametrize("sizes", [[100, 0, 60], [0, 0, 0], [256, 0, 0]])
def test_grouped_row_sums_adds_unrounded_products_into_what_it_is_given(
        sizes, scaled):
    """The kernel (interpreted) over bfloat16 rows with a float32 scale: the
    scale goes in as three bfloat16 pieces, so each product is float32's
    own and not the 3 digits a rounded one keeps; rows of no group are in no
    sum and an empty group keeps what it was given."""
    ks = jax.random.split(jax.random.key(21), 4)
    rows = jax.random.normal(ks[0], (256, 128), jnp.bfloat16)
    slot = jax.random.randint(ks[1], (256,), 0, 128)
    scale = jax.random.uniform(ks[2], (256,)) if scaled else None
    into = jax.random.normal(ks[3], (3, 128, 128), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = np.array(into, np.float64)
    ends = np.cumsum(sizes)
    for r in range(int(ends[-1])):
        want[np.searchsorted(ends, r, side="right"), int(slot[r])] += (
            np.float64(rows[r].astype(jnp.float32))
            * (np.float64(scale[r]) if scaled else 1.0))
    for use_pallas in (True, False):
        got = grouped_row_sums(rows, slot, sizes, into, scale, use_pallas)
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


# ---- the expert layer ---------------------------------------------------------


def _layer(cfg, trained, seed=3, tokens=48):
    ks = jax.random.split(jax.random.key(seed), 5)
    d, f, held = cfg.dim, cfg.moe_hidden_dim, cfg.held
    h = jax.random.normal(ks[0], (tokens, d))
    x = jax.random.normal(ks[1], (tokens, d))
    stacks = (0.3 * jax.random.normal(ks[2], (held, d, 2 * f)),
              0.3 * jax.random.normal(ks[3], (held, f, d)))
    layer = HeldExperts(cfg, trained=trained)
    router = jax.random.normal(ks[4], (d, cfg.n_routed_experts))
    params = {"router": router}
    return layer, params, h, x, stacks


#: tokens of a routed layer below: with 3 experts a token and 4 of 8 held the
#: bound is 768 rows, six stretches of 128
ROUTED_TOKENS = 256
#: routing -> the rows each of the 4 held experts gets, a token sending at
#: most one pair to a held expert; "every_pair" sends all three
ROUTINGS = {
    "no_row": [0, 0, 0, 0],
    "one_full_stretch": [128, 0, 0, 0],
    "one_row_more": [129, 0, 0, 0],
    "boundary_inside_a_group": [100, 100, 0, 0],
    "an_empty_expert_between": [150, 0, 100, 0],
    "every_pair": [192, 192, 192, 192],
}


def _routed(routing, seed=11):
    """A layer whose router is told what to choose: the rows the router
    reads carry the logits themselves (the router's matrix is the identity
    on the first 8 columns), so `ROUTINGS[routing]` decides the group sizes
    and a seeded draw the weights."""
    cfg = SwaMoeConfig.tiny()
    layer, params, h, x, stacks = _layer(cfg, True, tokens=ROUTED_TOKENS)
    t, e = ROUTED_TOKENS, cfg.n_routed_experts
    chosen = np.tile(np.asarray([5, 6, 7]), (t, 1))       # held elsewhere
    if routing == "every_pair":
        chosen = (np.arange(t)[:, None] + np.arange(3)[None, :]) % 4
    else:
        ends = np.cumsum(ROUTINGS[routing])
        chosen[:ends[-1], 0] = np.searchsorted(ends, np.arange(ends[-1]),
                                               side="right")
    logits = np.array(jax.random.normal(jax.random.key(seed), (t, e)))
    np.put_along_axis(logits, chosen, 8.0 + np.take_along_axis(
        logits, chosen, axis=1), axis=1)
    x = jnp.zeros_like(x).at[:, :e].set(logits)
    params = {"router": jnp.eye(cfg.dim, e)}
    return cfg, layer, params, h, x, stacks


def _layer_grads(layer, params, h, x, stacks):
    """Output, counts and the gradient of a weighted sum of the output
    with respect to the router, the rows, the router's rows and both
    stacks."""
    ct = jax.random.normal(jax.random.key(12), h.shape)

    def loss(params, h, x, stacks):
        return (layer.apply({"params": params}, h, stacks,
                            route_from=x)[0] * ct).sum()

    y, counts = layer.apply({"params": params}, h, stacks, route_from=x)
    return y, counts, jax.tree.leaves(
        jax.grad(loss, (0, 1, 2, 3))(params, h, x, stacks))


@pytest.mark.parametrize("routing", ["seeded", *ROUTINGS])
def test_dispatch_by_index_is_the_one_hot_dispatch(routing):
    """The looped layer against the serving decoders' one-hot dispatch under
    XLA's own transposes: output, counts and every gradient, at routings
    that run no stretch, one to its last row, two by one row, with a
    stretch's end inside a group and past an empty one, and all six (the
    bound itself: nothing dropped)."""
    if routing == "seeded":
        cfg = SwaMoeConfig.tiny()
        layer, params, h, x, stacks = _layer(cfg, True)
    else:
        cfg, layer, params, h, x, stacks = _routed(routing)
    y, counts, grads = _layer_grads(layer, params, h, x, stacks)
    want_y, want_counts, want_grads = _layer_grads(
        HeldExperts(cfg, trained=False), params, h, x, stacks)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(counts[:2], want_counts)
    rows = int(counts[0])
    if routing != "seeded":
        assert rows == sum(ROUTINGS[routing])
    r1 = stretch_rows(held_rows_bound(cfg, h.shape[0]))
    assert r1 == 128 and int(counts[2]) == -(-rows // r1)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def _plain_layer(cfg, params, h, x, stacks):
    """What the layer computes, written down: every held expert over every
    row, weighted by what the router gave that (token, expert) pair."""
    logits = jnp.dot(x, params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    experts, weights = route(cfg, logits)
    y = jnp.zeros(h.shape, jnp.float32)
    for e in range(cfg.held):
        gate, up = jnp.split(h @ stacks[0][e], 2, axis=-1)
        w = jnp.sum(jnp.where(experts == cfg.experts_first + e, weights,
                              0.0), axis=-1)
        y = y + w[:, None] * ((jax.nn.relu(gate) * up) @ stacks[1][e])
    return y


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_looped_layer_is_the_plain_sum_over_held_experts(routing):
    cfg, layer, params, h, x, stacks = _routed(routing)
    ct = jax.random.normal(jax.random.key(12), h.shape)
    with jax.default_matmul_precision("highest"):
        y, _, grads = _layer_grads(layer, params, h, x, stacks)
        want = _plain_layer(cfg, params, h, x, stacks)
        want_grads = jax.tree.leaves(jax.grad(
            lambda *a: (_plain_layer(cfg, *a) * ct).sum(), (0, 1, 2, 3))(
                params, h, x, stacks))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    moved = [float(jnp.abs(g).max()) > 0 for g in grads]
    assert moved == [routing != "no_row"] * 5, moved
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("routing", ["boundary_inside_a_group", "every_pair"])
def test_a_stretch_goes_through_the_pallas_products(routing):
    """The kernels (interpreted) inside the loop, both ways, against their
    `jax.numpy` twin: a stretch's own group sizes and 128 rows each call."""
    cfg, layer, params, h, x, stacks = _routed(routing)
    ct = jax.random.normal(jax.random.key(12), h.shape)

    def grads(use_pallas):
        return jax.tree.leaves(jax.grad(lambda p, h, x, s: (layer.apply(
            {"params": p}, h, s, 0, use_pallas, route_from=x)[0] * ct).sum(),
            (0, 1, 2, 3))(params, h, x, stacks))

    for got, ref in zip(grads(True), grads(False)):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sizes", [*ROUTINGS.values(), [0, 768, 0, 0],
                                   [1, 0, 0, 766]])
def test_a_bounds_stretches_share_out_its_group_sizes(sizes):
    sizes = jnp.asarray(sizes, jnp.int32)
    parts = np.stack([stretch_sizes(sizes, lo, 128)
                      for lo in range(0, 768, 128)])
    np.testing.assert_array_equal(parts.sum(0), sizes)
    np.testing.assert_array_equal(
        parts.sum(1), np.clip(int(sizes.sum()) - np.arange(0, 768, 128),
                              0, 128))


# the cell's bound; bounds of 6, 5 and 2 tiles of 128 rows, and of 6, 2 and 1
# of 512; seven tiles have one stretch, the bound itself; less than a tile
@pytest.mark.parametrize("rows,want", [
    (98304, 16384), (768, 128), (640, 128), (256, 128), (3072, 512),
    (1024, 512), (512, 512), (896, 896), (48, 48)])
def test_a_stretch_is_whole_row_tiles_and_divides_the_bound(rows, want):
    assert stretch_rows(rows) == want


def test_gradients_reach_the_router_through_the_weights():
    cfg = SwaMoeConfig.tiny()
    layer, params, h, x, stacks = _layer(cfg, True)

    def loss(params, h, x, stacks):
        return jnp.square(layer.apply({"params": params}, h, stacks,
                                      route_from=x)[0]).sum()

    g_router, g_h, g_x, g_stacks = jax.grad(loss, (0, 1, 2, 3))(
        params, h, x, stacks)
    assert float(jnp.abs(g_router["router"]).max()) > 0
    assert float(jnp.abs(g_x).max()) > 0          # only through the router
    assert float(jnp.abs(g_h).max()) > 0
    # the one-hot layer under XLA's own transposes gives the same numbers
    plain = HeldExperts(cfg, trained=False)
    want = jax.grad(lambda p, h, x, s: jnp.square(plain.apply(
        {"params": p}, h, s, route_from=x)[0]).sum(), (0, 1, 2, 3))(
            params, h, x, stacks)
    for got, ref in zip(jax.tree.leaves((g_router, g_h, g_x, g_stacks)),
                        jax.tree.leaves(want)):
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_the_four_shares_sum_to_the_uncut_layer():
    """The parts the shares (0, 2) .. (6, 2) of the 8 experts give add up to
    what one chip holding all 8 gives: what absent experts would add is
    left out, never replaced."""
    whole_cfg = SwaMoeConfig.tiny(experts_first=0, experts_held=8)
    layer, params, h, x, stacks = _layer(whole_cfg, True)
    whole, counts = layer.apply({"params": params}, h, stacks, route_from=x)
    assert int(counts[0]) == 48 * 3              # every pair is held
    parts, rows = jnp.zeros_like(whole), 0
    for first in range(0, 8, 2):
        cfg = SwaMoeConfig.tiny(experts_first=first, experts_held=2)
        held = tuple(s[first:first + 2] for s in stacks)
        part, c = HeldExperts(cfg, trained=True).apply(
            {"params": params}, h, held, route_from=x)
        parts, rows = parts + part, rows + int(c[0])
    assert rows == 48 * 3                        # no row dropped, none twice
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-5)


def test_topk_softmax_weights_are_the_softmax_over_the_chosen():
    cfg = SwaMoeConfig.tiny()
    logits = jax.random.normal(jax.random.key(4), (16, 8))
    experts, weights = route(cfg, logits)
    full = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(full, experts, axis=-1)
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)


# ---- the decoder ---------------------------------------------------------------


def test_a_nope_layer_ignores_positions_and_a_rope_layer_does_not():
    cfg = SwaMoeConfig.tiny()
    x = jax.random.normal(jax.random.key(5), (1, 32, cfg.dim))
    cos, sin = rope_frequencies(cfg.head_dim, 128, cfg.rope_theta)
    here, there = (cos[:32], sin[:32]), (cos[7:71:2], sin[7:71:2])
    for layer, moved in ((0, False), (1, True)):
        block = SwaMoeBlock(cfg, layer)
        params = block.init(jax.random.key(6), x, *here)["params"]
        a, _ = block.apply({"params": params}, x, *here)
        b, _ = block.apply({"params": params}, x, *there)
        far = float(jnp.abs(a - b).max())
        assert (far > 1e-6) if moved else (far == 0.0), (layer, far)


def test_a_window_layer_sees_only_its_band():
    cfg = SwaMoeConfig.tiny(n_routed_experts=8, experts_held=8)
    x = jax.random.normal(jax.random.key(7), (1, 64, cfg.dim))
    y = x.at[:, :3].add(1.0)
    cos, sin = rope_frequencies(cfg.head_dim, 64, cfg.rope_theta)
    for layer, reaches in ((0, True), (1, False)):
        block = SwaMoeBlock(cfg, layer)
        params = block.init(jax.random.key(8), x, cos, sin)["params"]
        a, _ = block.apply({"params": params}, x, cos, sin)
        b, _ = block.apply({"params": params}, y, cos, sin)
        far = float(jnp.abs(a - b)[:, 3 + cfg.window:].max())
        assert (far > 1e-6) if reaches else (far == 0.0), (layer, far)


def _reference():
    from benchmarks.harness import common

    adapter = common.load_model_file(ROOT, "models", "swa_moe_decoder")
    ref = common.load_model_file(ROOT, "reference", "swa_moe_decoder")
    config = {
        "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "sliding_window_size": 24,
        "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
        "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 4,
        "moe_num_active_primary_experts": 3, "vocab_size": 96,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "max_position_embeddings": 128,
        "published": {"moe_num_primary_experts": 8},
        "deployment": {"experts_first": 2},
        "assumed": {"initializer_std": 0.3},
        "execution": {"ce_chunk_tokens": 16}}
    return adapter, ref, config, adapter.hyperparams(config, "train")


def test_loss_and_every_leafs_gradient_match_the_plain_reference():
    """`SwaMoeModule`'s loss and gradients in float32 against
    `benchmarks/reference/swa_moe_decoder.py` on the same seeded weights:
    the program's fused leaves split back into the published ones."""
    from benchmarks.harness import train, weights

    adapter, ref, config, hp = _reference()
    cfg = adapter.program_config(config, hp)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
    module = SwaMoeModule(cfg)
    module.setup()
    s32 = weights.seed_u32(9)
    params = adapter.program_tree(hp, s32, jnp.float32, False)
    tokens = jax.random.randint(jax.random.key(10), (2, 49), 0, 96)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: module.training_step(p, {"tokens": tokens}, None))(
                params)
    logged = module.pop_logged()
    assert logged["expert_rows"].dtype == jnp.int32
    assert 0 < int(logged["expert_rows_max"]) <= int(logged["expert_rows"])
    assert 4 <= int(logged["expert_stretches"]) <= 4 * 3
    canon = weights.canonical(hp, ref.tables, s32, False)
    want, want_grads = train.batch_loss_and_grads(
        ref, hp, canon, tokens[:, None, :])
    assert abs(float(loss) - float(want)) < 2e-5 * abs(float(want))
    got = adapter.canonical_from_program(hp, grads)
    flat = lambda t: {jax.tree_util.keystr(p): v for p, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    got, want_grads = flat(got), flat(want_grads)
    assert got.keys() == want_grads.keys()
    for name, w in want_grads.items():
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], w, atol=2e-4 * scale,
                                   rtol=2e-3, err_msg=name)


def test_expert_stretches_is_each_layers_rows_over_a_stretch_rounded_up():
    """The third count: a layer runs ``ceil(rows / stretch)`` trips of its
    loop, and the decoder sums them over its layers. 96 tokens of 3 experts
    with 4 of 8 held: a bound of 384 rows, three stretches of 128."""
    cfg = SwaMoeConfig.tiny(remat=False)
    tokens = jax.random.randint(jax.random.key(13), (2, 48), 0, 96)
    model = SwaMoe(cfg)
    params = model.init(jax.random.key(14), tokens)["params"]
    (_, counts), state = model.apply(
        {"params": params}, tokens, capture_intermediates=True,
        mutable=["intermediates"])
    r1 = stretch_rows(held_rows_bound(cfg, 96))
    assert r1 == 128
    layers = [state["intermediates"][f"layer_{i}"]["experts"]["__call__"][0][1]
              for i in range(cfg.n_layers)]
    for c in layers:
        assert int(c[2]) == -(-int(c[0]) // r1) >= 1
    np.testing.assert_array_equal(counts, np.sum(layers, axis=0))


def test_param_specs_name_every_leaf():
    cfg = SwaMoeConfig.tiny()
    params = jax.eval_shape(SwaMoe(cfg).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    paths = {"/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert paths == set(swa_moe_param_specs(cfg))


def _fit(strategy, tmp_path, steps=3):
    cfg = SwaMoeConfig.tiny(dtype=jnp.float32, ce_chunk_tokens=32)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8 * steps, 33)).astype(np.int32)
    losses = []

    from ray_lightning_tpu.core.callbacks import Callback

    class Keep(Callback):
        def on_train_batch_end(self, trainer, module, metrics, batch_idx):
            losses.append(float(metrics["loss"]))

    trainer = Trainer(
        strategy=strategy, max_epochs=1, max_steps=steps,
        log_every_n_steps=1, enable_checkpointing=False,
        enable_progress_bar=False, seed=0, callbacks=[Keep()],
        default_root_dir=str(tmp_path))
    trainer.fit(SwaMoeModule(cfg, warmup_steps=1, total_steps=10),
                DataLoader({"tokens": tokens}, batch_size=8))
    return trainer, losses


def test_three_fit_steps_give_the_same_losses_on_one_device_and_under_fsdp(
        tmp_path, devices8):
    one, losses_one = _fit(SingleDevice(), tmp_path / "one")
    four, losses_four = _fit(FSDP(num_workers=4), tmp_path / "four")
    assert len(losses_one) == len(losses_four) == 3
    assert losses_one[2] < losses_one[0]
    np.testing.assert_allclose(losses_four, losses_one, rtol=2e-5)
    assert one.callback_metrics["expert_rows"] == \
        four.callback_metrics["expert_rows"] > 0
    # under FSDP the experts' stacks are sharded, not replicated
    leaf = four.state.params["layer_0"]["experts_gate_up"]
    assert len(leaf.sharding.device_set) == 4
    assert leaf.addressable_shards[0].data.size * 4 == leaf.size


# ---- the step's counts on the profiler's clock ----------------------------------


def test_a_fetch_puts_the_steps_counts_in_the_trace(tmp_path):
    """`rlt.train.account` after each `rlt.metrics_fetch`, with `step=` and
    the integer counts the module logged; nothing outside a session."""
    trace_dir = tmp_path / "trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        trainer, _ = _fit(SingleDevice(), tmp_path / "fit", steps=2)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(found[0])
    events = [(ev.name, ev.start_ns, dict(ev.stats))
              for plane in data.planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name in ("rlt.train.account", "rlt.metrics_fetch")]
    events.sort(key=lambda e: e[1])
    assert [e[0] for e in events] == ["rlt.metrics_fetch",
                                      "rlt.train.account"] * 2
    for step, (_, _, stats) in enumerate(events[1::2], start=1):
        assert int(stats["step"]) == step
        assert 0 < int(stats["expert_rows_max"]) <= int(stats["expert_rows"])
        assert 4 <= int(stats["expert_stretches"]) <= 4 * 6
        assert "loss" not in stats and "grad_norm" not in stats
    assert int(events[-1][2]["expert_rows"]) == int(
        trainer.callback_metrics["expert_rows"])
