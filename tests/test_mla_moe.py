"""The decoder with latent attention and routed experts
(`models/mla_moe.py`) against its plain reference
(`benchmarks/reference/mla_moe_decoder.py`, which imports nothing of the
program) on seeded weights at a tiny size: logits through the latent paged
cache, the router, the share of held experts, and the kernels against
their `jax.numpy` twins in interpret mode."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import common, serving, weights  # noqa: E402
from ray_lightning_tpu.models.mla_moe import (  # noqa: E402
    HeldExperts, MlaMoe, MlaMoeConfig, held_dispatch, held_rows_bound,
    route, yarn_inv_freq, yarn_tables,
)
from ray_lightning_tpu.ops.attention import (  # noqa: E402
    PagedDecodeView, PagedPrefillView, mla_decode_reference,
    mla_prefill_reference,
)
from ray_lightning_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402
from ray_lightning_tpu.ops.pallas.mla_attention import (  # noqa: E402
    mla_decode_pallas, mla_prefill_pallas, mla_shapes_supported,
)

MODEL = "mla_moe_decoder"
SEED = 7

#: the published keys of a tiny twin: 3 layers (1 dense), 8 heads, a
#: 128 + 64 latent row, 16 experts in 4 groups of which 2 are kept, 4 a
#: token; this "chip" holds experts [8, 16)
FILE = {
    "model": MODEL, "hidden_size": 64, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 8,
    "q_lora_rank": 48, "kv_lora_rank": 128, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 64, "v_head_dim": 32, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "max_position_as_run": 256, "published": {"n_routed_experts": 16},
    "deployment": {"experts_first": 8}, "assumed": {"initializer_std": 0.05},
}


@pytest.fixture(scope="module")
def twin():
    """(hp, reference module, program config float32, program params
    float32, canonical float32 weights)."""
    adapter = common.load_model_file(ROOT, "models", MODEL)
    ref = common.load_model_file(ROOT, "reference", MODEL)
    hp = adapter.hyperparams(FILE, "serve")
    # rounded to bfloat16-representable numbers, as the harness hands them
    # to the reference (`serving.reference_logits`)
    canon = adapter._canonical(hp, weights.seed_u32(SEED), True)
    params = adapter.tree_from_canonical(hp, canon, jnp.float32)
    cfg = dataclasses.replace(adapter.program_config(FILE, hp),
                              dtype=jnp.float32)
    return hp, ref, cfg, params, canon


def _reference_logits(ref, hp, tokens):
    return np.asarray(serving.reference_logits(
        ref, hp, SEED, [(tokens, 0, len(tokens))], 128)[0])


def _through_the_cache(cfg, params, tokens, chunk=16, n_prefill=32,
                       block=16):
    """Logits of every position: `n_prefill` tokens in chunks through the
    paged prefill path, the rest one token at a time through paged
    decode, over a pool whose blocks are handed out out of order."""
    model = MlaMoe(cfg)
    (shape,) = cfg.pool_leaf_shapes(9, block)
    pool = jnp.zeros(shape, cfg.dtype)
    table = jnp.asarray([[3, 5, 1, 7]], jnp.int32)
    toks = jnp.asarray(tokens, jnp.int32)[None]
    out = []
    for start in range(0, n_prefill, chunk):
        wpos = start + jnp.arange(chunk)
        view = PagedPrefillView(
            tables=table, write_block=table[0][wpos // block][None],
            write_offset=(wpos % block)[None], use_pallas=True)
        lg, (pool,), _ = model.apply(
            {"params": params}, toks[:, start:start + chunk], cache=(pool,),
            pos=jnp.int32(start), paged=view)
        out.append(lg[0])
    for t in range(n_prefill, len(tokens)):
        pos = jnp.asarray([t], jnp.int32)
        view = PagedDecodeView(
            tables=table, lengths=pos + 1, write_block=table[0][pos // block],
            write_offset=pos % block, use_pallas=True)
        lg, (pool,), _ = model.apply(
            {"params": params}, toks[:, t:t + 1], cache=(pool,), pos=pos,
            paged=view)
        out.append(lg[0])
    return np.concatenate([np.asarray(x) for x in out], 0)


#: float32 on both sides, the same seeded weights: what is left is the
#: order of float32 sums (absorbed against expanded products, online
#: softmax, the grouped product), 2e-6 here on logits of magnitude 0.3.
#: bfloat16 accumulation reads 4e-3 and a dropped rope term 2e-2 (both
#: tested below), so 5e-5 fails either by a wide margin.
LOGIT_TOL = 5e-5


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(
        twin):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    want = _reference_logits(ref, hp, tokens)
    got = _through_the_cache(cfg, params, tokens)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_expanded_full_forward_matches_the_reference_and_the_absorbed_path(
        twin):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(1).integers(0, 256, 40).astype(np.int32)
    full = np.asarray(MlaMoe(cfg).apply({"params": params},
                                        jnp.asarray(tokens)[None])[0])
    np.testing.assert_allclose(full, _reference_logits(ref, hp, tokens),
                               atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(_through_the_cache(cfg, params, tokens), full,
                               atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault", ["bfloat16", "no_rope"])
def test_the_tolerance_fails_a_lower_precision_and_a_dropped_rope_term(
        twin, fault):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(2).integers(0, 256, 40).astype(np.int32)
    want = _reference_logits(ref, hp, tokens)
    if fault == "bfloat16":
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    else:
        n = hp["qk_nope_head_dim"]
        params = jax.tree.map(lambda x: x, params)
        for stack in ("dense_layers", "moe_layers"):
            params[stack] = dict(params[stack])
            params[stack]["wq_b"] = params[stack]["wq_b"].at[..., n:].set(0)
    got = _through_the_cache(cfg, params, tokens)
    assert np.abs(got - want).max() > 20 * LOGIT_TOL


# ---- YaRN ---------------------------------------------------------------------


def test_yarn_frequencies_at_the_published_scaling():
    """low = floor(c(32)) = 10, high = ceil(c(1)) = 23 for d = 64, theta
    10000, 4096 original positions: dims below 10 keep their frequency,
    dims from 23 on are slowed by the factor 40, a linear ramp between."""
    cfg = MlaMoeConfig()
    inv = np.asarray(yarn_inv_freq(cfg))
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    assert abs(cfg.softmax_scale
               - 192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2) < 1e-9
    cos, sin = yarn_tables(dataclasses.replace(cfg, max_seq_len=8))
    np.testing.assert_allclose(np.asarray(cos[0]), 1.0)   # mscale ratio 1
    assert cos.shape == (8, 32)


# ---- the router ---------------------------------------------------------------


def _noaux_tc(scores, bias, n_group, topk_group, k, scaling):
    """A literal transcription of the published choice, one token at a
    time."""
    chosen, weight = [], []
    per = scores.shape[1] // n_group
    for s in scores:
        biased = s + bias
        group_score = [np.sort(biased[g * per:(g + 1) * per])[-2:].sum()
                       for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-group_score[g], g))[
            :topk_group]
        allowed = [e for g in kept for e in range(g * per, (g + 1) * per)]
        top = sorted(allowed, key=lambda e: (-biased[e], e))[:k]
        w = s[top]
        chosen.append(top)
        weight.append(w / (w.sum() + 1e-20) * scaling)
    return np.asarray(chosen), np.asarray(weight)


@pytest.mark.parametrize("bias_std", [0.0, 0.3])
def test_router_matches_a_literal_transcription(bias_std):
    cfg = MlaMoeConfig(n_routed_experts=256, n_group=8, topk_group=4,
                       n_experts_per_tok=8, dim=64, n_layers=1,
                       n_dense_layers=0)
    rng = np.random.default_rng(3)
    scores = 1 / (1 + np.exp(-rng.standard_normal((64, 256)))).astype(
        np.float32)
    bias = (bias_std * rng.standard_normal(256)).astype(np.float32)
    chosen, weight = route(cfg, jnp.asarray(scores), jnp.asarray(bias))
    want_c, want_w = _noaux_tc(scores, bias, 8, 4, 8, 2.5)
    np.testing.assert_array_equal(np.asarray(chosen), want_c)
    np.testing.assert_allclose(np.asarray(weight), want_w, rtol=1e-6)
    # 8 a token out of 4 of the 8 groups, weights summing to 2.5
    assert all(len({e // 32 for e in row}) <= 4 for row in want_c)
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-5)


def test_the_bias_decides_the_choice_and_not_the_weight():
    cfg = MlaMoeConfig(n_routed_experts=16, n_group=4, topk_group=2,
                       n_experts_per_tok=4, dim=64, n_layers=1,
                       n_dense_layers=0)
    rng = np.random.default_rng(4)
    scores = jnp.asarray(rng.uniform(0.2, 0.8, (32, 16)).astype(np.float32))
    bias = jnp.zeros(16).at[5].set(10.0)        # expert 5 wins every row
    chosen, weight = route(cfg, scores, bias)
    assert bool(jnp.all(jnp.any(chosen == 5, axis=-1)))
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        np.asarray(weight),
        np.asarray(picked / picked.sum(-1, keepdims=True) * 2.5), rtol=1e-6)


# ---- the share of held experts -------------------------------------------------


def _moe_layer(twin, layer=1):
    hp, ref, cfg, params, canon = twin
    one = lambda tree: jax.tree.map(lambda x: x[layer - 1], tree)
    return (one(canon["layers"]["moe"]),
            one(params["moe_layers"]["experts"]),
            (params["experts_gate_up"], params["experts_down"]), layer - 1)


def _routed_part(cfg, p, stacks, h, use_pallas=None, index=0):
    return HeldExperts(cfg).apply({"params": p}, h, stacks, index, use_pallas)


def test_the_shares_add_up_to_the_uncut_layer_of_the_reference(twin):
    """Four shares of a 16-expert layer: the routed parts plus the shared
    expert counted once equal the reference's layer that holds all 16."""
    hp, ref, cfg, params, _ = twin
    whole_hp = dict(hp, n_routed_experts=16, experts_first=0)
    adapter = common.load_model_file(ROOT, "models", MODEL)
    w = weights.leaves(whole_hp, adapter.tables.layer_table(whole_hp, "moe"),
                       weights.seed_u32(SEED), 1, False)
    h = jnp.asarray(np.random.default_rng(5).standard_normal(
        (24, 64)).astype(np.float32))
    want = ref.routed_share(whole_hp, w, h, None) + ref.swiglu(
        h, w["shared_gate_proj"], w["shared_up_proj"], w["shared_down_proj"],
        None)
    total = ref.swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                       w["shared_down_proj"], None)
    rows = 0
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, experts_first=first, experts_held=4)
        sl = slice(first, first + 4)
        p = {"router": w["gate"], "router_bias": w["e_score_correction_bias"]}
        stacks = (jnp.concatenate([w["experts_gate_proj"][sl],
                                   w["experts_up_proj"][sl]], -1)[None],
                  w["experts_down_proj"][sl][None])
        part, counts = _routed_part(share, p, stacks, h)
        total = total + part
        rows += int(counts[0])
    assert rows == 24 * 4                 # every (token, expert) pair once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-6, rtol=0)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_no_token_is_dropped_when_every_row_goes_to_one_held_expert(
        twin, use_pallas):
    hp, ref, cfg, params, _ = twin
    w, p, stacks, index = _moe_layer(twin)
    forced = 8 + 3                        # a held expert: this chip has 8..15
    bias = jnp.zeros(16).at[forced].set(10.0)
    w = dict(w, e_score_correction_bias=bias)
    p = dict(p, router_bias=bias)
    h = jnp.asarray(np.random.default_rng(6).standard_normal(
        (40, 64)).astype(np.float32))
    part, counts = _routed_part(cfg, p, stacks, h, use_pallas, index)
    assert int(counts[1]) == 40           # the fullest expert has every row
    np.testing.assert_allclose(
        np.asarray(part), np.asarray(ref.routed_share(hp, w, h, None)),
        atol=2e-6, rtol=0)


def test_held_dispatch_sorts_by_expert_within_its_static_bound():
    cfg = MlaMoeConfig.tiny(experts_first=4, experts_held=8)
    experts = jnp.asarray([[0, 5, 11, 15], [4, 5, 6, 7], [1, 2, 3, 12]],
                          jnp.int32)
    weights_ = jnp.arange(12, dtype=jnp.float32).reshape(3, 4) + 1
    token, weight, sizes = held_dispatch(cfg, experts, weights_)
    assert token.shape == (held_rows_bound(cfg, 3),) == (16,)
    # held pairs, by local expert: (t1,e4) (t0,e5) (t1,e5) (t1,e6) (t1,e7)
    # (t0,e11)
    np.testing.assert_array_equal(np.asarray(sizes), [1, 2, 1, 1, 0, 0, 0, 1])
    np.testing.assert_array_equal(np.asarray(token[:6]), [1, 0, 1, 1, 1, 0])
    np.testing.assert_array_equal(np.asarray(weight[:6]), [5, 2, 6, 7, 8, 3])
    assert float(jnp.abs(weight[6:]).max()) == 0.0


# ---- kernels against their jax.numpy twins (interpret mode) -------------------


def _latent_case(seed=0, layers=2, n_blocks=9, p=16, dk=256, rows=3, m=4):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((layers, n_blocks, p, dk),
                                           dtype=np.float32), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(1, n_blocks, (rows, m)), jnp.int32)
    return rng, pool, tables


def _close(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.abs(a - b).max() <= 2e-2 * max(1.0, np.abs(b).max())


@pytest.mark.parametrize("tile_blocks", [1, 2, 4])
def test_mla_decode_kernel_matches_its_reference(tile_blocks):
    rng, pool, tables = _latent_case()
    q = jnp.asarray(rng.standard_normal((3, 8, 256), dtype=np.float32),
                    jnp.bfloat16)
    lengths = jnp.asarray([1, 33, 64], jnp.int32)
    _close(mla_decode_pallas(q, pool, tables, lengths, 128, 0.1, layer=1,
                             tile_blocks=tile_blocks),
           mla_decode_reference(q, pool, tables, lengths, 128, 0.1, layer=1))


@pytest.mark.parametrize("tile_blocks,block_q,pos", [
    (1, 4, 0), (2, 8, 16), (4, 16, 48), (2, 2, 48)])
def test_mla_prefill_kernel_matches_its_reference(tile_blocks, block_q, pos):
    rng, pool, tables = _latent_case(seed=1)
    q = jnp.asarray(rng.standard_normal((2, 16, 8, 256), dtype=np.float32),
                    jnp.bfloat16)
    _close(mla_prefill_pallas(q, pool, tables[:2], pos, 128, 0.1, layer=1,
                              tile_blocks=tile_blocks, block_q=block_q),
           mla_prefill_reference(q, pool, tables[:2], pos, 128, 0.1, layer=1))


def test_mla_kernels_read_the_layer_of_a_stack_and_a_flat_pool_alike():
    rng, pool, tables = _latent_case(seed=2)
    q = jnp.asarray(rng.standard_normal((3, 8, 256), dtype=np.float32),
                    jnp.bfloat16)
    lengths = jnp.asarray([7, 20, 50], jnp.int32)
    stacked = jax.jit(lambda layer: mla_decode_pallas(
        q, pool, tables, lengths, 128, 0.1, layer=layer))(jnp.int32(1))
    flat = mla_decode_pallas(q, pool[1], tables, lengths, 128, 0.1)
    np.testing.assert_array_equal(np.asarray(stacked, np.float32),
                                  np.asarray(flat, np.float32))


def test_mla_shape_gate():
    assert mla_shapes_supported((24, 128, 640), (6, 3073, 64, 640), 512)
    assert mla_shapes_supported((1, 512, 128, 640), (3073, 64, 640), 512)
    assert not mla_shapes_supported((24, 128, 576), (3073, 64, 576), 512)
    assert not mla_shapes_supported((24, 128, 640), (3073, 8, 640), 512)
    assert not mla_shapes_supported((24, 4, 640), (3073, 64, 640), 512)


def test_grouped_matmul_pallas_matches_ragged_dot():
    rng = np.random.default_rng(7)
    lhs = jnp.asarray(rng.standard_normal((32, 64), dtype=np.float32))
    rhs = jnp.asarray(rng.standard_normal((4, 64, 256), dtype=np.float32))
    sizes = jnp.asarray([3, 0, 10, 5], jnp.int32)
    got = grouped_matmul(lhs, rhs, sizes, use_pallas=True)
    want = grouped_matmul(lhs, rhs, sizes, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    assert float(jnp.abs(got[18:]).max()) == 0.0   # rows of no group
    by_hand = np.asarray(lhs[3:13]) @ np.asarray(rhs[2])
    np.testing.assert_allclose(np.asarray(want[3:13]), by_hand, atol=1e-4)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_grouped_matmul_reads_a_layer_of_a_stack_without_slicing_it(
        use_pallas):
    """A stack [L, G, K, N] read at a traced layer equals that layer's own
    product; on the Pallas path the layer is folded into the group sizes
    and the stack only relabelled (the v5e compile of the cell's step,
    `benchmarks/tests/test_aot_mla_moe.py`, shows that no layer is copied)."""
    rng = np.random.default_rng(8)
    lhs = jnp.asarray(rng.standard_normal((16, 64), dtype=np.float32))
    stack = jnp.asarray(rng.standard_normal((3, 4, 64, 128),
                                            dtype=np.float32))
    sizes = jnp.asarray([2, 5, 0, 4], jnp.int32)
    got = jax.jit(lambda layer: grouped_matmul(
        lhs, stack, sizes, use_pallas=use_pallas, layer=layer))(jnp.int32(2))
    want = grouped_matmul(lhs, stack[2], sizes, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
