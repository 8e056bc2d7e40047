"""The decoder of gated short convolutions, attention at heads of 64 and an
expert layer that may hold every expert (`models/conv_moe.py`) against its
plain reference (`benchmarks/reference/conv_moe_decoder.py`, which imports
nothing of the program) on seeded weights at a tiny size: the full forward
pass; logits through the paged pool and the carried tails, in chunks and a
token at a time; what the tolerance refuses; the shares of a layer's
experts summed; the router's bias; two KV heads a 128-lane row against
attention at heads of 64; and who refuses the decoder by name."""
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
from ray_lightning_tpu.models.conv_moe import (  # noqa: E402
    ConvMoe, ConvMoeConfig,
)
from ray_lightning_tpu.models.held_experts import (  # noqa: E402
    HeldExperts, route,
)
from ray_lightning_tpu.models.mla_moe import MlaMoeConfig  # noqa: E402
from ray_lightning_tpu.ops import attention as attn_ops  # noqa: E402
from ray_lightning_tpu.ops.attention import (  # noqa: E402
    PagedDecodeView, PagedPrefillView,
)
from ray_lightning_tpu.serve.kv_cache import (  # noqa: E402
    PagedPoolSpec, init_pool, state_pool_spec,
)

MODEL = "conv_moe_decoder"
SEED = 11
CONV, FULL = "conv", "full_attention"

#: the published keys of a tiny twin: the published list's shape (two dense
#: convolution layers in front, then periods of an attention layer and
#: convolution layers), run from entry 1 on as the configuration's stage is:
#: conv (dense), attention, conv, conv, attention, conv; 4 query heads over 2
#: KV heads of 64 (ONE paired row); 8 experts, all held, 2 a token
FILE = {
    "model": MODEL, "hidden_size": 128, "num_hidden_layers": 6,
    "num_dense_layers": 1,
    "layer_types": [CONV, CONV, FULL, CONV, CONV, FULL, CONV, CONV],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1, "conv_L_cache": 3,
    "vocab_size": 256, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000},
    "max_position_as_run": 256,
    "published": {"num_experts": 8, "num_dense_layers": 2},
    "deployment": {"layer_first": 1, "experts_first": 0},
    # at 128 columns the hash's std is widened so that no sublayer's output
    # falls under a norm's eps; the taps at the published start (x 4 here)
    "assumed": {"head_dim": 64, "initializer_std": 0.08,
                "conv_init": {"scale": 4}},
}


@pytest.fixture(scope="module")
def twin():
    """(hp, reference module, program config float32, program params
    float32, adapter)."""
    adapter = common.load_model_file(ROOT, "models", MODEL)
    ref = common.load_model_file(ROOT, "reference", MODEL)
    hp = adapter.hyperparams(FILE, "serve")
    # rounded to bfloat16-representable numbers, as the harness hands them
    # to the reference (`serving.reference_logits`)
    canon = weights.canonical(hp, adapter.tables, weights.seed_u32(SEED),
                              True)
    params = adapter.tree_from_canonical(hp, canon, jnp.float32)
    cfg = dataclasses.replace(adapter.program_config(FILE, hp),
                              dtype=jnp.float32)
    return hp, ref, cfg, params, adapter


def _reference_logits(ref, hp, tokens):
    return np.asarray(serving.reference_logits(
        ref, hp, SEED, [(tokens, 0, len(tokens))], 128)[0])


def _through_the_cache(cfg, params, tokens, chunk=16, n_prefill=32,
                       block=16, slots=3, slot=1, pool=None):
    """Logits of every position: `n_prefill` tokens in chunks of `chunk`
    through the prefill lane of `slot` (the last chunk partial where
    `n_prefill` is no whole number of chunks: zeros follow the prompt's
    rows), the rest one at a time through the decode lane with every other
    slot idle, over the attention group and the tails, kernels
    interpreted. Returns (logits, pool)."""
    model = ConvMoe(cfg)
    m = -(-len(tokens) // block)
    spec = state_pool_spec(PagedPoolSpec(1 + slots * m, block, m), True,
                           slots)
    if pool is None:
        pool = init_pool(cfg, spec)
    table = (1 + slot * m + jnp.arange(m, dtype=jnp.int32))[None]
    toks = jnp.asarray(tokens, jnp.int32)

    @jax.jit
    def prefill(pool, toks, start, last):
        wpos = start + jnp.arange(chunk)
        real = jnp.arange(chunk) <= last
        view = PagedPrefillView(
            tables=table,
            write_block=jnp.where(real, table[0, wpos // block], 0)[None],
            write_offset=(wpos % block)[None], state_slot=jnp.int32(slot),
            real_rows=jnp.stack([jnp.int32(0), last]), use_pallas=True)
        logits, pool, counts = model.apply(
            {"params": params}, toks[None], cache=pool, pos=start,
            paged=view)
        return logits[0], pool, counts

    @jax.jit
    def decode(pool, tok, pos):
        mine = jnp.arange(slots) == slot
        at = jnp.where(mine, pos, 0)
        tables = jnp.where(mine[:, None], table, 0)
        view = PagedDecodeView(
            tables=tables, lengths=jnp.where(mine, pos + 1, 0),
            write_block=jnp.where(mine, table[0, pos // block], 0),
            write_offset=at % block, state_moves=mine, use_pallas=True)
        logits, pool, counts = model.apply(
            {"params": params}, jnp.where(mine, tok, 0)[:, None],
            cache=pool, pos=at, paged=view)
        return logits[slot, 0], pool, counts

    out, counted = [], []
    for start in range(0, n_prefill, chunk):
        rows = min(chunk, n_prefill - start)
        piece = jnp.zeros((chunk,), jnp.int32).at[:rows].set(
            toks[start:start + rows])
        logits, pool, counts = prefill(pool, piece, jnp.int32(start),
                                       jnp.int32(rows - 1))
        out.append(logits[:rows])
        counted.append(np.asarray(counts))
    for pos in range(n_prefill, len(tokens)):
        logits, pool, counts = decode(pool, toks[pos], jnp.int32(pos))
        out.append(logits[None])
        counted.append(np.asarray(counts))
    return np.asarray(jnp.concatenate(out, 0)), pool, counted


#: float32 on both sides, the same seeded weights: what is left is the
#: order of float32 sums (online softmax over tiles, the grouped product,
#: the one-hot gather), about 1e-6 on logits of magnitude one. bfloat16
#: activations read 1e-2, and a bias, a gate or a tap left out 1e-2 to 1
#: (all tested below), so 5e-5 fails each by a wide margin.
LOGIT_TOL = 5e-5


def test_full_forward_matches_the_reference(twin):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(1).integers(0, 256, 80).astype(np.int32)
    full = np.asarray(ConvMoe(cfg).apply({"params": params},
                                         jnp.asarray(tokens)[None])[0])
    want = _reference_logits(ref, hp, tokens)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(full, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("n_prefill,total", [
    (16, 20),      # one chunk, then the decode lane reads its tail
    (32, 40),      # the second chunk's first two rows read the first's tail
    (21, 30),      # a partial last chunk: zeros follow 5 real rows
    (17, 24),      # ONE real row in the last chunk: the tail keeps a row of
                   # the chunk before
    (64, 80),      # four chunks over four pool blocks
], ids=["one-chunk", "two-chunks", "partial", "one-row", "four-chunks"])
def test_prefill_then_decode_through_pool_and_tails_matches_the_reference(
        twin, n_prefill, total):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(total).integers(0, 256, 80).astype(
        np.int32)
    got, _, counted = _through_the_cache(cfg, params, tokens[:total],
                                         n_prefill=n_prefill)
    want = _reference_logits(ref, hp, tokens[:total])
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # the device-side counts of a call: [expert_rows, expert_rows_max,
    # hit words.., conv_rows, state_slots]
    first, last = counted[0], counted[-1]
    assert first[0] == 16 * 2 * cfg.n_expert_layers        # every pair real
    assert first[-2:].tolist() == [min(16, n_prefill), 0]
    assert last[0] == 3 * 2 * cfg.n_expert_layers          # idle slots too
    assert last[-2:].tolist() == [0, 1]
    assert len(first) == 4 + cfg.hit_words == 6


def test_a_slot_reused_after_another_request_starts_from_zeros(twin):
    """The second request's first chunk starts at position 0: whatever the
    slot's tails and blocks hold of the first is never read."""
    hp, ref, cfg, params, _ = twin
    rng = np.random.default_rng(7)
    one = rng.integers(0, 256, 40).astype(np.int32)
    two = rng.integers(0, 256, 30).astype(np.int32)
    # the same pool geometry for both (5 blocks a slot)
    _, pool, _ = _through_the_cache(cfg, params, np.resize(one, 80)[:80],
                                    n_prefill=32)
    assert float(jnp.abs(pool[2][:, 1]).max()) > 0    # slot 1's tails moved
    got, _, _ = _through_the_cache(
        cfg, params, np.concatenate([two, np.zeros(50, np.int32)])[:80],
        n_prefill=16, pool=pool)
    want = _reference_logits(ref, hp, two)
    np.testing.assert_allclose(got[:30], want, atol=LOGIT_TOL, rtol=0)


def _swap_gates(params):
    """`in_proj`'s C and u parts swapped: the gate in the wrong place."""
    def leaf(path, x):
        if path[-1].key != "in_proj":
            return x
        b, c, u = jnp.split(x, 3, axis=-1)
        return jnp.concatenate([b, u, c], -1)

    return jax.tree_util.tree_map_with_path(leaf, params)


def _edit(params, name, fn):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: fn(x) if path[-1].key == name else x, params)


@pytest.mark.parametrize("fault", ["bfloat16", "float8", "no_bias",
                                   "no_tap", "gate_misplaced",
                                   "no_qk_norm_gain"])
def test_the_tolerance_fails_a_lower_precision_and_a_part_left_out(
        twin, fault):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(2).integers(0, 256, 80).astype(np.int32)
    want = _reference_logits(ref, hp, tokens)
    if fault == "float8":
        got = np.asarray(serving.reference_logits(
            ref, hp, SEED, [(tokens, 0, len(tokens))], 128,
            quant=ref.fp8_operands)[0])
    else:
        model, p = ConvMoe(cfg), params
        if fault == "bfloat16":
            model = ConvMoe(dataclasses.replace(cfg, dtype=jnp.bfloat16))
        elif fault == "no_bias":
            p = _edit(params, "router_bias", jnp.zeros_like)
        elif fault == "no_tap":
            p = _edit(params, "conv_weight", lambda w: w.at[:, 0].set(0.0))
        elif fault == "gate_misplaced":
            p = _swap_gates(params)
        else:
            p = _edit(params, "q_norm", lambda g: 0.5 * g)
        got = np.asarray(model.apply({"params": p},
                                     jnp.asarray(tokens)[None])[0])
    assert np.abs(got - want).max() > 20 * LOGIT_TOL


# ---- the share tied to the model ------------------------------------------------


def _expert_layer(twin, layer=2):
    hp, ref = twin[:2]
    kind = ref.tables.layer_kinds(hp)[layer]
    assert kind == ref.tables.CONV
    w = weights.leaves(hp, ref.tables.layer_table(hp, kind),
                       weights.seed_u32(SEED), layer, True)
    x = 0.5 * jax.random.normal(jax.random.key(3), (40, 128))
    return kind, ref.tables.seeded(hp, kind, w), x


@pytest.mark.parametrize("held", [8, 2], ids=["all-held", "four-shares"])
def test_the_shares_add_up_to_the_uncut_layer(twin, held):
    """The routed parts of the shares (all 8 experts held alone, as the
    configuration runs them; or 4 shares of 2, the program's `HeldExperts`
    told which), plus the convolution counted once (what every chip computes
    alike), add up to the UNCUT reference's layer."""
    hp, ref, cfg, _, _ = twin
    kind, w, x = _expert_layer(twin)
    want = np.asarray(ref.layer(hp, kind, dict(w, conv_unit=w[
        "conv_weight"] / hp["conv_init_scale"]), x))
    y = ref.rms_norm(x, w["operator_norm"], hp["norm_eps"])
    h = x + ref.short_conv(hp, w, y, None)
    z = ref.rms_norm(h, w["ffn_norm"], hp["norm_eps"])
    gate_up = jnp.concatenate([w["experts_gate_proj"], w["experts_up_proj"]],
                              -1)
    routed, rows, hit = jnp.zeros_like(x), 0, []
    for first in range(0, 8, held):
        share = dataclasses.replace(cfg, experts_first=first,
                                    experts_held=held)
        stacks = (gate_up[None, first:first + held],
                  w["experts_down_proj"][None, first:first + held])
        part, counts, hits = HeldExperts(share, with_hits=True).apply(
            {"params": {"router": w["gate"],
                        "router_bias": w["expert_bias"]}},
            z, stacks, 0, False)
        routed = routed + part
        rows += int(counts[0])
        hit += np.asarray(hits).tolist()
    assert rows == 40 * 2                  # every chosen pair, exactly once
    assert len(hit) == 8 and sum(hit) >= 2
    np.testing.assert_allclose(np.asarray(h + routed), want, atol=2e-5,
                               rtol=0)
    if held < 8:
        # one share alone is not the layer
        assert np.abs(np.asarray(h + part) - want).max() > 1e-3


def test_a_row_whose_choice_the_bias_changes_keeps_its_unbiased_weight(twin):
    """`route` at ONE group: the 2 largest of score + bias choose, and the
    weights are the chosen UNBIASED scores over their sum + 1e-6 (the
    published epsilon, the configuration's `route_norm_eps`)."""
    hp, ref, cfg, _, _ = twin
    assert (cfg.expert_choice, cfg.n_group, cfg.route_norm_eps) == (
        "noaux_tc", 1, 1e-6)
    rng = np.random.default_rng(5)
    scores = (1.0 / (1.0 + np.exp(-rng.standard_normal((64, 8))))).astype(
        np.float32)
    bias = (0.3 * rng.standard_normal(8)).astype(np.float32)
    experts, weight = route(cfg, jnp.asarray(scores), jnp.asarray(bias))
    plain, _ = route(cfg, jnp.asarray(scores), jnp.zeros(8))
    experts, weight = np.asarray(experts), np.asarray(weight)
    moved = (np.sort(experts, -1) != np.sort(np.asarray(plain), -1)).any(-1)
    assert 5 < moved.sum() < 64            # the bias decides on some rows
    for t in np.flatnonzero(moved)[:8]:
        order = sorted(range(8), key=lambda e: (-(scores[t, e] + bias[e]), e))
        assert experts[t].tolist() == order[:2]
        picked = scores[t, order[:2]]
        np.testing.assert_allclose(weight[t], picked / (picked.sum() + 1e-6),
                                   rtol=1e-6)
    # the reference's transcription chooses and weighs alike
    chosen, w_ref = ref.route(hp, jnp.asarray(scores), jnp.asarray(bias))
    np.testing.assert_array_equal(np.asarray(chosen), experts)
    np.testing.assert_allclose(np.asarray(w_ref), weight, rtol=1e-6)


def test_the_normalisers_epsilon_is_the_configurations_own():
    """A configuration that names none keeps 1e-20: what longdocs and
    ragdocs compute is what they computed."""
    scores = jnp.full((1, 16), 1e-7, jnp.float32)
    grouped = MlaMoeConfig.tiny(n_group=1, topk_group=1,
                                routed_scaling_factor=1.0)
    assert not hasattr(grouped, "route_norm_eps")
    _, w = route(grouped, scores, jnp.zeros(16))
    np.testing.assert_allclose(np.asarray(w).sum(), 1.0, rtol=1e-6)
    tiny = ConvMoeConfig.tiny(n_routed_experts=16, n_experts_per_tok=8)
    _, w = route(tiny, scores, jnp.zeros(16))
    np.testing.assert_allclose(np.asarray(w).sum(), 8e-7 / (8e-7 + 1e-6),
                               rtol=1e-5)


# ---- the grouped product over 64 groups of a stacked layer -----------------------


def test_the_grouped_product_at_a_width_of_three_tiles_of_128():
    """64 groups over 512 rows, some empty, of the second layer of a stack,
    contracting over 384 columns (three tiles of 128, as the experts' 1536 is
    twelve): the Pallas product, interpreted, against `lax.ragged_dot`."""
    from ray_lightning_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(0)
    sizes = rng.multinomial(500, np.ones(64) / 64).astype(np.int32)
    sizes[[3, 17, 40]] = 0
    lhs = jnp.asarray(rng.standard_normal((512, 384)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((2, 64, 384, 256)), jnp.float32)
    got = grouped_matmul(lhs, rhs, jnp.asarray(sizes), use_pallas=True,
                         layer=1)
    want = grouped_matmul(lhs, rhs, jnp.asarray(sizes), use_pallas=False,
                          layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=1e-5)
    assert not np.asarray(got[int(sizes.sum()):]).any()


# ---- two KV heads a 128-lane row --------------------------------------------------


def _paged_case(rng, h=8, hkv=4, hd=64, block=16, m=4, slots=3):
    n_blocks = 1 + slots * m
    pool = lambda: jnp.asarray(rng.standard_normal(
        (2, n_blocks, block, hkv, hd)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(slots * m).reshape(slots, m),
                         jnp.int32)
    return pool(), pool(), tables


@pytest.mark.parametrize("lane", ["decode", "prefill"])
@pytest.mark.parametrize("kernel", [True, False], ids=["pallas", "xla"])
def test_two_heads_a_row_is_attention_at_heads_of_64(lane, kernel):
    """Heads of 64 laid two a 128-lane row (queries zero-padded into their
    half, outputs read back from it, the model's scale) through the paged
    dispatch with a window of None, against the XLA reference at heads of 64
    over the same pool unpaired."""
    rng = np.random.default_rng(0)
    h, hkv, hd = 8, 4, 64
    pk, pv, tables = _paged_case(rng, h, hkv, hd)
    paired = (attn_ops.pair_kv_heads(pk), attn_ops.pair_kv_heads(pv))
    assert paired[0].shape[-2:] == (2, 128)
    kw = dict(scale=hd ** -0.5, layer=1, window=None)
    if lane == "decode":
        q = jnp.asarray(rng.standard_normal((3, h, hd)), jnp.float32)
        lengths = jnp.asarray([37, 0, 64], jnp.int32)
        want = attn_ops.paged_attention_reference(q, pk, pv, tables, lengths,
                                                  **kw)
        got = attn_ops.paged_attention(
            attn_ops.pair_query_heads(q, hkv), *paired, tables, lengths,
            use_pallas=kernel, **kw)
    else:
        q = jnp.asarray(rng.standard_normal((1, 16, h, hd)), jnp.float32)
        want = attn_ops.paged_prefill_reference(q, pk, pv, tables[:1], 32,
                                                **kw)
        got = attn_ops.paged_prefill(
            attn_ops.pair_query_heads(q, hkv), *paired, tables[:1], 32,
            use_pallas=kernel, **kw)
    assert got.shape[-1] == 128
    np.testing.assert_allclose(np.asarray(attn_ops.unpair_heads(got, hkv)),
                               np.asarray(want), atol=2e-6, rtol=0)
    # the other half of a head's 128 is its PAIR's values: not its own
    assert np.abs(np.asarray(got[..., :hd] - got[..., hd:])).max() > 0.1


def test_the_pool_keeps_a_pair_of_heads_a_row_and_the_same_bytes():
    cfg = ConvMoeConfig(layer_types=(CONV, FULL), n_dense_layers=1)
    assert cfg.pairs_heads and cfg.kv_row == (4, 128)
    kv, _, tails = cfg.pool_leaf_shapes(2177, 128, state_slots=128)
    assert kv == (1, 2177, 128, 4, 128)
    assert tails == (1, 128, 2, 16, 128)
    # a cached token of one layer: 8 heads of 64, K and V, bfloat16
    assert 2 * 4 * 128 * 2 == 2 * 8 * 64 * 2 == 2048
    wide = dataclasses.replace(cfg, head_dim=128)
    assert not wide.pairs_heads and wide.kv_row == (8, 128)
    model = ConvMoe(cfg)
    # the kernels are asked about what they will see: heads of 128, 4 rows
    assert model.prefill_tile_shape(1, 1024, 128, 17) == (128, 512)
    assert model.decode_tile_tokens(128, 17) == 128


# ---- who refuses the decoder, and how ---------------------------------------------


def test_the_static_audit_and_the_cli_refuse_the_decoder_by_name():
    """`serve/audit.py` builds `Llama` itself (ROADMAP Queue 2 mechanism 8):
    another decoder's configuration is a ValueError that names its type,
    not a traceback from inside `Llama`; the CLI's presets are a closed
    list."""
    from ray_lightning_tpu.__main__ import main
    from ray_lightning_tpu.serve import audit
    from ray_lightning_tpu.serve.engine import EngineConfig

    cfg = ConvMoeConfig.tiny()
    ecfg = EngineConfig(capacity=2, block_size=16, blocks_per_slot=4,
                        prefill_chunk=16)
    for call in (lambda: audit.trace_decode_step(cfg, ecfg),
                 lambda: audit.serve_memory_summary(cfg, ecfg),
                 lambda: audit.audit_decode_step(cfg, ecfg),
                 lambda: audit._param_count(cfg)):
        with pytest.raises(ValueError, match="'ConvMoeConfig'"):
            call()
    with pytest.raises(SystemExit):
        main(["serve", "conv_moe"])
