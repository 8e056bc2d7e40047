"""The decoder with sliding-window and full attention layers and a parallel
attention-and-experts block (`models/window_moe.py`) against its plain
reference (`benchmarks/reference/window_moe_decoder.py`, which imports
nothing of the program) on seeded weights at a tiny size: the full forward
pass, logits through the two-group paged pool at contexts under, at and
several times the window, the eight shares of a layer's experts summed, and
the two forms of the router's choice."""
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
from ray_lightning_tpu.models.held_experts import (  # noqa: E402
    HeldExperts, route,
)
from ray_lightning_tpu.models.mla_moe import MlaMoeConfig  # noqa: E402
from ray_lightning_tpu.models.window_moe import (  # noqa: E402
    WindowMoe, WindowMoeConfig,
)
from ray_lightning_tpu.ops.attention import (  # noqa: E402
    PagedDecodeView, PagedPrefillView,
)
from ray_lightning_tpu.ops.norms import layer_norm  # noqa: E402
from ray_lightning_tpu.serve.kv_cache import (  # noqa: E402
    PagedPoolSpec, init_pool, window_pool_spec, window_ring_table,
)

MODEL = "window_moe_decoder"
SEED = 11
WINDOW = 24

#: the published keys of a tiny twin: one period (three window layers of 24
#: tokens and a full one), 4 query heads over 2 KV heads of 128, 16 experts
#: of which this "chip" holds [8, 16), 4 a token, 2 shared experts
FILE = {
    "model": MODEL, "hidden_size": 64, "num_hidden_layers": 4,
    "layer_switch": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
                   + ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "sliding_window": WINDOW, "intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 4, "num_shared_experts": 2, "vocab_size": 256,
    "layer_norm_eps": 1e-5, "rope_theta": 50000, "logit_scale": 0.5,
    "max_position_as_run": 256, "published": {"num_experts": 16},
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
    canon = weights.canonical(hp, adapter.tables, weights.seed_u32(SEED),
                              True)
    params = adapter.tree_from_canonical(hp, canon, jnp.float32)
    cfg = dataclasses.replace(adapter.program_config(FILE, hp),
                              dtype=jnp.float32)
    return hp, ref, cfg, params, canon


def _reference_logits(ref, hp, tokens):
    return np.asarray(serving.reference_logits(
        ref, hp, SEED, [(tokens, 0, len(tokens))], 128)[0])


def _through_the_cache(cfg, params, tokens, chunk=16, n_prefill=32,
                       block=16, slots=2):
    """Logits of every position: `n_prefill` tokens in chunks through the
    prefill lane of slot 1, the rest one at a time through the decode lane,
    over a two-group pool whose window group is the engine's ring
    (`window_ring_table`), kernels interpreted."""
    model = WindowMoe(cfg)
    m = -(-len(tokens) // block)
    spec = window_pool_spec(PagedPoolSpec(1 + slots * m, block, m),
                            cfg.window, slots, chunk)
    assert spec.window_ring < m, "the context has to wrap the ring"
    pool = init_pool(cfg, spec)
    slot = 1
    table = (1 + slot * m + jnp.arange(m, dtype=jnp.int32))[None]
    toks = jnp.asarray(tokens, jnp.int32)

    @jax.jit
    def prefill(pool, toks, start):
        wpos = start + jnp.arange(chunk)
        ring = window_ring_table(
            spec, slot, jnp.maximum(start - cfg.window + 1, 0),
            start + chunk - 1)
        view = PagedPrefillView(
            tables=table, write_block=table[:, wpos // block],
            write_offset=(wpos % block)[None], window_tables=ring,
            window_write_block=ring[:, wpos // block], use_pallas=True)
        logits, pool, _ = model.apply({"params": params}, toks[None],
                                      cache=pool, pos=start, paged=view)
        return logits[0], pool

    @jax.jit
    def decode(pool, tok, pos):
        at = jnp.asarray([pos])
        ring = window_ring_table(
            spec, slot, jnp.maximum(at + 1 - cfg.window, 0), at)
        view = PagedDecodeView(
            tables=table, lengths=at + 1, write_block=table[:, pos // block][0],
            write_offset=at % block, window_tables=ring,
            window_write_block=ring[:, pos // block][0], use_pallas=True)
        logits, pool, _ = model.apply({"params": params}, tok[None, None],
                                      cache=pool, pos=at, paged=view)
        return logits[0, 0], pool

    out = []
    for start in range(0, n_prefill, chunk):
        logits, pool = prefill(pool, toks[start:start + chunk],
                               jnp.int32(start))
        out.append(logits)
    for pos in range(n_prefill, len(tokens)):
        logits, pool = decode(pool, toks[pos], jnp.int32(pos))
        out.append(logits[None])
    return np.asarray(jnp.concatenate(out, 0))


#: float32 on both sides, the same seeded weights: what is left is the
#: order of float32 sums (online softmax over tiles, the grouped product,
#: the shared experts side by side), about 1e-6 on logits of magnitude
#: 0.2. bfloat16 activations read 1e-3 or more and a window that is not
#: applied 1e-2 (both tested below), so 5e-5 fails each by a wide margin.
LOGIT_TOL = 5e-5


def test_full_forward_matches_the_reference(twin):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(1).integers(0, 256, 80).astype(np.int32)
    full = np.asarray(WindowMoe(cfg).apply({"params": params},
                                           jnp.asarray(tokens)[None])[0])
    want = _reference_logits(ref, hp, tokens)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(full, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("n_prefill,total", [
    (16, 20),      # under the window
    (16, 26),      # decode crosses the window's edge at 24
    (32, 40),      # the second chunk straddles the window's edge
    (64, 80),      # several times the window: the ring has wrapped
], ids=["under", "decode-crosses", "chunk-straddles", "several-times"])
def test_prefill_then_decode_through_the_two_groups_matches_the_reference(
        twin, n_prefill, total):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(total).integers(0, 256, 80).astype(
        np.int32)
    # the pool is sized for 80 tokens (5 blocks against a ring of 4)
    got = _through_the_cache(cfg, params, tokens, n_prefill=n_prefill)[:total]
    want = _reference_logits(ref, hp, tokens[:total])
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault", ["bfloat16", "no_window"])
def test_the_tolerance_fails_a_lower_precision_and_a_window_not_applied(
        twin, fault):
    hp, ref, cfg, params, _ = twin
    tokens = np.random.default_rng(2).integers(0, 256, 80).astype(np.int32)
    want = _reference_logits(ref, hp, tokens)
    model = WindowMoe(cfg)
    if fault == "bfloat16":
        model = WindowMoe(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    else:
        model = WindowMoe(dataclasses.replace(cfg, window=1 << 20))
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray(tokens)[None])[0])
    assert np.abs(got - want).max() > 20 * LOGIT_TOL


def test_layer_norm_subtracts_the_mean():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 64)).astype(np.float32) + 3.0
    g = rng.standard_normal(64).astype(np.float32)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(np.asarray(layer_norm(x, g, 1e-5)), want,
                               atol=2e-6, rtol=0)
    # statistics in float32 whatever the activations' type
    low = layer_norm(jnp.asarray(x, jnp.bfloat16), g, 1e-5)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32), want, atol=0.05)


# ---- the share tied to the model ------------------------------------------------


def test_the_eight_shares_and_the_shared_experts_add_up_to_the_uncut_layer(
        twin):
    """The routed parts of the 8 shares (2 of 16 experts each, the program's
    `HeldExperts`), plus attention and the shared experts counted once (what
    every chip computes alike), add up to the UNCUT reference's layer: all
    16 experts held."""
    hp, ref, cfg, _, _ = twin
    uncut = dict(hp, num_experts=16, experts_first=0)
    kind = ref.tables.WINDOW
    w = weights.leaves(uncut, ref.tables.layer_table(uncut, kind),
                       weights.seed_u32(SEED), 1, True)
    x = 0.5 * jax.random.normal(jax.random.key(3), (40, 64))
    want = np.asarray(ref.layer(uncut, kind, w, x))
    h = ref.layer_norm(x, w["input_layernorm"], hp["layer_norm_eps"])
    alike = x + ref.attention(uncut, kind, w, h, None) + ref.shared_mean(
        w, h, None)
    gate_up = jnp.concatenate([w["experts_gate_proj"], w["experts_up_proj"]],
                              -1)
    routed = jnp.zeros_like(x)
    rows = 0
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, experts_first=first, experts_held=2)
        stacks = (gate_up[None, first:first + 2],
                  w["experts_down_proj"][None, first:first + 2])
        part, counts = HeldExperts(share).apply(
            {"params": {"router": w["gate"]}}, h, stacks, 0, False)
        routed = routed + part
        rows += int(counts[0])
    assert rows == 40 * 4                  # every chosen pair, exactly once
    np.testing.assert_allclose(np.asarray(alike + routed), want, atol=2e-5,
                               rtol=0)
    # one share alone is not the layer
    assert np.abs(np.asarray(alike + part) - want).max() > 1e-3


# ---- the router's two forms -------------------------------------------------------


def _plain_topk(scores, k):
    """A literal transcription: the k largest, ties to the lower index, the
    weights normalised."""
    chosen = np.asarray([sorted(range(len(s)), key=lambda e: (-s[e], e))[:k]
                         for s in scores])
    picked = np.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / picked.sum(-1, keepdims=True)


def test_plain_topk_against_its_transcription_and_against_noaux_tc():
    """`route`'s two forms cannot drift apart: `noaux_tc` with one group
    and a zero bias IS the plain top-k, bit for bit."""
    rng = np.random.default_rng(5)
    scores = 1.0 / (1.0 + np.exp(-rng.standard_normal((64, 16)))).astype(
        np.float32)
    scores[3, 5] = scores[3, 9]            # a tie goes to the lower index
    plain = WindowMoeConfig.tiny()
    assert (plain.expert_choice, plain.n_experts_per_tok) == ("topk", 4)
    experts, weight = route(plain, jnp.asarray(scores))
    chosen, want = _plain_topk(scores, 4)
    np.testing.assert_array_equal(np.asarray(experts), chosen)
    np.testing.assert_allclose(np.asarray(weight), want, rtol=1e-6)
    grouped = MlaMoeConfig.tiny(n_group=1, topk_group=1,
                                routed_scaling_factor=1.0)
    assert grouped.expert_choice == "noaux_tc"
    e2, w2 = route(grouped, jnp.asarray(scores), jnp.zeros(16))
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(experts))
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(weight))


def test_the_plain_router_has_no_bias_parameter(twin):
    _, _, cfg, params, _ = twin
    assert set(params["periods"]["full_layer"]["experts"]) == {"router"}

    class Other:
        expert_choice, n_experts_per_tok = "other", 1

    with pytest.raises(ValueError, match="none of"):
        route(Other(), jnp.zeros((1, 2)))
