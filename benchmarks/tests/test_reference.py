"""The plain reference against `models/llama.py` at a tiny size on the CPU
(float32 activations there, so the two agree to rounding), and the control:
the reference with float8 operands comes out as NOT correct, a broken timed
path likewise."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import serving, train, weights
from benchmarks.models import dense_decoder as adapter
from benchmarks.reference import dense_decoder as ref
from benchmarks.tests import tiny

HP = adapter.hyperparams(tiny.TINY_CONFIG, "serve")
SEED = 2 ** 31 + 5
KIND = ref.tables.KIND


def _canonical(round_bf16):
    return weights.canonical(HP, ref.tables, weights.seed_u32(SEED),
                             round_bf16)


def _one_layer(seed, layer):
    return weights.leaves(HP, ref.tables.layer_table(HP, KIND),
                          weights.seed_u32(seed), jnp.uint32(layer), True)


def test_stacked_and_single_layer_weights_agree_bit_for_bit():
    stacked = _canonical(True)["layers"]
    for layer in range(HP["num_hidden_layers"]):
        one = _one_layer(SEED, layer)
        for k in one:
            assert np.array_equal(np.asarray(one[k]),
                                  np.asarray(stacked[k][layer])), k
    w = np.asarray(stacked["q_proj"])
    assert abs(w.std() - 0.05) < 0.005 and abs(w.mean()) < 3e-3
    other = _one_layer(SEED + 1, 0)
    assert not np.array_equal(np.asarray(other["q_proj"]), w[0])


def test_reference_forward_matches_the_program():
    from ray_lightning_tpu.models.llama import Llama

    cfg = adapter.llama_config(tiny.TINY_CONFIG, HP, "serve")
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
    params = adapter.program_tree(HP, weights.seed_u32(SEED), jnp.float32,
                                  True)
    tokens = np.random.default_rng(0).integers(0, 256, (1, 40)).astype(
        np.int32)
    want = Llama(cfg).apply({"params": params}, jnp.asarray(tokens))[0]
    canon = _canonical(True)
    x = ref.embed(canon["globals"], jnp.asarray(tokens[0]))
    for layer in range(HP["num_hidden_layers"]):
        w = jax.tree.map(lambda a: a[layer], canon["layers"])
        x = ref.layer(HP, KIND, w, x)
    got = ref.head_logits(HP, canon["globals"], x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_adapter_round_trip():
    tree = adapter.program_tree(HP, weights.seed_u32(SEED), jnp.float32,
                                False)
    canon = adapter.canonical_from_program(HP, tree)
    want = _canonical(False)
    for group in ("layers", "globals"):
        for k, v in want[group].items():
            assert np.array_equal(np.asarray(canon[group][k]),
                                  np.asarray(v)), k


@pytest.fixture(scope="module")
def three_steps():
    traffic = tiny.TRAFFIC["tiny_train"]
    hp = adapter.hyperparams(tiny.TINY_CONFIG, "train")
    rows = np.random.default_rng(3).integers(0, 256, (3, 2, 33)).astype(
        np.int32)
    dev = jax.devices()[:1]
    sound = train.reference_three_steps(ref, hp, SEED, rows, traffic, dev)
    low = train.reference_three_steps(ref, hp, SEED, rows, traffic, dev,
                                      quant=ref.fp8_operands)
    return traffic, sound, low


def test_training_control_is_not_correct(three_steps):
    traffic, sound, low = three_steps
    limits = traffic["check"]
    same = train.compare(sound, sound)
    assert same["loss_gap"] == same["grad_gap"] == same["delta_gap"] == 0
    cmp = train.compare(low, sound)
    assert (cmp["loss_gap"] > limits["loss_limit"]
            or cmp["grad_gap"] > limits["grad_limit"]
            or cmp["delta_gap"] > limits["delta_limit"]), cmp


def test_a_step_that_returns_its_state_unchanged_is_not_correct(three_steps):
    traffic, sound, _ = three_steps
    broken = dict(sound, delta_norms={k: 0.0 for k in sound["delta_norms"]})
    assert train.compare(broken, sound)["delta_gap"] > \
        traffic["check"]["delta_limit"]


def test_a_part_of_the_batch_left_out_moves_the_loss(three_steps):
    traffic, sound, _ = three_steps
    hp = adapter.hyperparams(tiny.TINY_CONFIG, "train")
    rows = np.random.default_rng(3).integers(0, 256, (3, 2, 33)).astype(
        np.int32)
    half = train.reference_three_steps(ref, hp, SEED, rows[:, :1], traffic,
                                       jax.devices()[:1])
    assert train.compare(half, sound)["loss_gap"] > 1e-3


def _greedy_sample():
    """Greedy decoding by the reference itself: a served stream that IS
    correct, and the same stream with one token altered."""
    rng = np.random.default_rng(1)
    canon = _canonical(True)
    out = []
    for i, n_prompt in enumerate((9, 17, 30)):
        toks = list(rng.integers(0, 256, n_prompt))
        for _ in range(6):
            x = ref.embed(canon["globals"], jnp.asarray(toks, jnp.int32))
            for layer in range(HP["num_hidden_layers"]):
                x = ref.layer(HP, KIND, jax.tree.map(
                    lambda a: a[layer], canon["layers"]), x)
            toks.append(int(jnp.argmax(
                ref.head_logits(HP, canon["globals"], x[-1:])[0])))
        plan = serving.traffic_gen.PlannedRequest(
            rid=f"r{i}", prompt=np.asarray(toks[:n_prompt], np.int32),
            max_new_tokens=6, temperature=0.0, top_k=None, seed=i)
        req = serving._Req(plan, 0.0)
        req.done = 1.0
        out.append((req, toks[n_prompt:]))
    return out


def test_serving_check_passes_sound_tokens_and_fails_an_altered_one():
    sample = _greedy_sample()
    reqs = [r for r, _ in sample]
    outputs = {r.plan.rid: toks for r, toks in sample}
    sound = serving.check_tokens(ref, HP, SEED, reqs, outputs)
    assert sound["widest_gap"] <= 1e-5 and sound["tokens"] == 18
    broken = dict(outputs)
    broken["r1"] = list(broken["r1"])
    broken["r1"][2] = (broken["r1"][2] + 1) % 256
    assert serving.check_tokens(ref, HP, SEED, reqs, broken)["widest_gap"] > \
        tiny.TRAFFIC["tiny_open"]["check"]["gap_limit"]


def test_serving_control_reads_a_wider_gap_than_sound_tokens():
    sample = _greedy_sample()
    reqs = [r for r, _ in sample]
    outputs = {r.plan.rid: toks for r, toks in sample}
    chk = serving.check_tokens(ref, HP, SEED, reqs, outputs,
                               control=ref.fp8_operands)
    assert chk["control_gap"] > 3 * max(chk["widest_gap"], 1e-3)


def test_credited_tokens_spread_a_prompt_over_its_prefill():
    mk = lambda n_prompt, due, times: (lambda r: (setattr(
        r, "times", times), r)[1])(serving._Req(
            serving.traffic_gen.PlannedRequest(
                rid="x", prompt=np.zeros(n_prompt, np.int32),
                max_new_tokens=len(times), temperature=0.0, top_k=None,
                seed=0), due))
    a = mk(100, 0.0, [1.0, 1.5, 2.0])        # prefilled over (0, 1]
    b = mk(200, 0.0, [3.0, 3.5])             # prefilled over (1, 3]
    whole = serving.credited_tokens([a, b], 0.0, 10.0)
    assert whole == pytest.approx(100 + 3 + 200 + 2)
    # a window that ends at 2.0 holds all of a and half of b's prompt
    assert serving.credited_tokens([a, b], 0.0, 2.0) == pytest.approx(
        100 + 3 + 100)
    assert serving.credited_tokens([a, b], 2.0, 10.0) == pytest.approx(
        1 + 100 + 2)
