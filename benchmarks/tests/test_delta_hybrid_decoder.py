"""The architecture `delta_hybrid_decoder` (gated-delta-rule layers with a
full-attention layer a period) as the benchmark sees it: its tables' leaves
and ids, the leaves the hash cannot make, its counts at the published sizes,
its configuration's file against the catalog's row, the adapter against the
plain reference, the reference's control, the delta rule's work function,
and its tiny twin through `run.py` on the CPU beside the throw-away cells of
`tests/tiny.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import common, serving, shapes_delta, weights
from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run

ROOT = tiny.ROOT
MODEL = "delta_hybrid_decoder"
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "Olmo-Hybrid-7B.json")
LINEAR, FULL = "linear_attention", "full_attention"

TINY_DELTA = {
    "name": "tinydelta", "source": "none: a test fixture", "model": MODEL,
    "hidden_size": 64, "num_hidden_layers": 4,
    # the published list is kept whole: the layers run are its first four
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 2,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "intermediate_size": 96, "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 16,
    "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "attention_bias": False,
    "tie_word_embeddings": False, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "max_position_as_run": 256, "reduced": [],
    # at 64 columns the hashed leaves are made wide enough that no
    # sublayer's output falls under a norm's eps and beta leaves 1
    "assumed": {"head_dim": 128, "initializer_std": 0.1,
                "delta_init": {"a_max": 16.0, "dt_min": 0.001,
                               "dt_max": 0.1, "beta_gain": 1.0,
                               "a_gain": 0.125}},
}
ENGINE = {"capacity": 4, "block_size": 16, "blocks_per_slot": 8,
          "n_blocks": 33, "prefill_chunk": 16, "prefill_batch": 1}
TRAFFIC = dict(
    tiny.TRAFFIC["tiny_closed"], engine=ENGINE, require_pallas=True,
    prompt_len={"dist": "bounded_pareto", "lo": 20, "hi": 100, "alpha": 1.2},
    check={"n_requests": 4, "gap_limit": 0.05})


def _hp(config=TINY_DELTA):
    adapter = common.load_model_file(ROOT, "models", MODEL)
    return adapter, adapter.hyperparams(config, "serve")


# ---- tables -------------------------------------------------------------------


def test_layer_kinds_and_leaf_ids():
    adapter, hp = _hp()
    t = adapter.tables
    assert t.layer_kinds(hp) == [LINEAR, LINEAR, LINEAR, FULL]
    ids = lambda table: {k: v["id"] for k, v in table.items() if "id" in v}
    # an id is part of the values' key: these never change
    mlp = {"gate_proj": 720, "up_proj": 721, "down_proj": 722}
    assert ids(t.layer_table(hp, LINEAR)) == {
        **mlp, "q_proj": 700, "k_proj": 701, "v_proj": 702, "g_proj": 703,
        "o_proj": 704, "b_proj": 705, "a_proj": 706, "q_conv1d_weight": 707,
        "k_conv1d_weight": 708, "v_conv1d_weight": 709, "a_unit": 710,
        "dt_bias_unit": 711}
    assert ids(t.layer_table(hp, FULL)) == {
        **mlp, "q_proj": 712, "k_proj": 713, "v_proj": 714, "o_proj": 715}
    assert ids(t.global_table(hp)) == {"embed_tokens": 730, "lm_head": 731}
    table = t.layer_table(hp, LINEAR)
    assert table["q_proj"]["shape"] == (64, 32)
    assert table["g_proj"]["shape"] == (64, 64)
    assert table["v_conv1d_weight"]["shape"] == (4, 64)
    assert table["o_norm"] == {"fill": 1.0, "shape": (32,)}
    assert t.layer_table(hp, FULL)["q_norm"]["shape"] == (256,)
    with pytest.raises(ValueError, match="no layer kind"):
        t.layer_table(hp, "window")
    with pytest.raises(ValueError, match="does not give"):
        t.layer_kinds(dict(hp, num_hidden_layers=9))
    with pytest.raises(ValueError, match="a value head a key head"):
        t.layer_table(dict(hp, linear_num_key_heads=1), LINEAR)


def test_the_leaves_the_hash_cannot_make():
    """`seeded`: A_log = log(a), a uniform in (0, 16]; the step's bias the
    inverse softplus of a step log-uniform in [dt_min, dt_max]; W_b and W_a
    times their gains; from one layer's leaves and from a stack alike, in
    numpy and in jax.numpy."""
    adapter, hp = _hp(common.load_json(CONFIG))
    t = adapter.tables
    half = 0.02 * 3 ** 0.5
    unit = np.linspace(-half, half, 30, dtype=np.float32)
    proj = np.full((3840, 30), half, np.float32)
    w = t.seeded(hp, LINEAR, {"a_unit": unit, "dt_bias_unit": unit,
                              "b_proj": proj, "a_proj": proj, "o_norm": 1.0},
                 np)
    assert set(w) == {"A_log", "dt_bias", "b_proj", "a_proj", "o_norm"}
    a = np.exp(w["A_log"])
    np.testing.assert_allclose(a[[0, 15, -1]], [16 / 1024, 16 * 15 / 29, 16],
                               rtol=2e-3)
    step = np.logaddexp(w["dt_bias"], 0.0)               # softplus
    np.testing.assert_allclose(step[[0, -1]], [1e-3, 1e-1], rtol=1e-3)
    # alpha a row: a head that forgets in a few rows, one that remembers
    assert np.exp(-16 * 1e-1) < 0.21 and np.exp(-(16 / 1024) * 1e-3) > 0.99998
    np.testing.assert_array_equal(w["b_proj"], proj * 0.25)
    np.testing.assert_array_equal(w["a_proj"], proj * 0.125)
    stack = t.seeded(hp, LINEAR, {
        "a_unit": jnp.asarray(np.stack([unit, unit[::-1]])),
        "dt_bias_unit": jnp.asarray(np.stack([unit, unit[::-1]]))}, jnp)
    assert stack["A_log"].shape == (2, 30)
    np.testing.assert_allclose(np.asarray(stack["dt_bias"][1]),
                               w["dt_bias"][::-1], rtol=1e-5)
    # another kind passes through untouched
    same = {"q_proj": unit}
    assert t.seeded(hp, FULL, same, np) is same


def test_counts_at_the_published_sizes():
    config = common.load_json(CONFIG)
    adapter, hp = _hp(config)
    t = adapter.tables
    kinds = t.layer_kinds(hp)
    assert [i for i, k in enumerate(kinds) if k == FULL] == [3, 7, 11, 15]
    assert (kinds.count(LINEAR), len(kinds)) == (12, 16)
    assert t.linear_params(hp) == 88_750_332
    assert t.full_params(hp) == 58_990_080
    assert t.mlp_params(hp) == 126_812_160
    assert t.layer_params(hp, LINEAR) == 215_570_172
    assert t.layer_params(hp, FULL) == 185_809_920
    assert t.held_params(hp) == 4_100_788_944 == \
        config["bytes_on_chip"]["parameters"]
    # all 32 published layers: the card's 7B
    assert t.held_params(hp, config["layer_types"]) == 7_430_870_688
    assert t.float32_params(hp) == 12 * 60
    assert 2 * t.held_params(hp) + 2 * t.float32_params(hp) == \
        config["bytes_on_chip"]["serve_weights"] == 8_201_579_328
    assert t.attention_dims(hp) == {"heads": 30, "kv_heads": 30,
                                    "head_dim": 128}
    assert (t.attention_layers(hp), t.delta_layers(hp)) == (4, 12)
    assert t.delta_dims(hp) == {"heads": 30, "d_k": 96, "d_v": 192}
    assert t.matmul_params(hp) == 3_714_723_840 < t.held_params(hp)
    # the cut that would not train: a period and an eighth of the
    # vocabulary at both ends, 14.9 GB at 16 bytes
    period = 3 * t.layer_params(hp, LINEAR) + t.layer_params(hp, FULL)
    assert period == 832_520_436
    small = period + 2 * (100_352 // 8) * 3840 + 3840
    assert abs(small * 16 / 1e9 - 14.9) < 0.05


def test_the_configuration_is_the_catalogs_row_cut_by_depth_alone():
    config = common.load_json(CONFIG)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == config["name"])
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config[k] != v] == [
        "num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["num_hidden_layers"] == 16
    for key in ("assumed", "precision", "why_no_training", "bytes_on_chip",
                "deployment", "reduced_why"):
        assert config[key], key
    for key in ("head_dim", "norm_placement", "positional_encoding",
                "linear_mixer", "delta_init", "delta_init_why"):
        assert config["assumed"][key], key
    assert config["assumed"]["head_dim"] == \
        row["config"]["hidden_size"] // row["config"]["num_attention_heads"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"]
                     if c["name"] == config["name"])
    assert (entry["source"], entry["reduced"]) == (
        config["source"], ["num_hidden_layers"])


def test_beta_covers_both_sides_of_one_at_the_published_width():
    """The seeded `b_proj` against a residual stream of the size the
    family's norms give it behind the first layer (a unit-size vector added
    a sublayer: 1.4 after one layer, 5.6 after fifteen)."""
    adapter, hp = _hp(common.load_json(CONFIG))
    t = adapter.tables
    w = jax.jit(lambda: t.seeded(hp, LINEAR, weights.leaves(
        hp, {k: v for k, v in t.layer_table(hp, LINEAR).items()
             if k in ("b_proj", "a_proj", "a_unit", "dt_bias_unit")},
        weights.seed_u32(7), jnp.uint32(1), True), jnp))()
    rng = np.random.default_rng(0)
    for size, lo, hi in ((1.4, 0.75, 1.25), (5.6, 0.3, 1.7)):
        x = rng.standard_normal((256, 3840)).astype(np.float32) * size
        beta = 2 / (1 + np.exp(-x @ np.asarray(w["b_proj"])))
        assert beta.min() < lo and beta.max() > hi, (size, beta.min(),
                                                     beta.max())
        # the step stays within a factor of e^2 of its bias
        swing = x @ np.asarray(w["a_proj"])
        assert np.abs(swing).max() < 4.0 and np.std(swing) < 1.0
    # without the gain beta would sit at 0 or 2 in the deep layers
    raw = 2 / (1 + np.exp(-x @ (np.asarray(w["b_proj"]) * 4)))
    assert np.mean((raw < 0.1) | (raw > 1.9)) > 0.5


# ---- the adapter against the reference -------------------------------------------


def test_the_adapters_tree_is_the_programs():
    adapter, hp = _hp()
    cfg, params = adapter.serving_params(TINY_DELTA, hp, 5)
    assert (cfg.full_period, cfg.n_kv_heads, cfg.kv_heads_held,
            cfg.conv_channels, cfg.head_dim) == (4, 2, 8, 128, 128)
    assert set(params) == {"tok_embed", "final_norm", "lm_head", "period_0"}
    per = params["period_0"]
    assert per["linear"]["in_proj"].shape == (3, 64, 128)
    assert per["linear"]["gate_proj"].shape == (3, 64, 64)
    assert per["linear"]["conv_weight"].shape == (3, 4, 128)
    assert per["linear"]["ba_proj"].shape == (3, 64, 4)
    assert per["linear"]["a_log"].shape == (3, 2)
    assert per["linear"]["a_log"].dtype == jnp.float32
    assert per["linear"]["dt_bias"].dtype == jnp.float32
    assert per["linear"]["out_proj"].dtype == jnp.bfloat16
    assert per["full_layer"]["wk"].shape == (64, 256)
    assert per["full_layer"]["q_norm"].shape == (256,)
    assert per["full_layer"]["gate_up"].shape == (64, 192)
    assert params["lm_head"].shape == (64, 256)
    odd = dict(TINY_DELTA, layer_types=[LINEAR, FULL, LINEAR, LINEAR])
    with pytest.raises(common.BenchError, match="whole periods"):
        adapter.program_config(odd, adapter.hyperparams(odd, "serve"))
    with pytest.raises(common.BenchError, match="untied"):
        adapter.program_config(TINY_DELTA, dict(hp, tie_word_embeddings=True))
    with pytest.raises(common.BenchError, match="serving configuration"):
        adapter.hyperparams(TINY_DELTA, "train")


def _both(seed=5, n=64):
    """(program logits in float32, in bfloat16, the reference's, the
    control's) over one seeded sequence."""
    import dataclasses

    from ray_lightning_tpu.models.delta_hybrid import DeltaHybrid

    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    t = ref.tables
    s32 = weights.seed_u32(seed)
    ws = [weights.leaves(hp, t.layer_table(hp, k), s32, jnp.uint32(i), True)
          for i, k in enumerate(t.layer_kinds(hp))]
    g = weights.leaves(hp, t.global_table(hp), s32, 0, True)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, n),
                       jnp.int32)
    cfg, params = adapter.serving_params(TINY_DELTA, hp, seed)
    p32 = jax.jit(lambda s: adapter.program_tree(hp, s, jnp.float32, True))(
        s32)
    run = lambda c, p: DeltaHybrid(c).apply({"params": p}, toks[None])[0]
    return (run(dataclasses.replace(cfg, dtype=jnp.float32), p32),
            run(cfg, params), ref.forward(hp, ws, g, toks),
            ref.forward(hp, ws, g, toks, quant=ref.fp8_operands))


def test_the_program_is_the_reference_and_float8_is_not(monkeypatch):
    """The same seeded values through the program's layout and kernels (the
    delta rule in chunks, interpreted) and through the plain reference (row
    by row): float32 against float32 agrees to rounding; the served bfloat16
    is several times nearer the reference than the float8 control is."""
    monkeypatch.setenv("RLT_PALLAS", "1")
    f32, bf16, want, low = _both()
    err = lambda x: float(jnp.max(jnp.abs(x - want)))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    # float32 to rounding, on logits of size 3
    assert err(f32) < 1e-4
    assert err(bf16) < 0.3
    assert err(low) > 5 * err(bf16)


def test_prefill_in_chunks_then_decode_through_the_cache_is_the_reference(
        monkeypatch):
    """The program in float32 through its paged paths (the adapter's
    `program_logits`: chunks of 16 through the pool and the state) against
    the reference's one pass."""
    import dataclasses

    monkeypatch.setenv("RLT_PALLAS", "1")
    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    t = ref.tables
    seed, s32 = 9, weights.seed_u32(9)
    toks = np.random.default_rng(1).integers(0, 256, 64).astype(np.int32)
    ws = [weights.leaves(hp, t.layer_table(hp, k), s32, jnp.uint32(i), True)
          for i, k in enumerate(t.layer_kinds(hp))]
    g = weights.leaves(hp, t.global_table(hp), s32, 0, True)
    want = ref.forward(hp, ws, g, jnp.asarray(toks))
    got = adapter.program_logits(TINY_DELTA, hp, seed, toks, 16, block=16)
    # served bfloat16, chunk by chunk through the cache
    assert float(jnp.max(jnp.abs(got - want))) < 0.3
    # and in float32 to rounding: the same path with float32 parameters
    real = adapter.serving_params

    def f32_params(config, hp_, seed_):
        cfg, _ = real(config, hp_, seed_)
        p32 = jax.jit(lambda s: adapter.program_tree(
            hp_, s, jnp.float32, True))(weights.seed_u32(seed_))
        return dataclasses.replace(cfg, dtype=jnp.float32), p32

    monkeypatch.setattr(adapter, "serving_params", f32_params)
    got = adapter.program_logits(TINY_DELTA, hp, seed, toks, 16, block=16)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_control_with_float8_operands_reads_far_over_the_limit():
    tokens = np.random.default_rng(0).integers(0, 256, 96).astype(np.int32)
    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    seqs = [(tokens, 0, len(tokens))]
    sound = serving.reference_logits(ref, hp, 3, seqs, 128)[0]
    low = serving.reference_logits(ref, hp, 3, seqs, 128,
                                   quant=ref.fp8_operands)[0]
    first = jnp.argmax(low, axis=-1)
    gap = jnp.max(sound, axis=-1) - jnp.take_along_axis(
        sound, first[:, None], axis=-1)[:, 0]
    assert float(jnp.max(gap)) > 3 * TRAFFIC["check"]["gap_limit"], gap


def test_the_references_recurrence_is_causal_and_row_by_row():
    """A later row never moves an earlier one, and each row is the update
    written out by hand."""
    ref = common.load_model_file(ROOT, "reference", MODEL)
    rng = np.random.default_rng(2)
    s, h, dk, dv = 24, 2, 8, 16
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q, k = unit(rng.standard_normal((2, s, h, dk)))
    v = rng.standard_normal((s, h, dv))
    alpha, beta = rng.uniform(0.5, 1.0, (s, h)), rng.uniform(0, 2, (s, h))
    args = [jnp.asarray(x, jnp.float32) for x in (q, k, v, alpha, beta)]
    whole = np.asarray(ref.recurrence(*args))
    state = np.zeros((h, dk, dv))
    for t_ in range(s):
        state = alpha[t_][:, None, None] * state
        corr = beta[t_][:, None] * (v[t_] - np.einsum("hkv,hk->hv", state,
                                                      k[t_]))
        state = state + k[t_][:, :, None] * corr[:, None]
        np.testing.assert_allclose(
            whole[t_], np.einsum("hkv,hk->hv", state, q[t_]), atol=2e-5)
    v2 = v.copy()
    v2[15:] += 1.0
    moved = ref.recurrence(args[0], args[1], jnp.asarray(v2, jnp.float32),
                           *args[3:])
    np.testing.assert_array_equal(np.asarray(moved)[:15], whole[:15])


# ---- the delta rule's work function ----------------------------------------------


def test_the_delta_rules_work_counts_steps_rows_and_the_state():
    dims = dict(heads=30, d_k=96, d_v=192)
    work = shapes_delta.gated_delta(2048, 1, **dims)
    assert work["flops"] == 2048 * 30 * (7 * 96 * 192 + 6 * 96 + 9 * 192)
    assert work["bytes"] == (2048 * 30 * (2 * 96 + 3 * 192) * 2
                             + 2 * 2048 * 30 * 4 + 2 * 30 * 96 * 192 * 4)
    # one row a sequence: the state's bytes are all but everything
    one = shapes_delta.gated_delta(16, 16, **dims)
    assert one["bytes"] > 0.98 * 2 * 16 * 30 * 96 * 192 * 4
    # against the table's peaks a chunk's recurrence is bound by bytes
    from benchmarks.harness import shapes

    peaks = common.load_json(os.path.join(
        ROOT, "benchmarks", "peaks.json"))["device_kinds"]["TPU v5 lite"]
    assert shapes.roofline_seconds(work, peaks)["bound"] == "memory"


# ---- the tiny twin through run.py ----------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.build(str(tmp_path_factory.mktemp("bench_delta")))
    bdir = os.path.join(tmp, "benchmarks")
    with open(os.path.join(bdir, "configs", "tinydelta.json"), "w") as fh:
        json.dump(TINY_DELTA, fh)
    with open(os.path.join(bdir, "traffic", "tinydelta_closed.json"),
              "w") as fh:
        json.dump(TRAFFIC, fh)
    with open(os.path.join(tmp, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tinydelta", "source": "test",
                             "file": "benchmarks/configs/tinydelta.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tinydelta.closed",
                               "config": "tinydelta",
                               "traffic": "tinydelta_closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and any(w.endswith(".paperqa")
                                    for w in m["workloads"]):
            m["workloads"].append("tinydelta.closed")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


def test_tiny_twin_end_to_end(root, lifted_gate, capsys, monkeypatch):
    # the decoder has no reference lanes: off the TPU its kernels run
    # interpreted, which the ambient dispatch switch asks for
    monkeypatch.setenv("RLT_PALLAS", "1")
    rc, out = _run(root, capsys, "tinydelta.closed", 0)
    assert rc == 0, out
    line = json.loads(out[-1])
    assert line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(line["metrics"])
    window = next(l for l in out if l.startswith("[window]"))
    assert "lanes=('paged-pallas', 'paged-pallas')" in window
    check = next(l for l in out if l.startswith("[check]"))
    assert "number=widest_logit_gap" in check and "limit=0.05" in check


def test_a_dense_cell_reads_nothing_from_the_delta_rules_readers(
        root, lifted_gate, capsys):
    """The three new metrics list the new cell alone; appended to a dense
    cell (as `tiny.build` does) the rooflines' readers find no delta rule in
    the run's tables and return nothing rather than raise."""
    from benchmarks.harness.common import RunRecord

    config = dict(tiny.TINY_CONFIG)
    rec = RunRecord(kind="serve_closed", cell={}, config=config, traffic={},
                    hp={}, seconds=1.0, chips=1, peaks={}, root=root)
    assert shapes_delta.delta_chunk_roofline_pct(rec) is None
    assert shapes_delta.delta_step_roofline_pct(rec) is None


def test_without_the_kernels_the_engine_refuses(root, lifted_gate, capsys,
                                                monkeypatch):
    monkeypatch.delenv("RLT_PALLAS", raising=False)
    with pytest.raises(ValueError, match="no reference"):
        _run(root, capsys, "tinydelta.closed", 0)
