"""The architecture `conv_moe_decoder` (gated short convolutions with a
full-attention layer every few, an expert layer that may hold every expert)
as the benchmark sees it: its tables' leaves and ids, the leaf the hash
cannot make, its counts at the published sizes, its configuration's file
against the catalog's row, the adapter against the plain reference, the
reference's control, the experts' stream's work function, and its tiny twin
through `run.py` on the CPU beside the throw-away cells of `tests/tiny.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import common, serving, shapes, shapes_conv_moe, weights
from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run

ROOT = tiny.ROOT
MODEL = "conv_moe_decoder"
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "LFM2-24B-A2B.json")
TRAFFIC_FILE = os.path.join(ROOT, "benchmarks", "traffic", "agentsteps.json")
CONV, FULL = "conv", "full_attention"

TINY_CONV = {
    "name": "tinyconv", "source": "none: a test fixture", "model": MODEL,
    "hidden_size": 128, "num_hidden_layers": 6, "num_dense_layers": 1,
    # the published list's shape, kept whole: the layers run are entries 1-6
    "layer_types": [CONV, CONV, FULL, CONV, CONV, FULL, CONV, CONV],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1, "conv_L_cache": 3,
    "vocab_size": 256, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000}, "max_position_as_run": 256,
    "published": {"num_experts": 8, "num_dense_layers": 2},
    "deployment": {"layer_first": 1, "experts_first": 0}, "reduced": [],
    "assumed": {"head_dim": 64, "initializer_std": 0.08,
                "conv_init": {"scale": 4}},
}
ENGINE = {"capacity": 4, "block_size": 16, "blocks_per_slot": 8,
          "n_blocks": 33, "prefill_chunk": 16, "prefill_batch": 1}
TRAFFIC = dict(
    tiny.TRAFFIC["tiny_closed"], engine=ENGINE, require_pallas=True,
    prompt_len={"dist": "bounded_pareto", "lo": 20, "hi": 100, "alpha": 1.2},
    # a few rows move whole on a flipped expert (2 of 8 a token): the limit
    # refuses what is grossly wrong (the test of the control says the rest)
    check={"n_requests": 4, "gap_limit": 1.5})


def _hp(config=TINY_CONV):
    adapter = common.load_model_file(ROOT, "models", MODEL)
    return adapter, adapter.hyperparams(config, "serve")


# ---- tables -------------------------------------------------------------------


def test_layer_kinds_and_leaf_ids():
    adapter, hp = _hp()
    t = adapter.tables
    assert t.layer_kinds(hp) == ["conv_dense", "attention", "conv", "conv",
                                 "attention", "conv"]
    ids = lambda table: {k: v["id"] for k, v in table.items() if "id" in v}
    # an id is part of the values' key: these never change
    conv = {"in_proj": 800, "conv_unit": 801, "out_proj": 802}
    attn = {"q_proj": 810, "k_proj": 811, "v_proj": 812, "o_proj": 813}
    mlp = {"gate_proj": 820, "up_proj": 821, "down_proj": 822}
    moe = {"gate": 830, "expert_bias": 831, "experts_gate_proj": 832,
           "experts_up_proj": 833, "experts_down_proj": 834}
    assert ids(t.layer_table(hp, "conv_dense")) == {**conv, **mlp}
    assert ids(t.layer_table(hp, "conv")) == {**conv, **moe}
    assert ids(t.layer_table(hp, "attention")) == {**attn, **moe}
    assert ids(t.layer_table(hp, "attention_dense")) == {**attn, **mlp}
    assert ids(t.global_table(hp)) == {"embed_tokens": 840}
    table = t.layer_table(hp, "attention")
    assert table["q_proj"]["shape"] == (128, 256)
    assert table["k_proj"]["shape"] == (128, 128)
    assert table["q_layernorm"] == {"fill": 1.0, "shape": (64,)}
    assert table["experts_down_proj"]["shape"] == (8, 32, 128)
    assert t.layer_table(hp, "conv")["conv_unit"]["shape"] == (3, 128)
    assert t.layer_table(hp, "conv")["in_proj"]["shape"] == (128, 384)
    with pytest.raises(ValueError, match="no layer kind"):
        t.layer_table(hp, "window")
    with pytest.raises(ValueError, match="does not give"):
        t.layer_kinds(dict(hp, num_hidden_layers=9))


def test_the_leaf_the_hash_cannot_make():
    """`seeded`: the convolution's weight is `conv_init_scale` times the
    hashed leaf, a power of two, so every value stays one bfloat16 holds;
    another kind passes through untouched."""
    adapter, hp = _hp(common.load_json(CONFIG))
    t = adapter.tables
    assert hp["conv_init_scale"] == 16
    w = jax.jit(lambda: weights.leaves(
        hp, {"conv_unit": t.layer_table(hp, "conv")["conv_unit"]},
        weights.seed_u32(7), jnp.uint32(2), True))()
    made = t.seeded(hp, "conv", w)
    assert set(made) == {"conv_weight"} and made["conv_weight"].shape == (
        3, 2048)
    taps = np.asarray(made["conv_weight"])
    # torch's Conv1d default for 3 taps a channel is uniform within 0.577
    # (0.5543 rounded to bfloat16 is 0.5547)
    assert 0.5 < np.abs(taps).max() <= 0.5546875 < 0.577
    assert abs(taps.std() - 0.32) < 0.02
    np.testing.assert_array_equal(
        taps, np.asarray(taps, jnp.bfloat16).astype(np.float32))
    same = {"q_proj": w["conv_unit"]}
    assert t.seeded(hp, "attention", same) is same


def test_counts_at_the_published_sizes():
    config = common.load_json(CONFIG)
    adapter, hp = _hp(config)
    t = adapter.tables
    kinds = t.layer_kinds(hp)
    assert kinds == ["conv_dense"] + ["attention", "conv", "conv",
                                      "conv"] * 2
    assert t.conv_params(hp) == 16_783_360
    assert t.attention_params(hp) == 10_485_760
    assert t.dense_mlp_params(hp) == 72_351_744
    assert t.expert_params(hp) == 9_437_184
    assert 64 * t.expert_params(hp) + 2048 * 64 == 604_110_848
    assert t.held_params(hp) == 5_177_950_976 == \
        config["bytes_on_chip"]["parameters"]
    # all 40 published layers, and what a token's products read: 24B-A2B
    assert t.published_params(hp) == 23_843_661_440
    assert t.published_params(hp, active=True) == 2_326_881_920
    # bfloat16 with the 8 routers and biases float32
    assert 2 * t.held_params(hp) + 2 * 8 * (2048 * 64 + 64) == \
        config["bytes_on_chip"]["serve_weights"] == 10_358_000_128
    assert t.attention_dims(hp) == {"heads": 32, "kv_heads": 8,
                                    "head_dim": 64}
    assert (t.attention_layers(hp), t.conv_layers(hp),
            t.expert_layers(hp)) == (2, 7, 8)
    assert t.expert_dims(hp) == {"hidden": 2048, "width": 1536, "held": 64}
    assert t.matmul_params(hp) == 648_062_976 < t.held_params(hp)


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
        "num_dense_layers", "num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_dense_layers": 2, "num_experts": 64,
                                   "vocab_size": 65536}
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (9, 1)
    # every expert and the whole vocabulary are held
    assert (config["num_experts"], config["vocab_size"]) == (64, 65536)
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    assert (config["deployment"]["pipeline_stages"],
            config["deployment"]["stage"],
            config["deployment"]["layer_first"]) == (5, 1, 1)
    for key in ("assumed", "precision", "why_no_training", "bytes_on_chip",
                "deployment", "reduced_why"):
        assert config[key], key
    for key in ("head_dim", "tie_word_embeddings", "qk_norm", "rope_pairing",
                "block", "short_conv", "router", "expert_bias", "conv_init"):
        assert config["assumed"][key], key
    assert config["assumed"]["head_dim"] == \
        row["config"]["hidden_size"] // row["config"]["num_attention_heads"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"]
                     if c["name"] == config["name"])
    assert (entry["source"], entry["reduced"]) == (
        config["source"], ["num_hidden_layers", "num_dense_layers"])


def test_the_traffic_is_the_issues():
    tr = common.load_json(TRAFFIC_FILE)
    # `serve_closed` with a second limit on a low rank of the requests'
    # gaps: 64 small experts, 4 a token, flip on near ties of the biased
    # scores in most rows (PERF.md section 6, PR 44)
    assert (tr["kind"], tr["clients"], tr["pool_size"]) == (
        "serve_closed_ranked", 128, 256)
    check = tr["check"]
    assert check["request_rank"] <= check["n_requests"] // 2 + 1
    assert check["rank_gap_limit"] < check["gap_limit"]
    assert tr["prompt_len"] == {"dist": "bounded_pareto", "lo": 128,
                                "hi": 1024, "alpha": 1.2}
    assert tr["output_len"] == {"dist": "bounded_pareto", "lo": 256,
                                "hi": 1024, "alpha": 1.2}
    assert tr["sampling"] == common.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "docs.json"))["sampling"]
    assert tr["engine"]["blocks_per_slot"] * tr["engine"]["block_size"] \
        >= 1024 + 1024
    assert tr["engine"]["n_blocks"] == 1 + 128 * 17
    from benchmarks.harness import traffic_gen

    plens = traffic_gen.quantile_grid(tr["prompt_len"], 256)
    olens = traffic_gen.quantile_grid(tr["output_len"], 256)
    assert 280 < plens.mean() < 320 and 430 < olens.mean() < 480
    # six tokens in ten are generated
    assert 0.55 < olens.sum() / (plens.sum() + olens.sum()) < 0.65


# ---- the adapter against the reference -------------------------------------------


def test_the_adapters_tree_is_the_programs():
    adapter, hp = _hp()
    cfg, params = adapter.serving_params(TINY_CONV, hp, 5)
    assert cfg.layer_types == (CONV, FULL, CONV, CONV, FULL, CONV)
    assert (cfg.n_dense_layers, cfg.held, cfg.kv_row) == (1, 8, (1, 128))
    assert set(params) == {"tok_embed", "final_norm", "experts_gate_up",
                           "experts_down"} | {f"run_{i}" for i in range(5)}
    assert params["experts_gate_up"].shape == (5, 8, 128, 64)
    assert params["experts_down"].shape == (5, 8, 32, 128)
    assert params["run_0"]["gate_up"].shape == (1, 128, 192)
    assert params["run_2"]["conv_weight"].shape == (2, 3, 128)
    assert params["run_2"]["experts"]["router"].dtype == jnp.float32
    assert params["run_1"]["experts"]["router_bias"].dtype == jnp.float32
    assert params["run_1"]["wq"].dtype == jnp.bfloat16
    # filled a layer at a time in place, the stacks are what one call makes
    whole = jax.jit(lambda s: adapter.program_tree(
        hp, s, jnp.bfloat16, True))(weights.seed_u32(5))
    jax.tree.map(np.testing.assert_array_equal, params, whole)
    # the experts' stack is in LAYER order: row 1 is layer 2's (a
    # convolution layer's), not the second attention layer's
    t = adapter.tables
    one = weights.leaves(hp, {"experts_down_proj": t.layer_table(
        hp, "conv")["experts_down_proj"]}, weights.seed_u32(5),
        jnp.uint32(2), True)
    np.testing.assert_array_equal(
        np.asarray(params["experts_down"][1], np.float32),
        np.asarray(one["experts_down_proj"].astype(jnp.bfloat16),
                   np.float32))
    with pytest.raises(common.BenchError, match="serving configuration"):
        adapter.hyperparams(TINY_CONV, "train")


def _both(seed=5, n=64):
    """(program logits in float32, in bfloat16, the reference's, the
    control's) over one seeded sequence."""
    import dataclasses

    from ray_lightning_tpu.models.conv_moe import ConvMoe

    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    t = ref.tables
    s32 = weights.seed_u32(seed)
    ws = [weights.leaves(hp, t.layer_table(hp, k), s32, jnp.uint32(i), True)
          for i, k in enumerate(t.layer_kinds(hp))]
    g = weights.leaves(hp, t.global_table(hp), s32, 0, True)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, n),
                       jnp.int32)
    cfg, params = adapter.serving_params(TINY_CONV, hp, seed)
    p32 = jax.jit(lambda s: adapter.program_tree(hp, s, jnp.float32, True))(
        s32)
    run = lambda c, p: ConvMoe(c).apply({"params": p}, toks[None])[0]
    return (run(dataclasses.replace(cfg, dtype=jnp.float32), p32),
            run(cfg, params), ref.forward(hp, ws, g, toks),
            ref.forward(hp, ws, g, toks, quant=ref.fp8_operands))


def _row_errors(got, want):
    """The largest error of each row's logits."""
    return np.abs(np.asarray(got - want)).max(-1)


def test_the_program_is_the_reference_and_float8_is_not(monkeypatch):
    """The same seeded values through the program's layout and kernels and
    through the plain reference: float32 against float32 agrees to
    rounding. The served bfloat16 is read by ROW: with 2 experts of 8 a
    token a rounding that flips a near tie of the biased scores swaps half a
    row's experts, so a few rows move whole (the widest by 0.7 here) while
    the median row moves by 0.06; the float8 control moves EVERY row, the
    median by 0.8."""
    monkeypatch.setenv("RLT_PALLAS", "1")
    f32, bf16, want, low = _both()
    assert float(jnp.max(jnp.abs(want))) > 1.0
    # float32 to rounding, on logits of size 3
    assert _row_errors(f32, want).max() < 1e-4
    served, control = _row_errors(bf16, want), _row_errors(low, want)
    assert np.median(served) < 0.1 and served.max() < 1.0
    assert np.median(control) > 5 * np.median(served)
    assert np.percentile(served, 90) < np.median(control) / 3


def test_prefill_in_chunks_through_the_cache_is_the_reference(monkeypatch):
    """The program through its paged path (the adapter's `program_logits`:
    chunks of 16 through the pool and the tails) against the reference's
    one pass, served bfloat16 and, the same path, float32 to rounding."""
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
    got = adapter.program_logits(TINY_CONV, hp, seed, toks, 16, block=16)
    # by row, as above: a flipped expert moves a row whole
    assert np.median(_row_errors(got, want)) < 0.1
    real = adapter.serving_params

    def f32_params(config, hp_, seed_):
        cfg, _ = real(config, hp_, seed_)
        p32 = jax.jit(lambda s: adapter.program_tree(
            hp_, s, jnp.float32, True))(weights.seed_u32(seed_))
        return dataclasses.replace(cfg, dtype=jnp.float32), p32

    monkeypatch.setattr(adapter, "serving_params", f32_params)
    got = adapter.program_logits(TINY_CONV, hp, seed, toks, 16, block=16)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_control_with_float8_operands_picks_a_worse_token_in_most_rows(
        monkeypatch):
    """At this width a flipped expert moves a few of the program's rows as
    far as float8 moves every row (widest gaps 0.2-0.7 against 0.9-1.8 over
    four seeds), so the tiny twin's `gap_limit` below only refuses what is
    grossly wrong and the control is told apart by ROW: the served bfloat16
    keeps the reference's first token in nearly every row, float8 loses it
    in a third of them. (On the chip the two limits of the traffic file do
    this: PERF.md section 6, PR 44.)"""
    monkeypatch.setenv("RLT_PALLAS", "1")
    _, bf16, want, low = _both(n=128)
    first = lambda x: np.asarray(jnp.argmax(x, -1) == jnp.argmax(want, -1))
    assert first(bf16).mean() > 0.9
    assert first(low).mean() < 0.75
    gap = lambda x: np.asarray(jnp.max(want, -1) - jnp.take_along_axis(
        want, jnp.argmax(x, -1)[:, None], -1)[:, 0])
    assert np.percentile(gap(low), 75) > 5 * np.percentile(gap(bf16), 75)


def test_the_references_convolution_is_causal_and_three_taps_deep():
    """A later row never moves an earlier one; row t reads rows t - 2, t - 1
    and t of B * u, and rows before the sequence are zeros."""
    ref = common.load_model_file(ROOT, "reference", MODEL)
    _, hp = _hp()
    rng = np.random.default_rng(0)
    d = 128
    w = {"in_proj": jnp.asarray(rng.standard_normal((d, 3 * d)) * 0.1,
                                jnp.float32),
         "conv_weight": jnp.asarray(rng.standard_normal((3, d)), jnp.float32),
         "out_proj": jnp.eye(d, dtype=jnp.float32)}
    y = jnp.asarray(rng.standard_normal((12, d)), jnp.float32)
    out = np.asarray(ref.short_conv(hp, w, y, None))
    np.testing.assert_allclose(
        np.asarray(ref.short_conv(hp, w, y[:7], None)), out[:7], atol=1e-6)
    b, c, u = np.split(np.asarray(y) @ np.asarray(w["in_proj"]), 3, axis=-1)
    v = b * u
    taps = np.asarray(w["conv_weight"])
    for t_ in (0, 1, 5):
        rows = [v[t_ - 2 + j] if t_ - 2 + j >= 0 else 0.0 for j in range(3)]
        want = c[t_] * sum(taps[j] * rows[j] for j in range(3))
        np.testing.assert_allclose(out[t_], want, atol=1e-5)


# ---- the experts' stream ---------------------------------------------------------


def test_the_streams_work_counts_the_experts_hit_and_the_rows():
    dims = dict(hidden=2048, width=1536, held=64)
    # a decode tick of 128 slots: 512 rows a layer over 8 layers, most
    # experts of every layer hit
    work = shapes_conv_moe.moe_stream(8 * 512, 8 * 60, **dims)
    assert work["flops"] == 8 * 512 * 3 * 2048 * 1536 * 2
    assert work["bytes"] == (8 * 60 * 3 * 2048 * 1536 * 2
                             + 8 * 512 * 2 * 2048 * 2)
    peaks = common.load_json(os.path.join(
        ROOT, "benchmarks", "peaks.json"))["device_kinds"]["TPU v5 lite"]
    least = shapes.roofline_seconds(work, peaks)
    # bound by streaming the weights: 9.1 GB at 819 GB/s
    assert least["bound"] == "memory" and 0.010 < least["seconds"] < 0.012
    # an expert nobody hit costs nothing, whatever is held
    none = shapes_conv_moe.moe_stream(0, 0, **dims)
    assert none == {"flops": 0.0, "bytes": 0}
    # at the ridge (240 rows an expert) the two bounds meet
    ridge = shapes_conv_moe.moe_stream(64 * 240, 64, **dims)
    t = shapes.roofline_seconds(ridge, peaks)
    assert abs(ridge["flops"] / peaks["bf16_flops_per_s"]
               - ridge["bytes"] / peaks["hbm_bytes_per_s"]) < 0.1 * t[
                   "seconds"]


def _traced_run():
    """Three ticks of a serving step on a hand-built trace: 8 expert
    layers' products of 1.5 ms a tick under `moe_experts`, a dispatch before
    each execution and, a tick late, the account of its device counts."""
    from benchmarks.harness import program_trace as pt
    from benchmarks.harness.common import RunRecord
    from benchmarks.harness.program_trace import HostEvent, Op

    host_ev = lambda name, a, b, **st: HostEvent("rlt." + name, a, b, 0, st)
    ops, host, modules = [], [], []
    counts = [(4096, 40, 480), (4096 + 8 * 4096, 200, 510), (4096, 33, 470)]
    for k, (rows, fullest, hit) in enumerate(counts):
        t0 = k * 0.1
        modules.append(("jit_step(1)", t0, t0 + 0.09))
        for layer in range(8):
            a = t0 + 0.001 + layer * 0.002
            ops.append(Op(f"gmm.{layer}", a, a + 0.0015, None, "moe_experts"))
        ops.append(Op("fusion.3", t0 + 0.05, t0 + 0.06, None, "shortconv"))
        host.append(host_ev("serve.dispatch", t0 - 0.002, t0 - 0.001,
                            decode_slots=128, kv_tokens=128 * 900))
        # the step's own counts are read once it has run
        host.append(host_ev("serve.account", t0 + 0.095, t0 + 0.096,
                            expert_rows=rows, expert_rows_max=fullest,
                            experts_hit=hit, conv_rows=0, state_slots=128))
    tb = pt.build_tables(pt.ProgramTrace([pt.Device(ops, modules)], host),
                         "serve")
    config = common.load_json(CONFIG)
    _, hp = _hp(config)
    peaks = common.load_json(os.path.join(
        ROOT, "benchmarks", "peaks.json"))["device_kinds"]["TPU v5 lite"]
    run = RunRecord(kind="serve_closed", cell={"name": "cell"}, config=config,
                    traffic={}, hp=hp, seconds=30.0, chips=1, peaks=peaks,
                    root=ROOT)
    run.trace = object()
    run.stamps[pt._STAMP] = tb
    return run, counts, peaks


def test_the_streams_roofline_and_the_load_peak_on_a_hand_built_trace():
    run, counts, peaks = _traced_run()
    dims = dict(hidden=2048, width=1536, held=64)
    least = sum(shapes.roofline_seconds(
        shapes_conv_moe.moe_stream(rows, hit, **dims), peaks)["seconds"]
        for rows, _, hit in counts)
    got = shapes_conv_moe.moe_stream_roofline_pct(run)
    # 8 x 1.5 ms under `moe_experts` in each of the three executions
    assert got == pytest.approx(100.0 * least / (3 * 8 * 0.0015), rel=1e-9)
    assert 0 < got < 100
    # the fullest expert over the mean of 512 (layer, expert) pairs: 500%,
    # 278% (the tick with a chunk) and 412.5%; the median tick's
    peak = shapes_conv_moe.expert_load_peak_pct(run)
    assert peak == pytest.approx(100.0 * 33 * 512 / 4096)
    # a program that counts no `experts_hit` (the parent): nothing to read
    for ev in run.stamps["program_trace"].trace.host_named(
            "rlt.serve.account"):
        ev.stats.pop("experts_hit")
    assert shapes_conv_moe.moe_stream_roofline_pct(run) is None


# ---- the tiny twin through run.py ----------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.build(str(tmp_path_factory.mktemp("bench_conv")))
    bdir = os.path.join(tmp, "benchmarks")
    with open(os.path.join(bdir, "configs", "tinyconv.json"), "w") as fh:
        json.dump(TINY_CONV, fh)
    with open(os.path.join(bdir, "traffic", "tinyconv_closed.json"),
              "w") as fh:
        json.dump(TRAFFIC, fh)
    with open(os.path.join(tmp, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tinyconv", "source": "test",
                             "file": "benchmarks/configs/tinyconv.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tinyconv.closed",
                               "config": "tinyconv",
                               "traffic": "tinyconv_closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and any(w.endswith(".agentsteps")
                                    for w in m["workloads"]):
            m["workloads"].append("tinyconv.closed")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


def test_tiny_twin_end_to_end(root, lifted_gate, capsys, monkeypatch):
    # the decoder has no reference lanes: off the TPU its kernels run
    # interpreted, which the ambient dispatch switch asks for
    monkeypatch.setenv("RLT_PALLAS", "1")
    rc, out = _run(root, capsys, "tinyconv.closed", 0)
    assert rc == 0, out
    line = json.loads(out[-1])
    assert line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(line["metrics"])
    window = next(l for l in out if l.startswith("[window]"))
    assert "lanes=('paged-pallas', 'paged-pallas')" in window
    check = next(l for l in out if l.startswith("[check]"))
    assert "number=widest_logit_gap" in check and "limit=1.5" in check


def test_a_dense_cell_reads_nothing_from_the_streams_readers(root):
    """The new metrics list the new cell alone; appended to a dense cell (as
    `tiny.build` does) the readers find no expert layer in the run's tables
    and return nothing rather than raise."""
    from benchmarks.harness.common import RunRecord

    config = dict(tiny.TINY_CONFIG)
    rec = RunRecord(kind="serve_closed", cell={}, config=config, traffic={},
                    hp={}, seconds=1.0, chips=1, peaks={}, root=root)
    assert shapes_conv_moe.moe_stream_roofline_pct(rec) is None
    assert shapes_conv_moe.expert_load_peak_pct(rec) is None


def test_without_the_kernels_the_engine_refuses(root, lifted_gate, capsys,
                                                monkeypatch):
    monkeypatch.delenv("RLT_PALLAS", raising=False)
    with pytest.raises(ValueError, match="no reference"):
        _run(root, capsys, "tinyconv.closed", 0)
