"""The architecture `ssm_hybrid_decoder` (state-space layers with an
attention layer a period) as the benchmark sees it: its tables' leaves and
ids, the leaves the hash cannot make, its counts at the published sizes, its
configuration's file against the catalog's row, the adapter against the
plain reference, the reference's control, the scan's work function, and its
tiny twin through `run.py` on the CPU beside the throw-away cells of
`tests/tiny.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import common, serving, shapes_ssm, weights
from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run

ROOT = tiny.ROOT
MODEL = "ssm_hybrid_decoder"
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "AI21-Jamba2-3B.json")

TINY_SSM = {
    "name": "tinyssm", "source": "none: a test fixture", "model": MODEL,
    "hidden_size": 64, "num_hidden_layers": 4, "attn_layer_period": 4,
    "attn_layer_offset": 1, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 96, "mamba_d_state": 4,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
    "num_experts": 1, "num_experts_per_tok": 1, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "max_position_as_run": 256, "reduced": [],
    "assumed": {"head_dim": 128, "initializer_std": 0.05,
                "ssm_init": {"dt_min": 0.001, "dt_max": 0.1}},
}
ENGINE = {"capacity": 4, "block_size": 16, "blocks_per_slot": 8,
          "n_blocks": 33, "prefill_chunk": 16, "prefill_batch": 1}
TRAFFIC = dict(
    tiny.TRAFFIC["tiny_closed"], engine=ENGINE, require_pallas=True,
    prompt_len={"dist": "bounded_pareto", "lo": 20, "hi": 100, "alpha": 1.2},
    check={"n_requests": 4, "gap_limit": 0.02})


def _hp(config=TINY_SSM):
    adapter = common.load_model_file(ROOT, "models", MODEL)
    return adapter, adapter.hyperparams(config, "serve")


# ---- tables -------------------------------------------------------------------


def test_layer_kinds_and_leaf_ids():
    adapter, hp = _hp()
    t = adapter.tables
    assert t.layer_kinds(hp) == ["ssm", "attention", "ssm", "ssm"]
    ids = lambda table: {k: v["id"] for k, v in table.items() if "id" in v}
    # an id is part of the values' key: these never change
    mlp = {"gate_proj": 620, "up_proj": 621, "down_proj": 622}
    assert ids(t.layer_table(hp, "ssm")) == {
        **mlp, "in_proj": 600, "conv1d_weight": 601, "conv1d_bias": 602,
        "x_proj": 603, "dt_proj": 604, "dt_bias_unit": 605, "out_proj": 606}
    assert ids(t.layer_table(hp, "attention")) == {
        **mlp, "q_proj": 610, "k_proj": 611, "v_proj": 612, "o_proj": 613}
    assert ids(t.global_table(hp)) == {"embed_tokens": 630}      # tied
    table = t.layer_table(hp, "ssm")
    assert table["in_proj"]["shape"] == (64, 256)
    assert table["x_proj"]["shape"] == (128, 8 + 2 * 4)
    assert table["conv1d_weight"]["shape"] == (4, 128)
    with pytest.raises(ValueError, match="no layer kind"):
        t.layer_table(hp, "window")


def test_the_leaves_the_hash_cannot_make():
    """`seeded`: A_log = log(1..N) on every channel, the step's bias the
    inverse softplus of a step log-uniform in [dt_min, dt_max], from one
    layer's leaves and from a stack alike, in numpy and in jax.numpy."""
    adapter, hp = _hp(common.load_json(CONFIG))
    t = adapter.tables
    half = 0.02 * 3 ** 0.5
    unit = np.linspace(-half, half, 5120, dtype=np.float32)
    w = t.seeded(hp, "ssm", {"dt_bias_unit": unit, "D": 1.0}, np)
    assert set(w) == {"dt_proj_bias", "A_log", "D"}
    step = np.logaddexp(w["dt_proj_bias"], 0.0)          # softplus
    np.testing.assert_allclose(step[[0, -1]], [1e-3, 1e-1], rtol=1e-3)
    np.testing.assert_allclose(np.log(step[2560]), np.log(1e-2), atol=2e-3)
    assert w["A_log"].shape == (5120, 16)
    np.testing.assert_allclose(np.exp(w["A_log"][7]), np.arange(1, 17),
                               rtol=1e-6)
    # exp(delta A): index 16 at the largest step forgets in a few rows,
    # index 1 at the smallest remembers a thousand
    assert np.exp(-16 * 1e-1) < 0.21 and np.exp(-1e-3) > 0.998
    stack = t.seeded(hp, "ssm", {"dt_bias_unit": jnp.asarray(
        np.stack([unit, unit[::-1]]))}, jnp)
    assert stack["A_log"].shape == (2, 5120, 16)
    np.testing.assert_allclose(np.asarray(stack["dt_proj_bias"][1]),
                               w["dt_proj_bias"][::-1], rtol=1e-5)
    # another kind passes through untouched
    same = {"q_proj": unit}
    assert t.seeded(hp, "attention", same, np) is same


def test_counts_at_the_published_sizes():
    config = common.load_json(CONFIG)
    adapter, hp = _hp(config)
    t = adapter.tables
    kinds = t.layer_kinds(hp)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert (kinds.count("ssm"), len(kinds)) == (26, 28)
    assert t.ssm_params(hp) == 41_241_792                 # 41.24M a mixer
    assert t.attention_params(hp) == 13_762_560           # 13.76M a block
    assert t.mlp_params(hp) == 62_914_560                 # 62.91M an MLP
    assert t.held_params(hp) == 3_029_337_472 == \
        config["bytes_on_chip"]["parameters"]
    f32 = 26 * (5120 * 16 + 2 * 5120)          # A_log, D, the step's bias
    assert 2 * t.held_params(hp) + 2 * f32 == \
        config["bytes_on_chip"]["serve_weights"]
    assert t.attention_dims(hp) == {"heads": 20, "kv_heads": 1,
                                    "head_dim": 128}
    assert (t.attention_layers(hp), t.scan_layers(hp)) == (2, 26)
    assert t.scan_dims(hp) == {"channels": 5120, "states": 16}
    assert t.matmul_params(hp) < t.held_params(hp)
    # the whole period that would not train: 25.6 GB at 16 bytes
    period = 13 * (t.ssm_params(hp) + t.mlp_params(hp) + 5120) + (
        t.attention_params(hp) + t.mlp_params(hp) + 5120) + 65536 * 2560
    assert abs(period * 16 / 1e9 - 25.6) < 0.05


def test_the_configuration_is_the_catalogs_row_uncut():
    config = common.load_json(CONFIG)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == config["name"])
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config[k] != v] == []
    assert config["reduced"] == [] and config["published"] == {}
    for key in ("assumed", "precision", "why_no_training", "bytes_on_chip"):
        assert config[key], key
    assert config["assumed"]["head_dim"] == \
        row["config"]["hidden_size"] // row["config"]["num_attention_heads"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"]
                     if c["name"] == config["name"])
    assert (entry["source"], entry["reduced"]) == (config["source"], [])


# ---- the adapter against the reference -------------------------------------------


def test_the_adapters_tree_is_the_programs():
    adapter, hp = _hp()
    cfg, params = adapter.serving_params(TINY_SSM, hp, 5)
    assert (cfg.attn_period, cfg.attn_offset, cfg.n_kv_heads, cfg.d_inner,
            cfg.head_dim) == (4, 1, 1, 128, 128)
    assert set(params) == {"tok_embed", "final_norm", "period_0"}
    per = params["period_0"]
    assert per["ssm_before"]["in_proj"].shape == (1, 64, 256)
    assert per["ssm_after"]["a_log"].shape == (2, 4, 128)       # [N, E]
    assert per["ssm_after"]["a_log"].dtype == jnp.float32
    assert per["ssm_after"]["dt_bias"].dtype == jnp.float32
    assert per["ssm_before"]["out_proj"].dtype == jnp.bfloat16
    assert per["attn_layer"]["wk"].shape == (64, 128)
    assert per["attn_layer"]["gate_up"].shape == (64, 192)
    with pytest.raises(common.BenchError, match="ONE expert"):
        adapter.program_config(TINY_SSM, dict(hp, num_experts=4))
    with pytest.raises(common.BenchError, match="serving configuration"):
        adapter.hyperparams(TINY_SSM, "train")


def _both(seed=5, n=64):
    """(program logits in float32, in bfloat16, the reference's, the
    control's) over one seeded sequence."""
    import dataclasses

    from ray_lightning_tpu.models.ssm_hybrid import SsmHybrid

    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    t = ref.tables
    s32 = weights.seed_u32(seed)
    ws = [weights.leaves(hp, t.layer_table(hp, k), s32, jnp.uint32(i), True)
          for i, k in enumerate(t.layer_kinds(hp))]
    g = weights.leaves(hp, t.global_table(hp), s32, 0, True)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, n),
                       jnp.int32)
    cfg, params = adapter.serving_params(TINY_SSM, hp, seed)
    p32 = jax.jit(lambda s: adapter.program_tree(hp, s, jnp.float32, True))(
        s32)
    run = lambda c, p: SsmHybrid(c).apply({"params": p}, toks[None])[0]
    return (run(dataclasses.replace(cfg, dtype=jnp.float32), p32),
            run(cfg, params), ref.forward(hp, ws, g, toks),
            ref.forward(hp, ws, g, toks, quant=ref.fp8_operands))


def test_the_program_is_the_reference_and_float8_is_not(monkeypatch):
    """The same seeded values through the program's layout and kernels (the
    scan interpreted) and through the plain reference: float32 against
    float32 agrees to rounding; the served bfloat16 is an order of magnitude
    nearer the reference than the float8 control is."""
    monkeypatch.setenv("RLT_PALLAS", "1")
    f32, bf16, want, low = _both()
    err = lambda x: float(jnp.max(jnp.abs(x - want)))
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert err(f32) < 1e-5
    assert err(bf16) < 0.03
    assert err(low) > 5 * err(bf16)


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


def test_the_references_recurrence_in_blocks_is_one_pass():
    """The state handed from block to block: any block size gives the rows
    of one pass, and a later row never moves an earlier one."""
    ref = common.load_model_file(ROOT, "reference", MODEL)
    rng = np.random.default_rng(2)
    s, e, n = 48, 128, 4
    x, delta = rng.standard_normal((s, e)), rng.uniform(1e-3, 1e-1, (s, e))
    b, c = rng.standard_normal((s, n)), rng.standard_normal((s, n))
    a = -np.broadcast_to(np.arange(1, n + 1.0), (e, n))
    args = [jnp.asarray(v, jnp.float32) for v in (x, delta, b, c, a)]
    d = jnp.ones(e)
    whole = ref.recurrence(*args, d, block=48)
    for block in (1, 7, 16):
        np.testing.assert_allclose(
            np.asarray(ref.recurrence(*args, d, block=block)),
            np.asarray(whole), atol=1e-6)
    x2 = x.copy()
    x2[30:] += 1.0
    moved = ref.recurrence(jnp.asarray(x2, jnp.float32), *args[1:], d)
    np.testing.assert_array_equal(np.asarray(moved)[:30],
                                  np.asarray(whole)[:30])


# ---- the scan's work function -------------------------------------------------


def test_the_scans_work_counts_steps_rows_and_the_state():
    work = shapes_ssm.selective_scan(1024, 1, channels=5120, states=16)
    assert work["flops"] == 1024 * 5120 * (7 * 16 + 12)
    assert work["bytes"] == (4 * 1024 * 5120 * 2 + 2 * 1024 * 16 * 4
                             + 2 * 5120 * 16 * 4)
    # one row a sequence: the state's bytes are all but everything
    one = shapes_ssm.selective_scan(128, 128, channels=5120, states=16)
    assert one["bytes"] > 0.9 * 2 * 128 * 5120 * 16 * 4
    # against the table's peaks a chunk's scan is bound by bytes
    from benchmarks.harness import shapes

    peaks = common.load_json(os.path.join(
        ROOT, "benchmarks", "peaks.json"))["device_kinds"]["TPU v5 lite"]
    assert shapes.roofline_seconds(work, peaks)["bound"] == "memory"


# ---- the tiny twin through run.py ----------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.build(str(tmp_path_factory.mktemp("bench_ssm")))
    bdir = os.path.join(tmp, "benchmarks")
    with open(os.path.join(bdir, "configs", "tinyssm.json"), "w") as fh:
        json.dump(TINY_SSM, fh)
    with open(os.path.join(bdir, "traffic", "tinyssm_closed.json"),
              "w") as fh:
        json.dump(TRAFFIC, fh)
    with open(os.path.join(tmp, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tinyssm", "source": "test",
                             "file": "benchmarks/configs/tinyssm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tinyssm.closed", "config": "tinyssm",
                               "traffic": "tinyssm_closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and any(w.endswith(".reasondocs")
                                    for w in m["workloads"]):
            m["workloads"].append("tinyssm.closed")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


def test_tiny_twin_end_to_end(root, lifted_gate, capsys, monkeypatch):
    # the decoder has no reference lanes: off the TPU its kernels run
    # interpreted, which the ambient dispatch switch asks for
    monkeypatch.setenv("RLT_PALLAS", "1")
    rc, out = _run(root, capsys, "tinyssm.closed", 0)
    assert rc == 0, out
    line = json.loads(out[-1])
    assert line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(line["metrics"])
    window = next(l for l in out if l.startswith("[window]"))
    assert "lanes=('paged-pallas', 'paged-pallas')" in window
    check = next(l for l in out if l.startswith("[check]"))
    assert "number=widest_logit_gap" in check and "limit=0.02" in check


def test_a_dense_cell_reads_nothing_from_the_scans_reader(root, lifted_gate,
                                                          capsys):
    """The three new metrics list the new cell alone; appended to a dense
    cell (as `tiny.build` does) the roofline's reader finds no scan in the
    run's tables and returns nothing rather than raise."""
    from benchmarks.harness.common import RunRecord

    config = dict(tiny.TINY_CONFIG)
    rec = RunRecord(kind="serve_closed", cell={}, config=config, traffic={},
                    hp={}, seconds=1.0, chips=1, peaks={}, root=root)
    assert shapes_ssm.ssm_scan_roofline_pct(rec) is None


def test_without_the_kernels_the_engine_refuses(root, lifted_gate, capsys,
                                                monkeypatch):
    monkeypatch.delenv("RLT_PALLAS", raising=False)
    with pytest.raises(ValueError, match="no reference"):
        _run(root, capsys, "tinyssm.closed", 0)
