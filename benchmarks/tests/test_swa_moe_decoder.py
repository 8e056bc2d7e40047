"""The architecture `swa_moe_decoder` (full NoPE layers and sliding-window
RoPE layers over routed ReGLU experts of which a chip holds a share, the
router reading the layer's input; trained) as the benchmark sees it: its
tables' leaves and counts at the published sizes, the configuration against
the catalog's row, the adapter's tree both ways, the work functions of its
two rooflines, the shares of an expert layer adding up to the whole, the
control, and its tiny twin through `run.py` on the CPU, sound and with two
leaf ids swapped."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import common, shapes, shapes_swa_moe, weights
from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run

ROOT = tiny.ROOT
MODEL = "swa_moe_decoder"
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "SmallThinker-21BA3B-Instruct.json")

TINY_SWA = {
    "name": "tinyswa", "source": "none: a test fixture", "model": MODEL,
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window_size": 24,
    "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 4,
    "moe_num_active_primary_experts": 3, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "max_position_embeddings": 128, "reduced": [],
    "published": {"moe_num_primary_experts": 8},
    "deployment": {"chips_sharing_a_layer": 2, "experts_first": 4},
    "assumed": {"initializer_std": 0.05},
    "execution": {"remat": True, "remat_policy": "attn_out",
                  "use_flash": True, "ce_chunk_tokens": 32},
}
TRAFFIC = dict(tiny.TRAFFIC["tiny_train"], seq=64, batch=1)


def _hp(config=TINY_SWA):
    adapter = common.load_model_file(ROOT, "models", MODEL)
    return adapter, adapter.hyperparams(config, "train")


def _published():
    adapter = common.load_model_file(ROOT, "models", MODEL)
    config = common.load_json(CONFIG)
    return adapter, config, adapter.hyperparams(config, "train")


# ---- tables and the configuration -------------------------------------------


def test_layer_kinds_and_leaf_ids():
    adapter, hp = _hp()
    t = adapter.tables
    assert t.layer_kinds(hp) == ["full", "window", "window", "window"]
    ids = {k: v["id"] for k, v in t.layer_table(hp, "full").items()
           if "id" in v}
    # an id is part of the values' key: these never change
    assert ids == {"q_proj": 900, "k_proj": 901, "v_proj": 902,
                   "o_proj": 903, "router": 910, "experts_gate_proj": 911,
                   "experts_up_proj": 912, "experts_down_proj": 913}
    assert t.layer_table(hp, "window") == t.layer_table(hp, "full")
    assert t.layer_table(hp, "full")["router"]["shape"] == (64, 8)
    assert t.layer_table(hp, "full")["experts_down_proj"]["shape"] == (
        4, 32, 64)
    # a norm's gain is seeded at 1 unless the configuration assumes another
    assert t.layer_table(hp, "full")["input_layernorm"]["fill"] == 1.0
    assert {k: v.get("id") for k, v in t.global_table(hp).items()} == {
        "embed_tokens": 920, "lm_head": 921, "norm": None}
    with pytest.raises(ValueError, match="neither|window without"):
        t.layer_kinds(dict(hp, rope_layout=(1, 1, 1, 1)))


def test_counts_at_the_published_sizes():
    adapter, config, hp = _published()
    t = adapter.tables
    assert t.layer_kinds(hp) == ["full", "window", "window", "window"]
    assert t.attention_params(hp) == 20_971_520
    assert t.expert_params(hp) == 5_898_240
    assert t.held_params(hp) == 656_529_920
    assert t.layer_table(hp, "window")["input_layernorm"]["fill"] == 0.05
    assert t.layer_table(hp, "window")["post_attention_layernorm"][
        "fill"] == 1.0
    assert config["bytes_on_chip"]["parameters"] == 656_529_920
    assert t.band_pairs(hp, "full", 16384) == 16384 * 16385 // 2
    assert t.band_pairs(hp, "window", 16384) == 58_722_304
    assert t.band_pairs(hp, "window", 2048) == 2048 * 2049 // 2
    # the issue's reckoning: 706 MFLOP a token forward at 16k, x3 a step
    fpt = t.train_flops_per_token(hp, 16384)
    assert abs(fpt / 3 / 1e6 - 706.9) < 1.0, fpt / 3 / 1e6
    whole = dict(hp, num_hidden_layers=52, moe_num_primary_experts=64,
                 vocab_size=151936, rope_layout=tuple(config["rope_layout"]),
                 sliding_window_layout=tuple(config["sliding_window_layout"]))
    assert abs(t.held_params(whole) / 1e9 - 21.5) < 0.05      # 21B


def test_the_configuration_changes_no_width_of_the_catalogs():
    config = common.load_json(CONFIG)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == config["name"])
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in differ}
    assert {"router_input", "attention_bias", "qk_norm", "rope_pairing",
            "auxiliary_loss"} <= set(config["assumed"])
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    assert config["deployment"]["pipeline_stages"] == 13


def test_the_adapters_tree_is_the_programs_and_comes_back():
    adapter, hp = _hp()
    cfg = adapter.program_config(TINY_SWA, hp)
    assert (cfg.experts_first, cfg.held, cfg.n_routed_experts) == (4, 4, 8)
    assert [cfg.windowed(i) for i in range(4)] == [False, True, True, True]
    from ray_lightning_tpu.models.swa_moe import SwaMoe

    s32 = weights.seed_u32(7)
    tree = adapter.program_tree(hp, s32, jnp.float32, False)
    adapter._check_tree(SwaMoe(cfg), tree)
    canon = weights.canonical(hp, adapter.tables, s32, False)
    back = adapter.canonical_from_program(hp, tree)
    flat = lambda t: {jax.tree_util.keystr(p): v for p, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    a, b = flat(canon), flat(back)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), k)
    with pytest.raises(common.BenchError, match="no serving path"):
        adapter.serving_params(TINY_SWA, hp, 7)
    with pytest.raises(common.BenchError, match="no 'serve' path"):
        adapter.hyperparams(TINY_SWA, "serve")


# ---- the work functions -----------------------------------------------------


def test_a_window_layers_flash_counts_the_band():
    dims = dict(heads=28, kv_heads=4, head_dim=128)
    full = shapes_swa_moe.flash_fwd_bwd_pairs(16384 * 16385 / 2, 16384,
                                              **dims)
    tri = shapes.flash_fwd_bwd(1, 16384, **dims)
    # the accepted work function halves S^2; the pairs have the diagonal too
    assert abs(full["flops"] / tri["flops"] - 1) < 1e-4
    assert full["bytes"] == tri["bytes"]
    band = shapes_swa_moe.flash_fwd_bwd_pairs(58_722_304, 16384, **dims)
    assert abs(band["flops"] / full["flops"] - 0.4375) < 1e-3
    assert band["bytes"] == full["bytes"]


def test_the_expert_products_work_counts_three_passes():
    w = shapes_swa_moe.moe_experts_fwd_bwd(24576 * 4, 4, 2560, 768, 16)
    assert w["flops"] == 24576 * 4 * 3 * 2560 * 768 * 2 * 3
    weights_ = 4 * 16 * 3 * 2560 * 768
    assert w["bytes"] == weights_ * (2 * 2 + 4) + 6 * 24576 * 4 * 2560 * 2


# ---- the reference -----------------------------------------------------------


def _layer_weights(hp, tables, seed, kind="window", layer=1):
    return weights.leaves(hp, tables.layer_table(hp, kind),
                          weights.seed_u32(seed), layer, False)


def test_the_four_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """The parts that the shares (0, 2) .. (6, 2) give add up to what the
    reference holding all 8 experts gives for the whole layer."""
    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    whole_hp = dict(hp, moe_num_primary_experts=8, experts_first=0)
    w = _layer_weights(whole_hp, adapter.tables, 11)
    x = jax.random.normal(jax.random.key(0), (48, 64), jnp.float32)
    z = jax.random.normal(jax.random.key(1), (48, 64), jnp.float32)
    whole = ref.routed_share(whole_hp, w, x, z, None)
    parts = jnp.zeros_like(whole)
    for first in range(0, 8, 2):
        part_hp = dict(hp, moe_num_primary_experts=2, experts_first=first)
        held = {k: (v[first:first + 2] if k.startswith("experts_") else v)
                for k, v in w.items()}
        parts = parts + ref.routed_share(part_hp, held, x, z, None)
    assert float(jnp.abs(whole).max()) > 1e-4
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-7)


def test_a_full_layer_ignores_positions_and_a_window_layer_does_not():
    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    x = jax.random.normal(jax.random.key(2), (32, 64), jnp.float32)
    for kind, moved in (("full", False), ("window", True)):
        w = _layer_weights(hp, adapter.tables, 5, kind)
        a = ref.layer(hp, kind, w, x)
        b = ref.layer(hp, kind, w, x, positions=jnp.arange(32) * 2 + 7)
        far = float(jnp.abs(a - b).max())
        assert (far > 1e-5) if moved else (far == 0.0), (kind, far)


def test_the_reference_reads_only_the_band_on_a_window_layer():
    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    x = jax.random.normal(jax.random.key(3), (64, 64), jnp.float32)
    y = x.at[:3].add(1.0)                    # rows 0-2 change
    for kind, moved in (("full", True), ("window", False)):
        w = _layer_weights(hp, adapter.tables, 5, kind)
        u, v = ref.rms_norm(x, 1.0, 1e-6), ref.rms_norm(y, 1.0, 1e-6)
        a = ref.attention(hp, kind, w, u, None, q_block=16)
        b = ref.attention(hp, kind, w, v, None, q_block=16)
        far = float(jnp.abs(a - b)[3 + 24:].max())
        assert (far > 1e-6) if moved else (far == 0.0), (kind, far)


def test_control_with_float8_operands_reads_far_over_the_limit():
    from benchmarks.harness import traffic_gen, train

    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    first = traffic_gen.train_tokens(hp["vocab_size"], 3, 3, 64).reshape(
        3, 1, 65)
    devices = jax.devices()[:1]
    sound = train.reference_three_steps(ref, hp, 3, first, TRAFFIC, devices)
    low = train.reference_three_steps(ref, hp, 3, first, TRAFFIC, devices,
                                      quant=ref.fp8_operands)
    cmp = train.compare(low, sound)
    assert cmp["grad_gap"] > 0.05 or cmp["delta_gap"] > 0.05, cmp


# ---- the tiny twin through run.py --------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.build(str(tmp_path_factory.mktemp("bench_swa")))
    bdir = os.path.join(tmp, "benchmarks")
    with open(os.path.join(bdir, "configs", "tinyswa.json"), "w") as fh:
        json.dump(TINY_SWA, fh)
    with open(os.path.join(bdir, "configs", "tinyswax.json"), "w") as fh:
        json.dump(dict(TINY_SWA, name="tinyswax", model=MODEL + "_swapped"),
                  fh)
    with open(os.path.join(bdir, "traffic", "tinyswa_train.json"), "w") as fh:
        json.dump(TRAFFIC, fh)
    # the broken twin: the same adapter beside a reference whose table has
    # two leaf ids swapped
    text = {}
    for part in common.MODEL_PARTS:
        with open(os.path.join(bdir, part, MODEL + ".py")) as fh:
            text[part] = fh.read()
    swapped = text["tables"].replace(
        '"q_proj": {"id": 900', '"q_proj": {"id": 903').replace(
        '"o_proj": {"id": 903', '"o_proj": {"id": 900')
    assert swapped != text["tables"]
    twin_ref = text["reference"].replace(
        f'"tables",\n                                "{MODEL}")',
        f'"tables",\n                                "{MODEL}_swapped")')
    assert twin_ref != text["reference"]
    for part, body in (("models", text["models"]), ("reference", twin_ref),
                       ("tables", swapped)):
        with open(os.path.join(bdir, part, MODEL + "_swapped.py"), "w") as fh:
            fh.write(body)
    with open(os.path.join(tmp, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {"tinyswa.train": "tinyswa", "tinyswax.train": "tinyswax"}
    for cell, config in cells.items():
        bench["configs"].append({"name": config, "source": "test",
                                 "file": f"benchmarks/configs/{config}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": "tinyswa_train", "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and any(w.endswith(".ctx16k")
                                    for w in m["workloads"]):
            m["workloads"] += list(cells)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


def test_tiny_twin_end_to_end(root, lifted_gate, capsys):
    rc, out = _run(root, capsys, "tinyswa.train", 0)
    assert rc == 0, out
    line = json.loads(out[-1])
    assert line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"setup_s", "train_tokens_per_s"} <= set(line["metrics"])
    checks = [l for l in out if l.startswith("[check]")]
    assert len(checks) == 3, checks


def _traced_run(accounts=True):
    """A synthetic traced stretch of three steps at the published sizes:
    per step 0.1 s of flash kernels and 0.05 s under `moe_experts`, and two
    fetches whose `rlt.train.account` carry the program's counts."""
    from benchmarks.harness import program_trace as pt
    from benchmarks.harness.common import RunRecord

    adapter, config, hp = _published()
    ops, modules, host = [], [], []
    for k in range(3):
        t0 = float(k)
        modules.append(("jit_step(2)", t0, t0 + 0.9))
        for j, name in enumerate(pt.FLASH_KERNELS):
            ops.append(pt.Op(name, t0 + 0.1 * j, t0 + 0.1 * j + 0.1 / 3,
                             name, "attn_window"))
        ops.append(pt.Op("gmm.3", t0 + 0.5, t0 + 0.55, None, "moe_experts"))
        ops.append(pt.Op("fusion.7", t0 + 0.6, t0 + 0.69, None,
                         "moe_dispatch"))
        host.append(pt.HostEvent("rlt.dispatch", t0 - 0.01, t0, 0,
                                 {"step": k}))
    if accounts:
        for k, (rows, top) in enumerate(((98000, 7000), (98600, 7400))):
            host.append(pt.HostEvent(
                "rlt.train.account", k + 0.95, k + 0.95, 0,
                {"step": k, "expert_rows": rows, "expert_rows_max": top}))
    tb = pt.build_tables(pt.ProgramTrace([pt.Device(ops, modules)], host),
                         "train")
    run = RunRecord(kind="train", cell={"name": "cell"}, config=config,
                    traffic={"batch": 1}, hp=hp, seconds=30.0, chips=1,
                    peaks={"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9},
                    root=ROOT, stamps={"seq": 16384})
    run.trace = object()
    run.stamps[pt._STAMP] = tb
    return run, adapter.tables, hp


def test_the_new_rooflines_against_a_hand_computation():
    run, t, hp = _traced_run()
    dims = t.attention_dims(hp)
    flops = 3.5 * 4 * 28 * 128 * (16384 * 16385 // 2 + 3 * 58_722_304)
    assert shapes_swa_moe.window_flash_roofline_pct(run) == pytest.approx(
        100.0 * (flops / 197e12) / 0.1, rel=1e-6)
    rows = (98000 + 98600) // 2                      # the fetches' median
    work = shapes_swa_moe.moe_experts_fwd_bwd(rows, 4, **t.expert_dims(hp))
    assert shapes_swa_moe.moe_experts_roofline_pct(run) == pytest.approx(
        100.0 * shapes.roofline_seconds(work, run.peaks)["seconds"] / 0.05,
        rel=1e-6)
    assert shapes_swa_moe.expert_load_peak_pct(run) == pytest.approx(
        (100 * 7000 * 16 / 98000 + 100 * 7400 * 16 / 98600) / 2)
    from benchmarks.harness import program_trace as pt

    assert pt.scope_share_pct(run, "moe_dispatch") == pytest.approx(10.0)
    assert dims["heads"] == 28


def test_a_program_without_the_account_event_reads_nothing():
    """The parent has no `rlt.train.account`: the readers of its counts
    return None (the line leaves the metric out) and do not raise."""
    run, _, _ = _traced_run(accounts=False)
    assert shapes_swa_moe.moe_experts_roofline_pct(run) is None
    assert shapes_swa_moe.expert_load_peak_pct(run) is None
    # a model whose tables know no band (the dense decoder) reads nothing
    run.config = {"model": "dense_decoder"}
    assert shapes_swa_moe.window_flash_roofline_pct(run) is None
    run.trace = None
    assert shapes_swa_moe.expert_load_peak_pct(run) is None


def test_two_leaf_ids_swapped_is_not_correct(root, lifted_gate, capsys):
    rc, out = _run(root, capsys, "tinyswax.train", 0)
    assert rc == 0, out
    assert json.loads(out[-1])["correct"] is False, out
