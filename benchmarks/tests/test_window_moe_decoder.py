"""The architecture `window_moe_decoder` (sliding-window and full attention
layers, a parallel attention-and-experts block, routed experts of which a
chip holds a share) as the benchmark sees it: its tables' leaves and ids,
its counts at the published sizes, the work functions of its rooflines, its
reference's control at a tiny size, and its tiny twin through `run.py` on
the CPU beside the throw-away cells of `tests/tiny.py`."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import common, serving, shapes, shapes_window
from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run

ROOT = tiny.ROOT
MODEL = "window_moe_decoder"

TINY_WIN = {
    "name": "tinywin", "source": "none: a test fixture", "model": MODEL,
    "hidden_size": 64, "num_hidden_layers": 4, "layer_switch": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "sliding_window": 24, "intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 4, "num_shared_experts": 2, "vocab_size": 256,
    "layer_norm_eps": 1e-5, "rope_theta": 50000, "logit_scale": 1,
    "max_position_as_run": 256, "reduced": [],
    "published": {"num_experts": 16},
    "deployment": {"chips_sharing_a_layer": 2, "experts_first": 8},
    "assumed": {"initializer_std": 0.05},
}
#: a ring of ceil((24 + 16) / 16) + 1 = 4 blocks a slot against tables of 8
ENGINE = {"capacity": 4, "block_size": 16, "blocks_per_slot": 8,
          "n_blocks": 33, "prefill_chunk": 16, "prefill_batch": 1}
#: the cell's runner kind: `serve_closed` with a second limit, on a low
#: rank of the sampled requests' widest gaps
TRAFFIC = dict(
    tiny.TRAFFIC["tiny_closed"], kind="serve_closed_ranked", engine=ENGINE,
    require_pallas=True,
    prompt_len={"dist": "bounded_pareto", "lo": 30, "hi": 100, "alpha": 1.2},
    check={"n_requests": 4, "gap_limit": 0.02, "request_rank": 2,
           "rank_gap_limit": 0.01})


def _hp(config=TINY_WIN):
    adapter = common.load_model_file(ROOT, "models", MODEL)
    return adapter, adapter.hyperparams(config, "serve")


# ---- tables -------------------------------------------------------------------


def test_layer_kinds_and_leaf_ids():
    adapter, hp = _hp()
    t = adapter.tables
    assert t.layer_kinds(hp) == ["window", "window", "window", "full"]
    ids = lambda table: {k: v["id"] for k, v in table.items() if "id" in v}
    # an id is part of the values' key: these never change
    for kind in ("window", "full"):
        assert ids(t.layer_table(hp, kind)) == {
            "q_proj": 400, "k_proj": 401, "v_proj": 402, "o_proj": 403,
            "gate": 410, "shared_gate_proj": 412, "shared_up_proj": 413,
            "shared_down_proj": 414, "experts_gate_proj": 415,
            "experts_up_proj": 416, "experts_down_proj": 417}
    assert ids(t.global_table(hp)) == {"embed_tokens": 500}   # tied
    # the router keeps its published width, the experts' leaves the share
    table = t.layer_table(hp, "window")
    assert table["gate"]["shape"] == (64, 16)
    assert table["experts_gate_proj"]["shape"] == (8, 64, 32)
    assert table["shared_down_proj"]["shape"] == (2, 32, 64)
    with pytest.raises(ValueError, match="no layer kind"):
        t.layer_table(hp, "dense")


def test_counts_at_the_published_sizes():
    config = common.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "command-a-plus-05-2026.json"))
    adapter, hp = _hp(config)
    t = adapter.tables
    assert t.layer_kinds(hp) == ["window", "window", "window", "full"]
    assert t.attention_params(hp) == 142_606_336          # 142.6M a block
    assert t.expert_params(hp) == 50_331_648              # 50.33M an expert
    assert t.held_params(hp) == 4_733_272_064             # 4.733B here
    assert t.held_params(hp) + 5 * 4096 == \
        config["bytes_on_chip"]["parameters"]             # and the norms
    assert t.attention_dims(hp) == {"heads": 128, "kv_heads": 8,
                                    "head_dim": 128}
    assert (t.attention_layers(hp), t.attention_layers(hp, "window"),
            t.attention_layers(hp, "full"), t.expert_layers(hp)) == (
                4, 3, 1, 4)
    assert t.window(hp) == 4096
    assert t.expert_dims(hp) == {"hidden": 4096, "width": 4096, "held": 16}
    whole = dict(hp, num_hidden_layers=32, num_experts=128,
                 vocab_size=262144)
    assert abs(t.held_params(whole) / 1e9 - 218.25) < 0.01   # 218B
    assert abs(t.matmul_params(whole) / 1e9 - 24.98) < 0.01  # A25B


def test_the_configuration_changes_no_width_of_the_catalogs():
    config = common.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "command-a-plus-05-2026.json"))
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == config["name"])
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in differ}


def test_the_adapters_tree_is_the_programs():
    adapter, hp = _hp()
    cfg, params = adapter.serving_params(TINY_WIN, hp, 5)
    assert cfg.experts_first == 8 and cfg.held == 8
    assert (cfg.n_routed_experts, cfg.window, cfg.period) == (16, 24, 4)
    win = params["periods"]["window_layers"]
    assert win["experts"]["router"].dtype == jnp.float32
    assert win["wq"].shape == (1, 3, 64, 512)
    assert win["shared_gate_up"].shape == (1, 3, 64, 128)
    assert params["periods"]["full_layer"]["shared_down"].shape == (1, 64, 64)
    assert params["experts_gate_up"].dtype == jnp.bfloat16
    assert params["experts_gate_up"].shape == (4, 8, 64, 64)
    with pytest.raises(common.BenchError, match="whole periods"):
        adapter.program_config(TINY_WIN, dict(
            hp, layer_types=("full_attention",) * 4))


# ---- the work functions -------------------------------------------------------


def test_a_window_layers_prefill_counts_the_band():
    dims = {"heads": 128, "kv_heads": 8, "head_dim": 128}
    full = shapes.paged_prefill(1024, 8192, **dims)
    band = shapes_window.window_prefill(1024, 8192, 4095, 4096, **dims)
    # every row sees 4096 keys; the kernel reads 4095 cached and its own
    assert band["flops"] == 4 * 1024 * 4096 * 128 * 128
    assert band["bytes"] == 2 * (4095 + 1024) * 8 * 128 * 2 \
        + 2 * 1024 * 128 * 128 * 2
    assert band["flops"] < full["flops"] / 2
    # under the window it is the full layer's work
    assert shapes_window.window_prefill(1024, 1024, 1024, 4096, **dims) == \
        shapes.paged_prefill(1024, 1024, **dims)
    # the chunk in which the window closes: rows ramp up, then stay
    mixed = shapes_window.window_prefill(8, 10, 10, 16, **dims)
    pairs = sum(min(10 + j + 1, 16) for j in range(8))
    assert mixed["flops"] == 4 * pairs * 128 * 128


# ---- the reference and its control ---------------------------------------------


def _reference_logits(quant, tokens, seed=3):
    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    seqs = [(tokens, 0, len(tokens))]
    return serving.reference_logits(ref, hp, seed, seqs, 128,
                                    quant=quant)[0], ref


def test_control_with_float8_operands_reads_far_over_the_limit():
    """Tokens the float32 reference puts first read a gap of 0; those its
    float8 twin puts first read a gap over the limit the tiny twin's sound
    runs are held to."""
    tokens = np.random.default_rng(0).integers(0, 256, 96).astype(np.int32)
    sound, ref = _reference_logits(None, tokens)
    low, _ = _reference_logits(ref.fp8_operands, tokens)
    first = jnp.argmax(low, axis=-1)
    gap = jnp.max(sound, axis=-1) - jnp.take_along_axis(
        sound, first[:, None], axis=-1)[:, 0]
    assert float(jnp.max(gap)) > 3 * TRAFFIC["check"]["gap_limit"], gap


def test_the_reference_reads_only_the_band_on_a_window_layer():
    """A window layer's answer at row s does not move when a token behind
    `s - window` does; a full layer's does."""
    adapter, hp = _hp()
    ref = common.load_model_file(ROOT, "reference", MODEL)
    from benchmarks.harness import weights

    rng = np.random.default_rng(1)
    x = rng.standard_normal((64, 64)).astype(np.float32)
    y = x.copy()
    y[3] = 3.0 * rng.standard_normal(64)   # not a shift: the norm centres
    for kind, moved in (("window", False), ("full", True)):
        w = weights.leaves(hp, adapter.tables.layer_table(hp, kind),
                           weights.seed_u32(2), 0, True)
        a = ref.layer(hp, kind, w, jnp.asarray(x))
        b = ref.layer(hp, kind, w, jnp.asarray(y))
        far = np.abs(np.asarray(a - b))[3 + 24:].max()
        assert (far > 1e-5) if moved else (far == 0.0), (kind, far)


# ---- the tiny twin through run.py ----------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.build(str(tmp_path_factory.mktemp("bench_win")))
    bdir = os.path.join(tmp, "benchmarks")
    with open(os.path.join(bdir, "configs", "tinywin.json"), "w") as fh:
        json.dump(TINY_WIN, fh)
    # the same traffic under a ranked limit no run can meet
    strict = dict(TRAFFIC, check=dict(TRAFFIC["check"],
                                      rank_gap_limit=-1.0))
    for name, body in (("tinywin_closed", TRAFFIC),
                       ("tinywin_strict", strict)):
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as fh:
            json.dump(body, fh)
    with open(os.path.join(tmp, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tinywin", "source": "test",
                             "file": "benchmarks/configs/tinywin.json",
                             "reduced": [], "why": "test"})
    cells = {"tinywin.closed": "tinywin_closed",
             "tinywin.strict": "tinywin_strict"}
    for cell, traffic in cells.items():
        bench["workloads"].append({"name": cell, "config": "tinywin",
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and any(w.endswith(".ragdocs")
                                    for w in m["workloads"]):
            m["workloads"] += list(cells)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


def test_tiny_twin_end_to_end(root, lifted_gate, capsys, monkeypatch):
    # the decoder has no reference lanes: off the TPU its kernels run
    # interpreted, which the ambient dispatch switch asks for
    monkeypatch.setenv("RLT_PALLAS", "1")
    rc, out = _run(root, capsys, "tinywin.closed", 0)
    assert rc == 0, out
    line = json.loads(out[-1])
    assert line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(line["metrics"])
    window = next(l for l in out if l.startswith("[window]"))
    assert "lanes=('paged-pallas', 'paged-pallas')" in window
    # both numbers of the comparison are printed, each beside its limit
    checks = [l for l in out if l.startswith("[check]")]
    assert [l.split()[1] for l in checks] == [
        "number=widest_logit_gap", "number=ranked_request_gap"], checks
    assert "rank=2" in checks[1] and "limit=0.01" in checks[1]
    assert "per_request=[" in checks[1]


def test_the_ranked_limit_alone_refuses_a_run(root, lifted_gate, capsys,
                                              monkeypatch):
    """The same run under a ranked limit it cannot meet: the widest gap is
    within its own limit, and the run is not `correct`."""
    monkeypatch.setenv("RLT_PALLAS", "1")
    rc, out = _run(root, capsys, "tinywin.strict", 0)
    assert rc == 0, out
    widest, ranked = [l for l in out if l.startswith("[check]")]
    assert widest.endswith("correct=True") and ranked.endswith(
        "correct=False"), (widest, ranked)
    assert json.loads(out[-1])["correct"] is False


def test_a_low_rank_tells_rare_rows_from_every_row():
    """What the second number is for: requests moved by a rare row leave a
    low rank of the requests' gaps where it was; every request a little
    wrong moves it. (Readings of PERF.md section 6, PR 31: a sound run
    that met two flipped held experts, and the float8 control.)"""
    from benchmarks.harness import serve_closed_ranked as runner

    rare = [0.153, 0.571, 0.025, 0.118, 0.017, 0.0, 0.065]
    every = [0.215, 0.228, 0.255, 0.317, 0.443, 0.453, 0.511]
    assert max(rare) > max(every)              # the widest gap cannot tell
    assert runner.ranked_gap(rare, 3) == 0.025
    assert runner.ranked_gap(every, 3) == 0.255
    # fewer requests than the rank: the largest of them, never a pass
    assert runner.ranked_gap([0.3, 0.1], 3) == 0.3
    assert runner.ranked_gap([], 3) == float("inf")


def test_without_the_kernels_the_engine_refuses(root, lifted_gate, capsys,
                                                monkeypatch):
    monkeypatch.delenv("RLT_PALLAS", raising=False)
    with pytest.raises(ValueError, match="no reference"):
        _run(root, capsys, "tinywin.closed", 0)
