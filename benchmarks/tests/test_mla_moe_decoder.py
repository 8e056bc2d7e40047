"""The architecture `mla_moe_decoder` (latent attention, routed experts of
which a chip holds a share) as the benchmark sees it: its tables' leaves and
ids, its reference's control at a tiny size, and its tiny twin through
`run.py` on the CPU beside the throw-away cells of `tests/tiny.py`."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import common, serving, weights
from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run

ROOT = tiny.ROOT
MODEL = "mla_moe_decoder"

TINY_MLA = {
    "name": "tinymla", "source": "none: a test fixture", "model": MODEL,
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 8, "q_lora_rank": 48, "kv_lora_rank": 128,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 64, "v_head_dim": 32,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "max_position_as_run": 256, "reduced": [],
    "published": {"n_routed_experts": 16},
    "deployment": {"chips_sharing_a_layer": 2, "experts_first": 8},
    "assumed": {"initializer_std": 0.05},
}
ENGINE = {"capacity": 4, "block_size": 16, "blocks_per_slot": 4,
          "n_blocks": 17, "prefill_chunk": 16, "prefill_batch": 1}
TRAFFIC = dict(tiny.TRAFFIC["tiny_closed"], engine=ENGINE,
               require_pallas=True, check={"n_requests": 4,
                                           "gap_limit": 0.02})


def _hp():
    adapter = common.load_model_file(ROOT, "models", MODEL)
    return adapter, adapter.hyperparams(TINY_MLA, "serve")


# ---- tables -------------------------------------------------------------------


def test_layer_kinds_and_leaf_ids():
    adapter, hp = _hp()
    t = adapter.tables
    assert t.layer_kinds(hp) == ["dense", "moe", "moe"]
    dense, moe, g = (t.layer_table(hp, "dense"), t.layer_table(hp, "moe"),
                     t.global_table(hp))
    ids = lambda table: {k: v["id"] for k, v in table.items() if "id" in v}
    # an id is part of the values' key: these never change
    assert ids(dense) == {
        "q_a_proj": 200, "q_b_proj": 201, "kv_a_proj_with_mqa": 202,
        "kv_b_proj": 203, "o_proj": 204, "gate_proj": 205, "up_proj": 206,
        "down_proj": 207}
    assert ids(moe) == {
        "q_a_proj": 200, "q_b_proj": 201, "kv_a_proj_with_mqa": 202,
        "kv_b_proj": 203, "o_proj": 204, "gate": 210,
        "e_score_correction_bias": 211, "shared_gate_proj": 212,
        "shared_up_proj": 213, "shared_down_proj": 214,
        "experts_gate_proj": 215, "experts_up_proj": 216,
        "experts_down_proj": 217}
    assert ids(g) == {"embed_tokens": 300, "lm_head": 301}
    # the router keeps its published width, the experts' leaves the share
    assert moe["gate"]["shape"] == (64, 16)
    assert moe["experts_gate_proj"]["shape"] == (8, 64, 32)
    assert moe["experts_down_proj"]["shape"] == (8, 32, 64)
    assert moe["kv_b_proj"]["shape"] == (128, 8 * (32 + 32))


def test_counts_at_the_published_sizes():
    adapter = common.load_model_file(ROOT, "models", MODEL)
    config = common.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "dots.vlm1.inst.json"))
    hp = adapter.hyperparams(config, "serve")
    t = adapter.tables
    assert t.attention_params(hp) == 187_105_280          # 187.1M a block
    assert t.expert_params(hp) == 44_040_192              # 44.04M an expert
    assert t.held_params(hp) == 5_503_254_528             # 5.503B here
    assert t.mla_dims(hp) == {"heads": 128, "score_dim": 576,
                              "value_dim": 512, "head_out": 256}
    assert t.expert_layers(hp) == 5 and t.attention_layers(hp) == 6
    whole = dict(hp, num_hidden_layers=61, first_k_dense_replace=3,
                 n_routed_experts=256, vocab_size=129280)
    assert abs(t.held_params(whole) / 1e9 - 671.0) < 0.1  # the card's size


def test_the_adapters_tree_is_the_programs(monkeypatch):
    adapter, hp = _hp()
    cfg, params = adapter.serving_params(TINY_MLA, hp, 5)
    assert cfg.experts_first == 8 and cfg.held == 8
    assert cfg.n_routed_experts == 16
    experts = params["moe_layers"]["experts"]
    assert experts["router"].dtype == jnp.float32
    assert params["experts_gate_up"].dtype == jnp.bfloat16
    assert params["experts_gate_up"].shape == (2, 8, 64, 64)
    assert params["experts_down"].shape == (2, 8, 32, 64)


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
    tokens = np.random.default_rng(0).integers(0, 256, 48).astype(np.int32)
    sound, ref = _reference_logits(None, tokens)
    low, _ = _reference_logits(ref.fp8_operands, tokens)
    first = jnp.argmax(low, axis=-1)
    gap = jnp.max(sound, axis=-1) - jnp.take_along_axis(
        sound, first[:, None], axis=-1)[:, 0]
    assert float(jnp.max(gap)) > 3 * TRAFFIC["check"]["gap_limit"], gap


# ---- the tiny twin through run.py ----------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tiny.build(str(tmp_path_factory.mktemp("bench_mla")))
    bdir = os.path.join(tmp, "benchmarks")
    with open(os.path.join(bdir, "configs", "tinymla.json"), "w") as fh:
        json.dump(TINY_MLA, fh)
    with open(os.path.join(bdir, "traffic", "tinymla_closed.json"), "w") as fh:
        json.dump(TRAFFIC, fh)
    with open(os.path.join(tmp, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "tinymla", "source": "test",
                             "file": "benchmarks/configs/tinymla.json",
                             "reduced": [], "why": "test"})
    cell = "tinymla.closed"
    bench["workloads"].append({"name": cell, "config": "tinymla",
                               "traffic": "tinymla_closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and any(w.endswith(".docs")
                                    for w in m["workloads"]):
            m["workloads"].append(cell)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


def test_tiny_twin_end_to_end(root, lifted_gate, capsys, monkeypatch):
    # the decoder has no reference lanes: off the TPU its kernels run
    # interpreted, which the ambient dispatch switch asks for
    monkeypatch.setenv("RLT_PALLAS", "1")
    rc, out = _run(root, capsys, "tinymla.closed", 0)
    assert rc == 0, out
    line = json.loads(out[-1])
    assert line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(line["metrics"])
    window = next(l for l in out if l.startswith("[window]"))
    assert "lanes=('paged-pallas', 'paged-pallas')" in window


def test_without_the_kernels_the_engine_refuses(root, lifted_gate, capsys,
                                                monkeypatch):
    monkeypatch.delenv("RLT_PALLAS", raising=False)
    with pytest.raises(ValueError, match="no reference"):
        _run(root, capsys, "tinymla.closed", 0)
