"""`BENCHMARK.json` against the files it names: every cell's configuration,
traffic mix, runner kind, adapter and metric reader is a file of its own,
and each reader declares the layer, unit, arrow and source its entry has."""
import json
import os
import re

import pytest

from benchmarks.harness import common

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BDIR = os.path.join(ROOT, "benchmarks")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert 1 <= BENCH["run_seconds"] <= 51
    n = 24                                   # what fits with the full 24 cells
    assert (2 + 14 * n) * (BENCH["run_seconds"] + 60) + n * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1 and e["source"] in (
            "host_clock", "device_trace")
    assert any(e["name"] == "setup_s" and "workloads" not in e
               for e in BENCH["end_to_end"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(x) for x in names)
    assert all(len(w["why"]) <= 200 for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_is_files(cell):
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    config = common.load_json(os.path.join(ROOT, entry["file"]))
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    for part in common.MODEL_PARTS:
        assert os.path.isfile(os.path.join(BDIR, part,
                                           config["model"] + ".py")), part
    traffic = common.load_json(os.path.join(BDIR, "traffic",
                                            cell["traffic"] + ".json"))
    assert os.path.isfile(os.path.join(BDIR, "harness",
                                       traffic["kind"] + ".py"))
    assert "bytes_on_chip" in traffic and "why" in traffic
    name = cell["name"]
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if name in m.get("workloads", [name])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = [m for m in BENCH["per_layer"]
              if name in m.get("workloads", [name])]
    assert layers
    assert all(m["moves"] in e2e for m in layers)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_reader_declares_what_its_entry_says(metric):
    reader = common.load_module(
        os.path.join(BDIR, "layer_metrics", metric["name"] + ".py"),
        "decl_" + metric["name"])
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"])
    assert callable(reader.reduce)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_the_reference_imports_nothing_of_the_program():
    for name in os.listdir(os.path.join(BDIR, "reference")):
        if name.endswith(".py"):
            with open(os.path.join(BDIR, "reference", name)) as fh:
                assert "ray_lightning_tpu" not in fh.read().replace(
                    "`ray_lightning_tpu`", "")


def test_the_harness_names_no_model():
    """What knows one architecture is found by a configuration's `"model"`:
    no file of `harness/`, `tools/` or `run.py` holds the name of a file in
    `models/`, so a second architecture enters with new files alone."""
    models = [n[:-3] for n in os.listdir(os.path.join(BDIR, "models"))
              if n.endswith(".py")]
    assert models
    files = [os.path.join(BDIR, "run.py")]
    for folder in ("harness", "tools"):
        files += [os.path.join(BDIR, folder, n)
                  for n in os.listdir(os.path.join(BDIR, folder))
                  if n.endswith(".py")]
    for path in files:
        with open(path) as fh:
            text = fh.read()
        assert not [m for m in models if m in text], path


def test_a_scope_enters_as_a_file(tmp_path):
    """The scopes the trace reducer knows are `scopes/<scope>.json`: a new
    file is a new scope, and one the program never opens changes nothing."""
    import shutil

    from benchmarks.harness import program_trace as pt

    today = pt.scope_names()
    assert set(today) == {"fused_ce", "optimizer", "kv_pool", "sample",
                          "lm_head", "attn", "mlp"}
    for name in today:
        body = common.load_json(os.path.join(BDIR, "scopes", name + ".json"))
        assert body["scope"] == name and body["opened_in"]
    shutil.copytree(os.path.join(BDIR, "scopes"), tmp_path / "scopes")
    (tmp_path / "scopes" / "router.json").write_text(
        '{"scope": "router", "opened_in": "a later PR"}')
    more = pt.scope_names(str(tmp_path))
    assert set(more) == set(today) | {"router"}
    path = "jit(step)/Llama/layers/while/body/mlp/router/top_k"
    assert pt.innermost_scope(path) == "mlp"
    assert pt.innermost_scope(path, more) == "router"
    # through `resolve`, as a trace's op events are read
    hlo = {"fusion.7": ("fusion", path)}
    assert pt.resolve("%fusion.7 = f32[8]{0} fusion(...)", hlo, more)[2] == \
        "router"
    assert pt.innermost_scope("jit(step)/Llama/attn/wo/dot_general",
                              more) == "attn"
    (tmp_path / "scopes" / "not a name.json").write_text("{}")
    with pytest.raises(common.BenchError):
        pt.scope_names(str(tmp_path))


def test_peaks_table_names_its_source():
    table = common.load_json(os.path.join(BDIR, "peaks.json"))
    assert "TPU v5e" in table["source"]
    assert table["device_kinds"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert table["device_kinds"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
