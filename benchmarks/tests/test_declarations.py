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
    assert os.path.isfile(os.path.join(BDIR, "models",
                                       config["model"] + ".py"))
    assert os.path.isfile(os.path.join(BDIR, "reference",
                                       config["model"] + ".py"))
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


def test_peaks_table_names_its_source():
    table = common.load_json(os.path.join(BDIR, "peaks.json"))
    assert "TPU v5e" in table["source"]
    assert table["device_kinds"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert table["device_kinds"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
