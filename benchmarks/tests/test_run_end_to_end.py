"""`run.py` end to end at a tiny preset on the CPU. The device gate is
lifted HERE, by patching `require_chips`; `run.py` has no option for it."""
import json
import os

import pytest

from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


#: `tiny2.*`: the second architecture, entered with new files alone
CELLS = ["tiny.open", "tiny.closed", "tiny.train", "tiny.fsdp",
         "tiny2.open", "tiny2.closed", "tiny2.train"]


@pytest.mark.parametrize("workload", CELLS)
def test_end_to_end_line(root, lifted_gate, capsys, workload):
    rc, out = _run(root, capsys, workload, 0)
    assert rc == 0, out
    line = json.loads(out[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert all(v["value"] > 0 for v in line["metrics"].values()), line
    assert any(l.startswith("[check]") for l in out)


@pytest.mark.parametrize("workload", ["tiny2x.open", "tiny2x.train"])
def test_a_reference_with_swapped_leaf_ids_is_not_correct(root, lifted_gate,
                                                          capsys, workload):
    rc, out = _run(root, capsys, workload, 0)
    assert rc == 0, out
    assert json.loads(out[-1])["correct"] is False, out


def test_the_second_architecture_edits_no_copied_file(root):
    """Every file of the benchmark is in the copy byte for byte; the second
    architecture is only files the benchmark does not have."""
    src = os.path.join(tiny.ROOT, "benchmarks")
    for folder, _dirs, names in os.walk(src):
        if "__pycache__" in folder or os.sep + "tests" in folder[len(src):]:
            continue
        for name in names:
            rel = os.path.relpath(os.path.join(folder, name), src)
            with open(os.path.join(folder, name), "rb") as a, \
                    open(os.path.join(root, "benchmarks", rel), "rb") as b:
                assert a.read() == b.read(), rel
    for part in ("models", "reference", "tables"):
        assert not os.path.exists(os.path.join(src, part, "two_kind.py"))
        assert os.path.isfile(os.path.join(root, "benchmarks", part,
                                           "two_kind.py"))


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result(root, capsys):
    rc, out = _run(root, capsys, "tiny.open", 0)
    assert rc != 0
    assert not any(l.startswith("{") for l in out)
