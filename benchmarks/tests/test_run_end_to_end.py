"""`run.py` end to end at a tiny preset on the CPU. The device gate is
lifted HERE, by patching `require_chips`; `run.py` has no option for it."""
import json

import pytest

from benchmarks.tests import tiny
from benchmarks.tests.tiny import run_cell as _run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


CELLS = ["tiny.open", "tiny.closed", "tiny.train", "tiny.fsdp"]


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


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result(root, capsys):
    rc, out = _run(root, capsys, "tiny.open", 0)
    assert rc != 0
    assert not any(l.startswith("{") for l in out)
