"""The train step of `train.SmallThinker-21BA3B-Instruct.ctx16k` compiled
for the v5e WITHOUT a chip, from the cell's own files at its real sizes
(`tests/test_tpu_aot_compile.py:cell_step_compiled` loads the adapter the
configuration names): it holds Mosaic calls for the three flash kernels and
the grouped products both ways, fits the chip, and plans what the traffic
file says. Compile results only.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_aot_swa_moe.py -m slow -q -s

The other `test_aot_*.py` files hold the other cells' compiles and may not
be edited by a PR that adds a configuration; run the files in separate
processes (a process that has described the topology keeps libtpu's lock).
"""
import json
import os
import re

import jax
import pytest

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GIB = 1024 ** 3
CHIP_GIB = 15.75


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no libtpu, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return list(topo.devices)


@pytest.fixture
def as_on_tpu(monkeypatch):
    from ray_lightning_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def test_train_step_holds_its_kernels_and_plans_what_the_file_says(
        v5e, as_on_tpu):
    from tests.test_tpu_aot_compile import cell_step_compiled

    cell, compiled = cell_step_compiled("ctx16k", v5e)
    assert cell["name"] == "train.SmallThinker-21BA3B-Instruct.ctx16k"
    text = compiled.as_text()
    calls = lambda name: len(re.findall(
        rf"%{name}[.\w]* = [^\n]*tpu_custom_call", text))
    # one full and three window layers; the kernels' residuals are saved, so
    # no forward runs twice
    assert calls("rlt_flash_fwd") == 4
    assert calls("rlt_flash_bwd_dkdv") == 4 and calls("rlt_flash_bwd_dq") == 4
    # a layer: gate-and-up and down, forward, recomputed and for the rows'
    # cotangent (gmm), and once each for the weights' (tgmm)
    assert calls("gmm") == 4 * 6 and calls("tgmm") == 4 * 2
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes) / GIB
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "ctx16k.json")) as fh:
        planned = json.load(fh)["bytes_on_chip"]
    assert total < CHIP_GIB, f"{total:.2f} GiB does not fit a v5e chip"
    assert abs(total - planned["planned_total_gib"]) < 0.05, total
    assert abs(m.argument_size_in_bytes
               - planned["state_f32_12B_per_param"]) < 2 ** 20
